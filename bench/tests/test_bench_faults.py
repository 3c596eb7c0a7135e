"""The check fails a broken timed path: each fault a cell can have is
planted under a rehearsal run (which skips the look for a chip), and
``correct`` must come out false by the benchmark's own comparison.

Where the program gates its candidates against its own reference, the
fault is planted in that reference too, so that the program's gate
passes and only the benchmark's reference can catch it. No cell keeps
state from call to call, so "a step that returns its state unchanged"
has no cell here.
"""
from __future__ import annotations

import json

import pytest
from bench_spec import RANKS_CELL

ALTER_SPMV = """
import repro.kernels.spmv.ops as ops, repro.kernels.spmv.ref as ref
mv, mv_ref = ops.ell_matvec, ref.ell_matvec_ref
def fault(y):
    return {body}
ops.ell_matvec = lambda *a, **k: fault(mv(*a, **k))
ref.ell_matvec_ref = lambda *a, **k: fault(mv_ref(*a, **k))
"""

ALTER_FLASH = """
import repro.kernels.flash_attention.ops as ops
import repro.kernels.flash_attention.ref as ref
mha, attention_ref = ops.mha, ref.attention_ref
fault = lambda o: {body}
ops.mha = lambda *a, **k: fault(mha(*a, **k))
ref.attention_ref = lambda *a, **k: fault(attention_ref(*a, **k))
"""

NO_EXCHANGE = """
import jax.numpy as jnp
import repro.spmv.distributed as dist
dist._halo_exchange = lambda xb, axis="ranks": jnp.zeros(
    (2 * xb.shape[0],), xb.dtype)
"""

ONE_ANSWER = ALTER_SPMV.format(body="y.at[3].add(1.0)")
HALF_ROWS = ALTER_SPMV.format(body="y.at[y.shape[0] // 2:].set(0.0)")
ONE_OUTPUT = ALTER_FLASH.format(body="o.at[0, 0, 5, 0].add(0.5)")
HALF_HEADS = ALTER_FLASH.format(body="o.at[:, o.shape[1] // 2:].set(0.0)")

CASES = [
    ("spmv_paper.call", "answer_altered", ONE_ANSWER, "spmv_row_err"),
    ("spmv_paper.call", "half_rows_left_out", HALF_ROWS, "spmv_row_err"),
    ("spmv_paper.tune", "answer_altered", ONE_ANSWER, "spmv_row_err"),
    ("spmv_paper.tune", "half_rows_left_out", HALF_ROWS, "spmv_row_err"),
    ("dsmoe16b_attn.tune", "answer_altered", ONE_OUTPUT, "attn_max_abs"),
    ("dsmoe16b_attn.tune", "half_heads_left_out", HALF_HEADS,
     "attn_rel_rms"),
    (RANKS_CELL, "answer_altered", ONE_ANSWER, "spmv_row_err"),
    (RANKS_CELL, "half_rows_left_out", HALF_ROWS, "spmv_row_err"),
    (RANKS_CELL, "exchange_left_out", NO_EXCHANGE, "spmv_row_err"),
]


@pytest.mark.parametrize("cell,fault,prelude,check", CASES,
                         ids=[f"{c}-{f}" for c, f, _, _ in CASES])
def test_fault_makes_the_run_incorrect(bench, cell, fault, prelude, check):
    rc, out, err = bench(cell, prelude=prelude)
    assert rc == 0, err[-3000:]
    res = json.loads(out.strip().splitlines()[-1])
    assert res["failed"] == 0, "the program's own gate caught it first"
    assert res["correct"] is False
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]

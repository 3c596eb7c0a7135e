"""JAX token-chained executor: schedule invariance property."""
import random

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core as C


@pytest.fixture(scope="module")
def setup():
    g = C.spmv_dag(rows_per_rank=32, nnz_per_rank=128)
    scheds = list(C.enumerate_schedules(g, 2))
    rng = np.random.default_rng(0)
    AL = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
    AR = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
    xL = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    impls = {
        "Pack": C.op_impl(lambda x: x * 1.0, ["xL"], ["sendbuf"]),
        "PostSend": C.op_impl(lambda b: b, ["sendbuf"], ["wire"]),
        "PostRecv": C.op_impl(lambda: jnp.zeros((8,), jnp.float32),
                              [], ["recvbuf"]),
        "WaitSend": C.op_impl(lambda w: w, ["wire"], ["sent"]),
        "WaitRecv": C.op_impl(lambda w, r: w + r, ["wire", "recvbuf"],
                              ["xR"]),
        "yL": C.op_impl(lambda x: AL @ x, ["xL"], ["yL"]),
        "yR": C.op_impl(lambda x: AR @ x, ["xR"], ["yR"]),
    }
    env0 = {"xL": xL}
    ref_run = C.build_runner(g, scheds[0], impls)
    ref = np.asarray(ref_run(env0)["yL"] + ref_run(env0)["yR"])
    return g, scheds, impls, env0, ref


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_schedule_invariance(setup, seed):
    """Every valid (order x stream) implementation computes the same
    values — the sync insertion must be sufficient for correctness."""
    g, scheds, impls, env0, ref = setup
    s = random.Random(seed).choice(scheds)
    out = C.build_runner(g, s, impls)(env0)
    np.testing.assert_allclose(np.asarray(out["yL"] + out["yR"]), ref,
                               rtol=1e-6)


def test_executor_jit_compiles(setup):
    g, scheds, impls, env0, ref = setup
    run = C.jit_runner(g, scheds[-1], impls)
    out = run(env0)
    np.testing.assert_allclose(np.asarray(out["yL"] + out["yR"]), ref,
                               rtol=1e-6)

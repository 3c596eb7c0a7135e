"""The MLA decode cell's own parts: the benchmark's copy of the instance
is the program's draw, and the work count the roofline divides by
depends on the configuration's sizes and on no block choice."""
from __future__ import annotations

import copy
import itertools
import json

import numpy as np
import pytest
from bench_spec import ROOT

from harness import mla_work
from reference import mla_decode as ref

CONFIG = json.loads((ROOT / "bench" / "configs"
                     / "moonlight_mla_decode.json").read_text())


@pytest.mark.parametrize("seed", [[7, 0], [2**31 + 5, 1, 3], 2**33 + 1])
def test_reference_instance_is_the_programs_draw(seed):
    import repro.search  # noqa: F401  (before repro.space: import order)
    from repro.kernels.autotune import mla_decode_instance
    from repro.kernels.mla_decode.ops import log_uniform_lengths

    qs, caches = mla_decode_instance(4, 16, 576, 256, seed, layers=2)
    for layer in range(2):
        np.testing.assert_array_equal(ref.queries(4, 16, 576, seed, layer),
                                      qs[layer])
        np.testing.assert_array_equal(ref.cache(4, 576, 256, seed, layer),
                                      caches[layer])
    np.testing.assert_array_equal(ref.lengths(128, 1024, 8192, 16),
                                  log_uniform_lengths(128, 1024, 8192, 16))


def test_work_count_depends_on_no_block_choice():
    """A call of the cell (4 layers) moves 2.06 GB and takes 61.4 GFLOP
    whatever block the tuner picks: the count is of live tokens, and
    blocking them (each group's blocks up to its longest sequence, each
    block cut at each sequence's length) covers the same tokens."""
    sizes = {k: v for k, v in CONFIG.items() if k != "rehearsal"}
    lengths = mla_work.shape(sizes)["lengths"]
    tokens = 441_226
    assert lengths.sum() == tokens and sizes["num_hidden_layers"] == 4
    assert mla_work.mla_decode_bytes(sizes) == 4 * (
        tokens * 576 * 2 + 128 * 16 * 576 * 2 + 128 * 16 * 512 * 4)
    assert mla_work.mla_decode_flops(sizes) == (
        4 * 2 * 16 * tokens * (576 + 512))
    assert mla_work.least_time_s("TPU v5 lite", sizes) == pytest.approx(
        4 * 628.3e-6, rel=1e-3)
    for bk, bb in itertools.product(sizes["block_k"], sizes["block_b"]):
        one = copy.deepcopy(sizes)
        one.update(block_k=[bk], block_b=[bb])
        assert (mla_work.mla_decode_bytes(one), mla_work.mla_decode_flops(
            one)) == (mla_work.mla_decode_bytes(sizes),
                      mla_work.mla_decode_flops(sizes))
        covered = 0
        for g in range(0, len(lengths), bb):
            group = lengths[g:g + bb]
            for start in range(0, int(group.max()), bk):
                covered += int(np.clip(group - start, 0, bk).sum())
        assert covered == tokens

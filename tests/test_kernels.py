"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.pack import ops as pack_ops
from repro.kernels.spmv import ops as spmv_ops
from repro.spmv.matrix import band_matrix


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-5


@pytest.mark.parametrize("n,k,dtype", [
    (64, 1, jnp.float32),
    (300, 7, jnp.float32),       # non-aligned rows and K
    (512, 8, jnp.float32),
    (1024, 16, jnp.bfloat16),
    (2048, 5, jnp.bfloat16),
])
def test_ell_matvec_sweep(n, k, dtype):
    rng = np.random.default_rng(n + k)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    cols = rng.integers(0, n, size=(n, k)).astype(np.int32)
    x = rng.standard_normal(n).astype(np.float32)
    ref = np.asarray(spmv_ops.ell_matvec_ref(
        jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(x)))
    out = spmv_ops.ell_matvec(jnp.asarray(vals, dtype),
                              jnp.asarray(cols),
                              jnp.asarray(x, dtype))
    scale = np.abs(ref).max() + 1e-6
    assert np.abs(np.asarray(out) - ref).max() / scale < _tol(dtype)


@pytest.mark.parametrize("n,k,hb,block_r", [
    (256, 4, 32, 64),
    (512, 8, 64, 128),
    (384, 3, 48, 128),    # n not a multiple of block_r
])
def test_ell_onehot_sweep(n, k, hb, block_r):
    rng = np.random.default_rng(n)
    offs = rng.integers(-hb, hb + 1, size=(n, k))
    cols = ((np.arange(n)[:, None] + offs) % n).astype(np.int32)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    x = rng.standard_normal(n).astype(np.float32)
    ref = np.asarray(spmv_ops.ell_matvec_ref(
        jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(x)))
    out = spmv_ops.ell_matvec_onehot(
        jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(x),
        half_bandwidth=hb, block_r=block_r)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                               atol=1e-4)


def test_kernels_agree_on_paper_matrix():
    """Reduced version of the paper's band matrix through both paths."""
    A = band_matrix(n=2048, nnz=16384, half_bandwidth=512, seed=7)
    x = np.random.default_rng(1).standard_normal(2048).astype(np.float32)
    ref = A.matvec(x)
    y1 = spmv_ops.ell_matvec(jnp.asarray(A.vals), jnp.asarray(A.cols),
                             jnp.asarray(x))
    y2 = spmv_ops.ell_matvec_onehot(
        jnp.asarray(A.vals), jnp.asarray(A.cols), jnp.asarray(x),
        half_bandwidth=512, block_r=128)
    np.testing.assert_allclose(np.asarray(y1), ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y2), ref, rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 9),
       st.sampled_from([33, 100, 256]))
def test_ell_matvec_property(seed, k, n):
    rng = np.random.default_rng(seed)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    cols = rng.integers(0, n, size=(n, k)).astype(np.int32)
    x = rng.standard_normal(n).astype(np.float32)
    ref = (vals.astype(np.float64) * x.astype(np.float64)[cols]).sum(1)
    out = spmv_ops.ell_matvec(jnp.asarray(vals), jnp.asarray(cols),
                              jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("n,m", [(128, 64), (1000, 333), (4096, 1024)])
def test_pack_sweep(n, m):
    rng = np.random.default_rng(m)
    x = rng.standard_normal(n).astype(np.float32)
    idx = rng.integers(0, n, size=m).astype(np.int32)
    out = pack_ops.pack(jnp.asarray(x), jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(out), x[idx])


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_pack_property(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(16, 512))
    m = int(rng.integers(1, 300))
    x = rng.standard_normal(n).astype(np.float32)
    idx = rng.integers(0, n, size=m).astype(np.int32)
    out = pack_ops.pack(jnp.asarray(x), jnp.asarray(idx))
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(pack_ops.pack_ref(
            jnp.asarray(x), jnp.asarray(idx))))


# -- flash attention ------------------------------------------------------------

from repro.kernels.flash_attention import ops as fa_ops  # noqa: E402


@pytest.mark.parametrize("b,h,s,d,dtype", [
    (2, 3, 256, 64, jnp.float32),
    (1, 2, 300, 64, jnp.float32),      # non-block-aligned seq
    (2, 2, 256, 128, jnp.bfloat16),
    (1, 2, 64, 48, jnp.float32),       # lane-padded head dim
])
def test_flash_attention_causal_sweep(b, h, s, d, dtype):
    rng = np.random.default_rng(s + d)
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    out = fa_ops.mha(q, k, v, causal=True)
    ref = fa_ops.attention_ref(q, k, v, causal=True)
    err = float(jnp.abs(out.astype(jnp.float32) -
                        ref.astype(jnp.float32)).max())
    assert err < (3e-2 if dtype == jnp.bfloat16 else 1e-5), err


def test_flash_attention_cross_noncausal():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    out = fa_ops.mha(q, k, v, causal=False)
    ref = fa_ops.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


def test_flash_attention_decode_alignment():
    """Right-aligned causal: queries are the last Sq of the kv seq."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 1, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 384, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 384, 64)), jnp.float32)
    out = fa_ops.mha(q, k, v, causal=True)
    ref = fa_ops.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


@pytest.mark.parametrize("block_q,block_k", [(16, 32), (32, 16)])
def test_flash_attention_causal_unequal_blocks(block_q, block_k):
    """Causal self-attention whose q and kv blocks differ: the block
    table's staircase steps by more than one kv block per q block, or
    stays on one kv block for several q blocks."""
    rng = np.random.default_rng(block_q + 2 * block_k)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 128, 64)),
                           jnp.float32) for _ in range(3))
    out = fa_ops.mha(q, k, v, causal=True, block_q=block_q,
                     block_k=block_k)
    ref = fa_ops.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


@pytest.mark.parametrize("block_q,block_k", [(16, 32), (32, 16)])
def test_flash_attention_decode_alignment_unequal_blocks(block_q,
                                                         block_k):
    """Right-aligned causal at unequal blocks: the table's staircase is
    shifted by the 256 keys before the first query."""
    rng = np.random.default_rng(3 * block_q + block_k)
    q = jnp.asarray(rng.standard_normal((1, 1, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 384, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 384, 64)), jnp.float32)
    out = fa_ops.mha(q, k, v, causal=True, block_q=block_q,
                     block_k=block_k)
    ref = fa_ops.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


# -- the flash kernel's block table ---------------------------------------------

from repro.kernels.flash_attention import kernel as fa_kernel  # noqa: E402


def _padded_table(sq, skv, block_q, block_k, causal):
    """The table ``mha`` hands the kernel: lengths padded to the blocks
    at the end, queries right-aligned on the keys."""
    sq_p, skv_p = sq + (-sq) % block_q, skv + (-skv) % block_k
    q_offset = skv_p - sq_p
    table = fa_kernel.block_table(sq_p // block_q, skv_p // block_k,
                                  block_q=block_q, block_k=block_k,
                                  q_offset=q_offset, causal=causal)
    return table, sq_p // block_q, skv_p // block_k, q_offset


@pytest.mark.parametrize("sq,skv,block_q,block_k,causal", [
    (256, 256, 32, 32, True),
    (256, 256, 16, 64, True),        # block_q < block_k
    (256, 256, 64, 16, True),        # block_q > block_k
    (128, 384, 16, 32, True),        # right-aligned, q_offset 256
    (128, 384, 32, 16, True),
    (96, 200, 32, 8, True),          # q_offset 104, not a block multiple
    (300, 300, 64, 64, True),        # both padded to 320
    (100, 100, 16, 16, True),        # both padded to 112
    (128, 256, 32, 64, False),
    (256, 128, 64, 32, False),
])
def test_flash_block_table_matches_brute_force(sq, skv, block_q, block_k,
                                               causal):
    """Every (query, key) pair of every block, checked one by one: the
    table lists exactly the blocks with a visible pair, by q block and
    then kv block; each q block finishes on its last listed kv block,
    which is ``last(qi)``; and a block straddles exactly where it holds
    visible and masked pairs both."""
    table, n_q, n_kv, q_offset = _padded_table(sq, skv, block_q, block_k,
                                               causal)
    want = []
    for qi in range(n_q):
        q_pos = qi * block_q + np.arange(block_q)[:, None] + q_offset
        for ki in range(n_kv):
            k_pos = ki * block_k + np.arange(block_k)[None, :]
            visible = (k_pos <= q_pos) if causal else np.ones(
                (block_q, block_k), bool)
            if visible.any():
                want.append((qi, ki, not visible.all()))
    got = list(zip(table.qi.tolist(), table.ki.tolist(),
                   ((table.flags & fa_kernel.STRADDLES) != 0).tolist()))
    assert got == want
    finishes = (table.flags & fa_kernel.FINISHES) != 0
    for qi in range(n_q):
        last = n_kv - 1
        if causal:
            last = min(n_kv - 1,
                       (qi * block_q + block_q - 1 + q_offset) // block_k)
        mine = np.flatnonzero(table.qi == qi)
        assert table.ki[mine[-1]] == last
        assert finishes[mine].tolist() == [False] * (mine.size - 1) + [True]
    assert table.qi.dtype == table.ki.dtype == table.flags.dtype == np.int32


@pytest.mark.parametrize("block_q,block_k,steps,straddling", [
    (32, 32, 8256, 128),
    (32, 64, 4160, 128), (64, 32, 4160, 128),
    (32, 128, 2112, 128), (128, 32, 2112, 128),
    (64, 64, 2080, 64),
    (64, 128, 1056, 64), (128, 64, 1056, 64),
    (128, 128, 528, 32),
])
def test_flash_block_table_at_the_attention_cells_shape(block_q, block_k,
                                                        steps, straddling):
    """Steps per head of each candidate of the 4,096-position causal
    attention cell, against the rectangle's (32 x 32 blocks: 16,384)."""
    table = fa_kernel.block_table(4096 // block_q, 4096 // block_k,
                                  block_q=block_q, block_k=block_k,
                                  q_offset=0, causal=True)
    assert table.qi.size == steps
    assert np.count_nonzero(table.flags & fa_kernel.STRADDLES) == straddling

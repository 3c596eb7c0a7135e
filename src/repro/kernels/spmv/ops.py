"""jit'd public wrappers for the ELL SpMV kernels."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.spmv import kernel as _k
from repro.kernels.spmv.ref import ell_matvec_ref  # re-export for callers

__all__ = ["ell_matvec", "ell_matvec_onehot", "ell_matvec_ref"]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def ell_matvec(vals: jax.Array, cols: jax.Array, x: jax.Array,
               block_n: int = 512,
               interpret: bool | None = None) -> jax.Array:
    """y = A x for ELL (vals, cols) row-major (N, K) and dense x.

    Gather via XLA's gather HLO (TPU-native for wide/irregular column
    sets), fused multiply-reduce in Pallas (ELL-T layout). The gather
    takes K-major indices: with row-major (N, K) indices, XLA's TPU
    compiler spends over a minute on the paper's 150k x 10 matrix.
    """
    xg_t = x[cols.T]          # (K, N)
    vals_t = vals.T
    return _k.ell_mulsum(vals_t, xg_t, block_n=block_n,
                         interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("half_bandwidth", "block_r",
                                    "interpret"))
def ell_matvec_onehot(vals: jax.Array, cols: jax.Array, x: jax.Array,
                      half_bandwidth: int, block_r: int = 256,
                      interpret: bool | None = None) -> jax.Array:
    """Narrow-band ELL SpMV with the in-kernel one-hot gather.

    Valid when every column is within ``half_bandwidth`` of its row
    (circular metric). Window width = 2*half_bandwidth + block_r.
    """
    n, k = vals.shape
    hb = half_bandwidth
    pad_n = (-n) % block_r
    if pad_n:
        vals = jnp.concatenate(
            [vals, jnp.zeros((pad_n, k), vals.dtype)], axis=0)
        cols = jnp.concatenate(
            [cols, jnp.arange(n, n + pad_n, dtype=cols.dtype)[:, None]
             .repeat(k, 1) % x.shape[0]], axis=0)
    np_ = n + pad_n
    n_x = x.shape[0]

    # Wrap-padded x: index p = original + hb.
    x_pad = jnp.concatenate([x[n_x - hb:], x, x[:hb]])
    w = 2 * hb + block_r
    nblocks = np_ // block_r
    starts = jnp.arange(nblocks) * block_r
    x_windows = jax.vmap(
        lambda s: jax.lax.dynamic_slice(x_pad, (s,), (w,)))(starts)

    # Window-relative columns: offset in [-hb, hb] circularly, then
    # position within the block's window.
    rows = jnp.arange(np_, dtype=jnp.int32)[:, None]
    offset = (cols.astype(jnp.int32) - rows % n_x + hb) % n_x - hb
    block_start = (rows // block_r) * block_r
    cols_win = offset + hb + (rows - block_start)

    y = _k.ell_onehot_mv(vals.T, cols_win.T.astype(jnp.int32), x_windows,
                         block_r=block_r, interpret=interpret)
    return y[:n]

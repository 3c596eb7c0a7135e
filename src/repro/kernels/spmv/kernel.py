"""Pallas TPU kernels for ELL sparse-matrix x vector products.

Hardware adaptation (see DESIGN.md): the paper's cuSPARSE-style SpMV is
gather-bound. TPUs have no per-lane hardware gather, so a mechanical port
is wrong. The TPU-native decomposition is:

  * ``ell_mulsum``  — the arithmetic half: y = sum_k vals[k] * x_gathered[k]
    as a lane-parallel fused multiply-reduce over a K-major ("ELL-T")
    layout: vals_t (K, N) so the short K axis sits on sublanes and the
    long row axis on lanes. The gather itself is done by XLA's gather HLO
    (efficient on TPU for VMEM/HBM-resident vectors) in the ops wrapper.
    ``block_n`` is a lane dim: Mosaic accepts multiples of 128 only.

  * ``ell_onehot_mv`` — a fully in-kernel variant for *narrow-band*
    matrices: each row-block's columns fall in a width-W window, so the
    gather is cast as a one-hot matmul against the window — an MXU-
    friendly pattern. Wasteful for the paper's wide band (W ~ n/2;
    overhead ~W/K), ideal for W <~ 1024; the ops wrapper picks per input.

Both are validated against ref.py in interpret mode across shape/dtype
sweeps (tests/test_kernels_spmv.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

LANES = 128
SUBLANES = 8


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Kernel A: fused multiply-reduce over pre-gathered operands (ELL-T layout)
# ---------------------------------------------------------------------------

def _mulsum_body(vals_ref, xg_ref, y_ref):
    v = vals_ref[...].astype(jnp.float32)
    g = xg_ref[...].astype(jnp.float32)
    y_ref[...] = jnp.sum(v * g, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def ell_mulsum(vals_t: jax.Array, xg_t: jax.Array,
               block_n: int = 512,
               interpret: bool | None = None) -> jax.Array:
    """y (N,) = sum over K of vals_t (K, N) * xg_t (K, N).

    K is padded to the sublane tile, N to ``block_n`` (lane-aligned).
    """
    k, n = vals_t.shape
    kp = _round_up(max(k, 1), SUBLANES)
    np_ = _round_up(n, block_n)
    vals_p = jnp.zeros((kp, np_), vals_t.dtype).at[:k, :n].set(vals_t)
    xg_p = jnp.zeros((kp, np_), xg_t.dtype).at[:k, :n].set(xg_t)

    grid = (np_ // block_n,)
    out = pl.pallas_call(
        _mulsum_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((kp, block_n), lambda j: (0, j)),
            pl.BlockSpec((kp, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(vals_p, xg_p)
    return out[0, :n]


# ---------------------------------------------------------------------------
# Kernel B: in-kernel gather via one-hot MXU matmul (narrow-band windows)
# ---------------------------------------------------------------------------

def _onehot_body(vals_ref, cols_ref, xwin_ref, y_ref, *, block_r: int,
                 window: int, k: int):
    xw = xwin_ref[0, 0, :].astype(jnp.float32)       # (W,)
    acc = jnp.zeros((block_r,), jnp.float32)
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_r, window), 1)
    for kk in range(k):  # K is small and static: unrolled
        c = cols_ref[kk, :]                          # (R,) int32
        v = vals_ref[kk, :].astype(jnp.float32)      # (R,)
        onehot = (iota == c[:, None]).astype(jnp.float32)   # (R, W)
        gathered = jax.lax.dot_general(
            onehot, xw[:, None], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[:, 0]
        acc = acc + v * gathered
    y_ref[...] = acc[None, None, :]


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def ell_onehot_mv(vals_t: jax.Array, cols_win_t: jax.Array,
                  x_windows: jax.Array, block_r: int = 256,
                  interpret: bool | None = None) -> jax.Array:
    """Narrow-band SpMV with in-kernel one-hot gather.

    vals_t / cols_win_t: (K, N) ELL-T; cols are *window-relative* per row
    block (see ops.ell_matvec_onehot). x_windows: (N // block_r, W) — the
    width-W slice of (wrap-padded) x covering each row block's columns.

    The per-block window and output row carry a unit middle axis, so each
    block's last two dims equal the array's ((1, W) and (1, block_r)):
    Mosaic needs the second-minor block dim to be a multiple of 8 or the
    whole dim, which a (1, W) block of an (nblocks, W) array is not.
    """
    k, n = vals_t.shape
    nblocks, w = x_windows.shape
    assert n % block_r == 0 and nblocks == n // block_r
    kp = _round_up(max(k, 1), SUBLANES)
    vals_p = jnp.zeros((kp, n), vals_t.dtype).at[:k].set(vals_t)
    # Padding rows gather window slot 0 with val 0: harmless.
    cols_p = jnp.zeros((kp, n), jnp.int32).at[:k].set(cols_win_t)

    out = pl.pallas_call(
        functools.partial(_onehot_body, block_r=block_r, window=w, k=kp),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((kp, block_r), lambda b: (0, b)),
            pl.BlockSpec((kp, block_r), lambda b: (0, b)),
            pl.BlockSpec((1, 1, w), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_r), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, 1, block_r),
                                       jnp.float32),
        interpret=resolve_interpret(interpret),
    )(vals_p, cols_p, x_windows.reshape(nblocks, 1, w))
    return out.reshape(n)

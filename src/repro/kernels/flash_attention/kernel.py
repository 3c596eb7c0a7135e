"""Pallas TPU flash attention (forward): blockwise online softmax.

The canonical TPU structure (cf. jax.experimental.pallas.ops.tpu):

  grid = (B*H, Sq/block_q, Skv/block_k)   — kv is the MINOR grid dim,
  so for a fixed (bh, q-block) the kernel visits kv blocks in order,
  carrying the online-softmax state (m, l, acc) in VMEM scratch and
  writing the normalized output on the last kv step. Block shapes are
  MXU-aligned (block_q x d and block_k x d tiles; d is a multiple of
  128 for the assigned archs' head dims or padded by ops.py).

Causal masking is positional (q block offset vs kv block offset), so
fully-masked blocks contribute nothing (and `ops.py` never visits kv
blocks strictly above the diagonal: the kv grid extent is set to the
full Skv, masking handles the rest — a production version would use a
triangular grid; noted in EXPERIMENTS as future perf headroom).

Validated against ref.py in interpret mode (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_body(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                block_q: int, block_k: int, n_kv: int, scale: float,
                causal: bool, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale        # (bq, d)
    k = k_ref[0].astype(jnp.float32)                # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        q_pos = qi * block_q + \
            jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
            + q_offset
        k_pos = ki * block_k + \
            jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if causal:
        p = jnp.where(k_pos <= q_pos, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret",
                     "scale"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, scale: float | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """q: (BH, Sq, D); k, v: (BH, Skv, D) — heads pre-flattened.

    Sq/Skv must divide by the block sizes (ops.py pads); causal
    alignment assumes the queries are the LAST Sq positions of the kv
    sequence (standard decode/prefill layout).
    """
    bh, sq, d = q.shape
    skv = k.shape[1]
    assert sq % block_q == 0 and skv % block_k == 0
    n_q = sq // block_q
    n_kv = skv // block_k
    # NOTE: when the head dim is lane-padded by ops.py, the true scale
    # must come from the caller (the padded d would skew the softmax).
    scale = d ** -0.5 if scale is None else scale

    return pl.pallas_call(
        functools.partial(
            _flash_body, block_q=block_q, block_k=block_k, n_kv=n_kv,
            scale=scale, causal=causal, q_offset=skv - sq),
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),   # output accum
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)

"""Pallas TPU flash attention (forward): blockwise online softmax.

The grid walks a block table, not the (q block, kv block) rectangle:

  grid = (B*H, T)   — T is the number of (q block, kv block) pairs that
  hold at least one visible (query, key) pair. For causal attention
  those are, for each q block ``qi`` in order, the kv blocks
  ``0 … last(qi)``, where ``last(qi)`` holds the key of the block's last
  query; no step is spent on a block wholly above the diagonal. Without
  ``causal`` the table lists the whole rectangle, kv-minor.

:func:`block_table` builds the table on the host from static shapes,
and it is scalar-prefetched into SMEM (``pltpu.PrefetchScalarGridSpec``):
the index maps read each step's q and kv block from it, and the body
reads two flags. Consecutive steps share a q block, so the kernel visits
its kv blocks in order, carrying the online-softmax state (m, l, acc) in
VMEM scratch with the output block resident; it initialises on kv block
0 and normalises and writes on the q block's last kv block.

Only the blocks that straddle the diagonal, holding visible and masked
pairs both, build the positional mask; a block wholly below it runs the
same arithmetic unmasked (``where(True, s, …)`` is the identity). Block
shapes are MXU-aligned (block_q x d and block_k x d tiles; d is a
multiple of 128 or padded by ops.py).

Validated against ref.py in interpret mode (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
FINISHES = 1     # flag: the q block's last kv block (normalise and write)
STRADDLES = 2    # flag: the block holds visible and masked pairs


class BlockTable(NamedTuple):
    """One entry per grid step (per head): its q block, its kv block,
    and its flags (``FINISHES``, ``STRADDLES``), each int32 (T,)."""
    qi: np.ndarray
    ki: np.ndarray
    flags: np.ndarray


def block_table(n_q: int, n_kv: int, *, block_q: int, block_k: int,
                q_offset: int, causal: bool) -> BlockTable:
    """The (q block, kv block) pairs the grid visits, ordered by q block
    and then kv block. Query row r of q block ``qi`` sits at position
    ``qi * block_q + r + q_offset`` and sees the keys at or before it.

    Every q block keeps kv block 0, so its output is written even where
    ``q_offset < 0`` leaves rows with no key to see.
    """
    qi = np.arange(n_q)
    first_q = qi * block_q + q_offset            # each block's first query
    last = np.full(n_q, n_kv - 1)
    if causal:
        last = np.clip((first_q + block_q - 1) // block_k, 0, n_kv - 1)
    counts = last + 1
    q_of = np.repeat(qi, counts)
    k_of = np.arange(counts.sum()) - np.repeat(np.cumsum(counts) - counts,
                                               counts)
    flags = np.where(k_of == last[q_of], FINISHES, 0)
    if causal:
        # the block's last key lies past its first query
        flags |= np.where((k_of + 1) * block_k - 1 > first_q[q_of],
                          STRADDLES, 0)
    return BlockTable(q_of.astype(np.int32), k_of.astype(np.int32),
                      flags.astype(np.int32))


def _flash_body(qi_ref, ki_ref, flags_ref, q_ref, k_ref, v_ref, o_ref,
                m_scr, l_scr, acc_scr, *, block_q: int, block_k: int,
                scale: float, causal: bool, q_offset: int):
    t = pl.program_id(1)
    qi, ki, flags = qi_ref[t], ki_ref[t], flags_ref[t]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def update(visible):
        """One kv block's online-softmax step; ``visible`` (bq, bk) masks
        the scores, or is None where every pair is visible."""
        q = q_ref[0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0].astype(jnp.float32)                # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if visible is not None:
            s = jnp.where(visible, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if visible is not None:
            p = jnp.where(visible, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        straddles = (flags & STRADDLES) != 0

        @pl.when(straddles)
        def _masked():
            q_pos = qi * block_q + \
                jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
                + q_offset
            k_pos = ki * block_k + \
                jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            update(k_pos <= q_pos)

        @pl.when(jnp.logical_not(straddles))
        def _below():
            update(None)
    else:
        update(None)

    @pl.when((flags & FINISHES) != 0)
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
    q_offset = skv - sq
    table = block_table(sq // block_q, skv // block_k, block_q=block_q,
                        block_k=block_k, q_offset=q_offset, causal=causal)
    # NOTE: when the head dim is lane-padded by ops.py, the true scale
    # must come from the caller (the padded d would skew the softmax).
    scale = d ** -0.5 if scale is None else scale

    def q_block(b, t, qi_ref, ki_ref, flags_ref):
        return (b, qi_ref[t], 0)

    def kv_block(b, t, qi_ref, ki_ref, flags_ref):
        return (b, ki_ref[t], 0)

    return pl.pallas_call(
        functools.partial(
            _flash_body, block_q=block_q, block_k=block_k, scale=scale,
            causal=causal, q_offset=q_offset),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, table.qi.size),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_block),
                pl.BlockSpec((1, block_k, d), kv_block),
                pl.BlockSpec((1, block_k, d), kv_block),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), q_block),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
                pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
                pltpu.VMEM((block_q, d), jnp.float32),   # output accum
            ]),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(*(jnp.asarray(a) for a in table), q, k, v)

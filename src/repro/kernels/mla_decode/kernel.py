"""Pallas TPU kernel: latent attention (MLA) at decode over a ragged
batch of shared latent caches.

With the up-projections absorbed (DeepSeek-V2, arXiv:2405.04434 §2.1),
one decode step of one layer reads, per sequence, a single cache vector
per token: ``[c (value_dim) | k_rope]``, the RMS-normed latent followed
by the rotated positional key. Every head's query is a vector of that
same width, so all heads of a sequence share its cache:

  scores_h = (q_h · col) * scale        over the sequence's tokens
  out_h    = softmax(scores_h) · col[:value_dim]

The cache is stored feature-major, (B, D, S_max): token t of sequence b
is ``cache[b, :, t]``. A TPU tiles an array's minor dimension in 128
lanes, so a token-major (B, S_max, 576) cache would pad each row to 640;
XLA's default layout for that shape puts S_max minor instead, and a
kernel that wants rows would then pay a full relayout copy of the cache
on every call. Feature-major, the minor dimension is the token axis,
the 576 features are whole sublane tiles, and nothing is padded.

grid = (B / block_b, S_max / block_k), the kv axis minor: for one group
of ``block_b`` sequences the kernel visits cache blocks in order and
carries the online-softmax state (m, l, acc) in VMEM scratch, as the
flash kernel does. The sequences' lengths are scalar-prefetched:

- each sequence masks its scores at its own length, so tokens past it
  (a reused cache's stale entries) never reach the output;
- a kv step wholly past the group's longest sequence computes nothing
  (``pl.when``), and its ``index_map`` clamps to the group's last valid
  block, so the pipeline sees an unchanged block index and issues no
  DMA for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _group_len(len_ref, g, block_b: int):
    """The longest of group ``g``'s ``block_b`` lengths."""
    out = len_ref[g * block_b]
    for b in range(1, block_b):
        out = jnp.maximum(out, len_ref[g * block_b + b])
    return out


def _last_block(len_ref, g, block_b: int, block_k: int):
    """Index of the cache block holding group ``g``'s last valid token."""
    return jnp.maximum(_group_len(len_ref, g, block_b) - 1, 0) // block_k


def _live(start, length):
    """Whether a kv step starting at token ``start`` holds any of a
    sequence's ``length`` tokens."""
    return start < length


def _masked(s, start, length):
    """Scores (H, bk) with the tokens at or past ``length`` set to a
    large negative, so stale cache entries get no probability."""
    pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(pos < length, s, NEG_INF)


def _values(cols, value_dim: int):
    """The value part of a cache block (D, bk): the latent ``c``."""
    return cols[:value_dim]


def _mla_body(len_ref, q_ref, c_ref, o_ref, m_scr, l_scr, acc_scr, *,
              block_b: int, block_k: int, n_kv: int, scale: float,
              value_dim: int):
    g = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = j * block_k
    for b in range(block_b):          # static: one sequence at a time
        length = len_ref[g * block_b + b]

        @pl.when(_live(start, length))
        def _step(b=b, length=length):
            q = q_ref[b]                                   # (H, D)
            cols = c_ref[b]                                # (D, bk)
            s = jax.lax.dot_general(
                q, cols, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (H, bk)
            s = _masked(s, start, length)
            m_prev = m_scr[b]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[b] = l_scr[b] * corr + p.sum(axis=-1, keepdims=True)
            acc_scr[b] = acc_scr[b] * corr + jax.lax.dot_general(
                p.astype(cols.dtype), _values(cols, value_dim),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[b] = m_new

    @pl.when(j == n_kv - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] /
                      jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _vmem_bytes(heads: int, width: int, value_dim: int, *, block_k: int,
               block_b: int, itemsize: int = 2) -> int:
    """VMEM one grid step holds: the double-buffered query, cache and
    output blocks, the f32 scratch, and one sequence's scores and
    probabilities (lanes padded to 128)."""
    lanes = -(-width // 128) * 128
    cache = 2 * block_b * width * block_k * itemsize
    q = 2 * block_b * heads * lanes * itemsize
    out = 2 * block_b * heads * value_dim * 4
    scratch = block_b * heads * (value_dim + 2 * 128) * 4
    scores = 4 * heads * block_k * 4
    return cache + q + out + scratch + scores


@functools.partial(
    jax.jit,
    static_argnames=("block_k", "block_b", "scale", "value_dim",
                     "interpret"))
def mla_decode(q: jax.Array, cache: jax.Array, lengths: jax.Array, *,
               block_k: int, block_b: int, scale: float,
               value_dim: int = 512,
               interpret: bool | None = None) -> jax.Array:
    """Absorbed MLA decode attention.

    q: (B, H, D) absorbed queries ``[W_uk^T q_nope | q_rope]``; cache:
    (B, D, S_max), feature-major, token t's vector ``[c | k_rope]`` in
    ``cache[b, :, t]``; lengths: (B,) int32, each in
    [1, S_max]. Returns (B, H, value_dim) float32: each head's softmax
    over its sequence's first ``lengths[b]`` tokens, applied to their
    first ``value_dim`` features (``W_uv`` is applied afterwards). B must
    divide by ``block_b`` and S_max by ``block_k``.
    """
    bsz, heads, width = q.shape
    s_max = cache.shape[2]
    if bsz % block_b or s_max % block_k:
        raise ValueError(
            f"batch {bsz} and cache length {s_max} must divide by "
            f"block_b={block_b} and block_k={block_k}")
    n_kv = s_max // block_k

    def cache_block(g, j, len_ref):
        return (g, 0, jnp.minimum(j, _last_block(len_ref, g, block_b,
                                                 block_k)))

    need = _vmem_bytes(heads, width, value_dim, block_k=block_k,
                      block_b=block_b, itemsize=cache.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_mla_body, block_b=block_b, block_k=block_k,
                          n_kv=n_kv, scale=scale, value_dim=value_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz // block_b, n_kv),
            in_specs=[
                pl.BlockSpec((block_b, heads, width),
                             lambda g, j, len_ref: (g, 0, 0)),
                pl.BlockSpec((block_b, width, block_k), cache_block),
            ],
            out_specs=pl.BlockSpec((block_b, heads, value_dim),
                                   lambda g, j, len_ref: (g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_b, heads, 1), jnp.float32),   # m
                pltpu.VMEM((block_b, heads, 1), jnp.float32),   # l
                pltpu.VMEM((block_b, heads, value_dim), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((bsz, heads, value_dim),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, need + (8 << 20))),
        interpret=resolve_interpret(interpret),
        name="mla_decode",
    )(lengths.astype(jnp.int32), q, cache)

"""jit'd public wrapper for the flash-attention kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref

__all__ = ["mha", "attention_ref"]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"))
def mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
        causal: bool = True, block_q: int = 128, block_k: int = 128,
        interpret: bool | None = None) -> jax.Array:
    """Multi-head attention. q: (B, H, Sq, D); k, v: (B, H, Skv, D).

    Pads Sq/Skv up to the block sizes (padded kv masked by position,
    padded q rows sliced off) and D up to the 128-lane tile.
    """
    b, h, sq, d = q.shape
    skv = k.shape[2]
    pq = (-sq) % block_q
    pk = (-skv) % block_k
    pd = (-d) % 128
    if pd:
        zq = ((0, 0), (0, 0), (0, 0), (0, pd))
        q, k, v = (jnp.pad(x, zq) for x in (q, k, v))
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    dp = d + pd
    qf = q.reshape(b * h, sq + pq, dp)
    kf = k.reshape(b * h, skv + pk, dp)
    vf = v.reshape(b * h, skv + pk, dp)
    # With right-aligned causal masking, padded q rows sit BELOW the real
    # rows and padded kv columns sit after the diagonal — the causal mask
    # must exclude padded kv for real queries, which it does because
    # padded kv positions > every real query position when pk rows are
    # appended at the end. Scale of padded columns is irrelevant for
    # non-causal ONLY if masked; so non-causal inputs must be pre-padded
    # by the caller (ops asserts).
    assert causal or (pq == 0 and pk == 0), \
        "non-causal requires block-aligned shapes"
    # Right-aligned causal offset is computed from padded shapes; equal
    # padding on both sides preserves it (block_q == block_k and
    # sq == skv, the training/prefill self-attention case).
    assert not causal or pq == pk, \
        "causal padding requires pq == pk (use equal blocks, sq == skv)"
    out = flash_attention(qf, kf, vf, causal=causal, block_q=block_q,
                          block_k=block_k, scale=d ** -0.5,
                          interpret=interpret)
    out = out.reshape(b, h, sq + pq, dp)
    return out[:, :, :sq, :d]

"""Pure-jnp oracle for the flash-attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True) -> jnp.ndarray:
    """q: (B, H, Sq, D); k, v: (B, H, Skv, D). Softmax in f32.

    Both products run at HIGHEST precision: at the default, a TPU
    multiplies f32 in bf16 passes, and the oracle would be the least
    accurate party to the comparison.
    """
    b, h, sq, d = q.shape
    skv = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=_HIGHEST) * (d ** -0.5)
    if causal:
        # Aligned on the right: query i attends keys <= i + (Skv - Sq).
        qi = jnp.arange(sq)[:, None] + (skv - sq)
        ki = jnp.arange(skv)[None, :]
        s = jnp.where(ki <= qi, s, -jnp.inf)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                     precision=_HIGHEST)
    return out.astype(q.dtype)

"""Latent attention (MLA) at decode, the benchmark's own copy: the
instance drawn from a seed, the plain absorbed reference, and its
float8 control.

The instance is drawn on the default device with ``jax.random``, draw
for draw as the program's ``mla_decode`` space draws it, so an instance
made there from a seed is this instance: per layer l, queries (B, H, D)
and a feature-major latent cache (B, D, S_max), bfloat16 standard
normals, from keys 2l and 2l + 1 folded into the key that folds in both
32-bit halves of each seed. The lengths
follow the configuration's rule and not the seed.

The reference is the absorbed form, ``softmax(q_h · cache[b, :, t] *
scale)`` over ``t < lengths[b]`` applied to ``cache[b, :value_dim, t]``,
in float32 at HIGHEST precision, a block of sequences at a time so that
a block's float32 copy of the cache fits beside the instance. Tokens
past a length are zeroed and their scores set to -inf before use. The
control rounds the queries and the cache to float8 e4m3 on the host
(``ml_dtypes``), one precision below the configuration's bfloat16, for
the reason ``reference/attention.py`` gives.
"""
from __future__ import annotations

import functools
import math

import ml_dtypes
import numpy as np

BLOCK = 8          # sequences per reference block


def key(seed):
    import jax

    k = jax.random.key(0)
    for s in (seed if isinstance(seed, (list, tuple)) else [seed]):
        s = int(s)
        k = jax.random.fold_in(k, s & 0xFFFFFFFF)
        k = jax.random.fold_in(k, s >> 32)
    return k


def queries(batch: int, heads: int, width: int, seed, layer: int):
    """Layer ``layer``'s q (B, H, D) bfloat16, on the default device."""
    import jax
    import jax.numpy as jnp

    return jax.random.normal(jax.random.fold_in(key(seed), 2 * layer),
                             (batch, heads, width), jnp.bfloat16)


def cache(batch: int, width: int, s_max: int, seed, layer: int):
    """Layer ``layer``'s latent cache (B, D, S_max) bfloat16, on the
    default device."""
    import jax
    import jax.numpy as jnp

    return jax.random.normal(jax.random.fold_in(key(seed), 2 * layer + 1),
                             (batch, width, s_max), jnp.bfloat16)


def lengths(batch: int, low: int, high: int, order_seed: int) -> np.ndarray:
    """The quantiles (i + 0.5) / batch of log-uniform [low, high],
    rounded, in the slots of ``order_seed``'s permutation."""
    u = (np.arange(batch) + 0.5) / batch
    out = np.rint(low * (high / low) ** u).astype(np.int32)
    return out[np.random.default_rng(order_seed).permutation(batch)]


@functools.lru_cache(maxsize=None)
def _block_fn(scale: float, value_dim: int):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def block(q, c, n):
        c = c.astype(jnp.float32)
        valid = jnp.arange(c.shape[2])[None, :] < n[:, None]   # (b, S)
        c = jnp.where(valid[:, None, :], c, 0.0)
        s = jnp.einsum("bhd,bds->bhs", q.astype(jnp.float32), c,
                       precision=hi) * scale
        s = jnp.where(valid[:, None, :], s, -jnp.inf)
        p = jnp.exp(s - s.max(axis=-1, keepdims=True))
        p = p / p.sum(axis=-1, keepdims=True)
        return jnp.einsum("bhs,bds->bhd", p, c[:, :value_dim], precision=hi)

    return jax.jit(block)


def attention(q, c, n, scale: float, value_dim: int):
    """(B, H, value_dim) float32 reference of the instance (q, c, n)."""
    import jax.numpy as jnp

    f = _block_fn(float(scale), int(value_dim))
    n = jnp.asarray(n, jnp.int32)
    step = math.gcd(q.shape[0], BLOCK)
    return jnp.concatenate([f(q[b:b + step], c[b:b + step], n[b:b + step])
                            for b in range(0, q.shape[0], step)])


def rounded(x, dtype: str):
    """``x`` rounded to ``dtype`` on the host, a block of sequences at a
    time, and put back on the device in its own dtype (float8 values
    are exact in bfloat16)."""
    import jax.numpy as jnp

    step = math.gcd(x.shape[0], BLOCK)
    return jnp.concatenate([
        jnp.asarray(np.asarray(x[b:b + step]).astype(getattr(
            ml_dtypes, dtype)).astype(x.dtype))
        for b in range(0, x.shape[0], step)])

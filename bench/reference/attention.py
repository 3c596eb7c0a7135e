"""Plain causal multi-head attention in jax.numpy: the reference, the
comparison, and the lower-precision control.

The reference multiplies at HIGHEST precision (float32 on a TPU; the
default there is one bfloat16 pass) with the softmax in float32, one
head at a time so that one S x S score matrix is alive at once.
The control is the same computation with its matmul inputs (q, k, v)
rounded to the format one step below the configuration's stated matmul
precision: float8 e4m3 below bfloat16. The rounding is done on the host
(``ml_dtypes``): inside a jitted program the TPU compiler may drop a
float32 -> float8 -> float32 round trip as excess precision, and the
control then reads what the reference does.
"""
from __future__ import annotations

import functools

import ml_dtypes
import numpy as np

# One step below each stated precision (float8 e4m3 below bfloat16).
BELOW = {"float64": "float32", "float32": "bfloat16",
         "bfloat16": "float8_e4m3fn"}


def instance(batch: int, heads: int, seq: int, head_dim: int, seed
             ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """q, k, v (B, H, S, D) float32 standard normals, drawn in that
    order from one generator seeded with ``seed``."""
    rng = np.random.default_rng(seed)
    shape = (batch, heads, seq, head_dim)
    return tuple(rng.standard_normal(shape).astype(np.float32)
                 for _ in range(3))


@functools.lru_cache(maxsize=None)
def _head_fn():
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def head(q, k, v):
        s = jnp.dot(q, k.T, precision=hi) * (q.shape[-1] ** -0.5)
        n = s.shape[0]
        s = jnp.where(jnp.arange(n)[None, :] <= jnp.arange(n)[:, None],
                      s, -jnp.inf)
        p = jnp.exp(s - s.max(axis=-1, keepdims=True))
        p = p / p.sum(axis=-1, keepdims=True)
        return jnp.dot(p, v, precision=hi)

    return jax.jit(head)


def attention(q, k, v, round_to: str | None = None):
    """Causal attention of (B, H, S, D) host arrays on the default
    device; ``round_to`` rounds the matmul inputs to that dtype first."""
    import jax.numpy as jnp

    if round_to is not None:
        dtype = getattr(ml_dtypes, round_to, None) or np.dtype(round_to)
        q, k, v = (np.asarray(t).astype(dtype).astype(np.float32)
                   for t in (q, k, v))
    f = _head_fn()
    q, k, v = (jnp.asarray(t) for t in (q, k, v))
    b, h = q.shape[:2]
    return jnp.stack([jnp.stack([f(q[i, j], k[i, j], v[i, j])
                                 for j in range(h)]) for i in range(b)])


def gaps(out, ref) -> dict[str, float]:
    """max |out - ref|, and ||out - ref|| / ||ref|| over all elements."""
    import jax.numpy as jnp

    d = jnp.asarray(out, jnp.float32) - ref
    return {"max_abs": float(jnp.abs(d).max()),
            "rel_rms": float(jnp.sqrt(jnp.sum(d * d) / jnp.sum(ref * ref)))}

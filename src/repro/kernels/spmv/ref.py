"""Pure-jnp oracle for the ELL SpMV kernels."""
from __future__ import annotations

import jax.numpy as jnp


def ell_matvec_ref(vals: jnp.ndarray, cols: jnp.ndarray,
                   x: jnp.ndarray) -> jnp.ndarray:
    """y[i] = sum_k vals[i, k] * x[cols[i, k]].

    Padding convention: padded entries have vals == 0 (cols may point
    anywhere valid), so they contribute nothing. The gather takes
    K-major indices (see ``ops.ell_matvec``); the values are the same.
    """
    return jnp.sum(vals * x[cols.T].T, axis=1)


def ell_matvec_f32_ref(vals, cols, x):
    return ell_matvec_ref(vals.astype(jnp.float32), cols,
                          x.astype(jnp.float32))

"""Plain SpMV: the float64 reference, its comparison, and the bfloat16
control that stands in the program's place.

The comparison is per row, against the row's own scale
``sum_k |a_ik| |x_col|``: a float32 product of K terms is off by at
most about K float32 roundings of that scale, whatever the signs, so
the reading is independent of cancellation in any one row.
"""
from __future__ import annotations

import functools

import numpy as np


def matvec(vals: np.ndarray, cols: np.ndarray, x: np.ndarray) -> np.ndarray:
    """y = A x in float64 over the stored entries (padding is 0)."""
    return (vals.astype(np.float64) * x.astype(np.float64)[cols]).sum(axis=1)


def row_error(y: np.ndarray, vals: np.ndarray, cols: np.ndarray,
              x: np.ndarray) -> float:
    """max over rows of |y - A x| / sum_k |a_ik x_col| (float64)."""
    ref = matvec(vals, cols, x)
    scale = (np.abs(vals.astype(np.float64))
             * np.abs(x.astype(np.float64))[cols]).sum(axis=1)
    err = np.abs(np.asarray(y, dtype=np.float64).reshape(ref.shape) - ref)
    return float((err / np.maximum(scale, np.finfo(np.float64).tiny)).max())


def control(vals: np.ndarray, cols: np.ndarray, x: np.ndarray) -> np.ndarray:
    """The reference computed in bfloat16 (the step below the
    configuration's float32), on the default device."""
    y = _control_t()(vals.T, cols.T, x)
    return np.asarray(y, dtype=np.float32)


@functools.lru_cache(maxsize=None)
def _control_t():
    import jax
    import jax.numpy as jnp

    def control_t(vals_t, cols_t, x):
        # K-major (K, N) operands: XLA's TPU compiler takes over a
        # minute on a row-major gather of the paper's size.
        v = vals_t.astype(jnp.bfloat16)
        g = x.astype(jnp.bfloat16)[cols_t]
        return jnp.sum(v * g, axis=0, dtype=jnp.bfloat16)
    return jax.jit(control_t)

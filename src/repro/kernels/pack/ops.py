"""jit'd public wrapper for the pack kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.pack.kernel import pack as _pack_kernel
from repro.kernels.pack.ref import pack_ref

__all__ = ["pack", "pack_ref"]


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack(x: jax.Array, idx: jax.Array,
         interpret: bool | None = None) -> jax.Array:
    return _pack_kernel(x, idx, interpret=interpret)

"""Pallas TPU pack kernel: out[j] = x[idx[j]].

The paper's Pack vertex gathers x entries into per-neighbor send buffers.
For band matrices the halo is contiguous (a slice — no kernel needed);
for irregular index sets the TPU-idiomatic gather is a chunked one-hot
matmul: stream x through VMEM in width-CH chunks, build the (C, CH)
one-hot of the indices that fall in the chunk, and accumulate the MXU
product. No per-lane hardware gather is required.

Cost: O(C * n) MACs per C outputs — worth it on TPU when the index set is
irregular and x is VMEM-resident (n up to ~1M f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

LANES = 128


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pack_body(idx_ref, x_ref, out_ref, *, chunk: int, n_chunks: int,
               block_c: int):
    idx = idx_ref[0, :]                                  # (C,) int32
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_c, chunk), 1)

    def step(c, acc):
        c0 = c * chunk
        xw = x_ref[0, pl.ds(c0, chunk)].astype(jnp.float32)   # (CH,)
        rel = idx[:, None] - c0
        onehot = (iota == rel).astype(jnp.float32)            # (C, CH)
        return acc + jax.lax.dot_general(
            onehot, xw[:, None], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[:, 0]

    acc = jax.lax.fori_loop(0, n_chunks,
                            step, jnp.zeros((block_c,), jnp.float32))
    out_ref[...] = acc[None, :]


@functools.partial(jax.jit,
                   static_argnames=("block_c", "chunk", "interpret"))
def pack(x: jax.Array, idx: jax.Array, block_c: int = 256,
         chunk: int = 1024, interpret: bool | None = None) -> jax.Array:
    """Gather x[idx] with the chunked one-hot kernel."""
    n = x.shape[0]
    m = idx.shape[0]
    np_ = _round_up(n, chunk)
    mp = _round_up(m, block_c)
    x_p = jnp.zeros((1, np_), x.dtype).at[0, :n].set(x)
    idx_p = jnp.full((1, mp), -1, jnp.int32).at[0, :m].set(
        idx.astype(jnp.int32))

    out = pl.pallas_call(
        functools.partial(_pack_body, chunk=chunk,
                          n_chunks=np_ // chunk, block_c=block_c),
        grid=(mp // block_c,),
        in_specs=[
            pl.BlockSpec((1, block_c), lambda b: (0, b)),
            pl.BlockSpec((1, np_), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c), lambda b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((1, mp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(idx_p, x_p)
    return out[0, :m].astype(x.dtype)

"""Public entry points for MLA decode attention: the kernel, one
decode step's attention over several layers, the softmax scale of an
MLA layer, and the ragged lengths of a decode batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.mla_decode.kernel import mla_decode
from repro.kernels.mla_decode.ref import mla_decode_ref

__all__ = ["mla_decode", "mla_decode_layers", "mla_decode_ref",
           "softmax_scale", "log_uniform_lengths"]


@functools.partial(
    jax.jit,
    static_argnames=("block_k", "block_b", "scale", "value_dim",
                     "interpret"))
def mla_decode_layers(qs, caches, lengths, *, block_k: int, block_b: int,
                      scale: float, value_dim: int = 512,
                      interpret: bool | None = None) -> jax.Array:
    """One decode step's attention over a chip's layers: the kernel on
    each layer's queries and latent cache (sequences of ``qs`` and
    ``caches``, as :func:`mla_decode` takes them), all at the batch's
    ``lengths``, in one dispatch. Returns the layers' outputs stacked,
    (layers, B, H, value_dim): one buffer for the host to wait on, where
    a tuple of four cost a v5e host 0.4 ms more a call (PERF.md)."""
    return jnp.stack([mla_decode(q, c, lengths, block_k=block_k,
                                 block_b=block_b, scale=scale,
                                 value_dim=value_dim, interpret=interpret)
                      for q, c in zip(qs, caches)])


def softmax_scale(qk_nope_head_dim: int, qk_rope_head_dim: int) -> float:
    """``(nope + rope) ** -0.5``: the scale of an MLA layer without YaRN
    (``rope_scaling`` null), 192 ** -0.5 for DeepSeek-V2/V3 widths."""
    return float((qk_nope_head_dim + qk_rope_head_dim) ** -0.5)


def log_uniform_lengths(batch: int, low: int, high: int,
                        order_seed: int) -> np.ndarray:
    """(batch,) int32 cache lengths: the quantiles ``(i + 0.5) / batch``
    of a log-uniform distribution over [low, high], rounded to whole
    tokens and placed in batch slots by the permutation that
    ``order_seed`` fixes. Every draw of the instance does the same work;
    the permutation leaves neighbouring slots (one kernel group) of
    unlike lengths, as a serving batch has them."""
    if not 1 <= low <= high:
        raise ValueError(f"lengths need 1 <= low <= high, got {low}, "
                         f"{high}")
    u = (np.arange(batch) + 0.5) / batch
    lengths = np.rint(low * (high / low) ** u).astype(np.int32)
    return lengths[np.random.default_rng(order_seed).permutation(batch)]

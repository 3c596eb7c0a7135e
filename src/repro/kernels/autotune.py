"""Parameter design spaces for the repo's own Pallas kernels.

The ROADMAP's "autotune the repo's own stack" item: the block/tile
sizes hard-coded in :mod:`repro.kernels` become searchable
:class:`~repro.space.params.ParamSpace` instances, evaluated through
the param-space ``wallclock`` backend (:class:`repro.engine.params.
KernelWallclockEvaluator` — value-correctness gate against the
kernel's reference implementation, batch-ahead compilation, persistent
:class:`~repro.engine.store.EvalStore` warm starts) and distilled into
per-platform block-size design rules by :func:`repro.rules.distill`.

Each factory closes the kernel over one fixed, seeded problem instance,
drawn on the host (a ``space.instance`` span) and then copied to the
device (``space.put``); ``mla_decode``'s gigabyte-sized instance is
drawn on the device instead (``space.instance`` with ``on="device"``),
where a host draw would take seconds. The instance is part of the
space — its shape/seed go into the ``signature`` hashed by the store
fingerprint, so measurements from different instances never alias.
Shapes default small enough that the interpret-mode (CPU) sweep stays
in test budgets; pass bigger ones for a real tuning run on TPU.
``interpret=None`` (the default) compiles the kernels everywhere but
on the CPU backend (:func:`repro.kernels.resolve_interpret`).

These constructors import JAX; :mod:`repro.space` registers them
lazily (``make_space("flash_attention")``) so the protocol layer stays
importable on JAX-free installs.
"""
from __future__ import annotations

import numpy as np

from repro import obs
from repro.space.params import KernelRunner, ParamSpace

__all__ = ["flash_attention_space", "spmv_mulsum_space", "pack_space",
           "mla_decode_space", "mla_decode_instance", "prng_key"]


def _put(*host: np.ndarray) -> tuple:
    """``space.put``: the instance's copy to the device, as issued. The
    copy may run on past the span, beside the evaluator's set-up; the
    first call that reads the arrays waits for it."""
    import jax.numpy as jnp

    with obs.span("space.put", bytes=sum(a.nbytes for a in host)):
        return tuple(jnp.asarray(a) for a in host)


def _divisors_of(seq: int, values) -> tuple[int, ...]:
    out = tuple(int(v) for v in values if seq % int(v) == 0)
    if not out:
        raise ValueError(
            f"no candidate block size in {tuple(values)} divides "
            f"{seq}")
    return out


def flash_attention_space(*, batch: int = 1, heads: int = 2,
                          seq: int = 128, head_dim: int = 64,
                          block_values=(16, 32, 64, 128),
                          causal: bool = True, seed: int = 0,
                          interpret: bool | None = None) -> ParamSpace:
    """(block_q, block_k) grid for :func:`repro.kernels.
    flash_attention.ops.mha` on one seeded self-attention instance.

    Block values are filtered to divisors of ``seq`` so the padded and
    unpadded paths measure the same problem (and causal right-aligned
    masking needs equal q/kv padding anyway).
    """
    from repro.kernels.flash_attention.kernel import block_table
    from repro.kernels.flash_attention.ops import mha
    from repro.kernels.flash_attention.ref import attention_ref

    blocks = _divisors_of(seq, block_values)
    with obs.span("space.instance") as sp:
        rng = np.random.default_rng(seed)
        host = [rng.standard_normal(                # q, k, v in turn
            (batch, heads, seq, head_dim)).astype(np.float32)
            for _ in range(3)]
        sp.set(bytes=sum(a.nbytes for a in host))
    q, k, v = _put(*host)

    def build(params: dict):
        bq, bk = params["block_q"], params["block_k"]
        # A call's grid steps, against the full (q block, kv block)
        # rectangle's: the blocks divide ``seq``, so nothing is padded.
        n_q, n_kv = seq // bq, seq // bk
        steps = block_table(n_q, n_kv, block_q=bq, block_k=bk, q_offset=0,
                            causal=causal).qi.size
        obs.counter("flash.grid_steps").add(batch * heads * steps)
        obs.counter("flash.grid_steps_dense").add(batch * heads * n_q * n_kv)

        def run():
            return mha(q, k, v, causal=causal, block_q=bq,
                       block_k=bk, interpret=interpret)
        return run

    return ParamSpace(
        "flash_attention",
        [("block_q", blocks), ("block_k", blocks)],
        # Compiled, the kernel's f32 dots run at Mosaic's default
        # contract precision, below f32: its largest |kernel -
        # reference| on a TPU v5e at 1x15x2048x64 was 8.533e-3 (max
        # |reference| 3.8; PERF.md has the same run at HIGHEST), so the
        # gate allows 2e-2. Planted masking and softmax-state faults
        # miss by more than 10x that (tests/test_kernel_autotune.py).
        runner=KernelRunner(
            build=build,
            reference=lambda: attention_ref(q, k, v, causal=causal),
            atol=2e-2),
        signature=(f"mha:b={batch}:h={heads}:sq={seq}:skv={seq}:"
                   f"d={head_dim}:causal={causal}:dtype=float32:"
                   f"seed={seed}"))


def spmv_mulsum_space(*, n: int = 1024, k: int = 8,
                      block_values=(128, 256, 512, 1024),
                      seed: int = 0,
                      interpret: bool | None = None) -> ParamSpace:
    """block_n grid for the ELL SpMV fused multiply-reduce
    (:func:`repro.kernels.spmv.ops.ell_matvec`) on one seeded instance
    of the paper's matrix (:func:`repro.spmv.matrix.band_matrix`: ``n``
    rows, ``k`` non-zeros each, uniform in a circulant band of
    half-width ``n // 4``).

    ``block_n`` is the kernel's lane dim, so the default grid holds
    multiples of 128 only; any other value is refused by the chip's
    compiler, and that candidate fails its measurement loudly.
    """
    from repro.kernels.spmv.ops import ell_matvec
    from repro.kernels.spmv.ref import ell_matvec_ref
    from repro.spmv.matrix import band_matrix

    with obs.span("space.instance") as sp:
        a = band_matrix(n, n * k, seed=seed)
        x = np.random.default_rng(seed).standard_normal(n).astype(
            np.float32)
        sp.set(bytes=a.vals.nbytes + a.cols.nbytes + x.nbytes)
    vals, cols, x = _put(a.vals, a.cols, x)

    def build(params: dict):
        bn = params["block_n"]

        def run():
            return ell_matvec(vals, cols, x, block_n=bn,
                              interpret=interpret)
        return run

    return ParamSpace(
        "spmv_mulsum",
        [("block_n", tuple(int(v) for v in block_values))],
        runner=KernelRunner(
            build=build,
            reference=lambda: ell_matvec_ref(vals, cols, x)),
        signature=(f"ell_matvec:band:n={n}:k={k}:dtype=float32:"
                   f"seed={seed}"))


def pack_space(*, n: int = 4096, m: int = 512,
               block_c_values=(128, 256, 512),
               chunk_values=(256, 512, 1024),
               seed: int = 0,
               interpret: bool | None = None) -> ParamSpace:
    """(block_c, chunk) grid for the chunked one-hot gather kernel
    (:func:`repro.kernels.pack.kernel.pack`) on one seeded index set.

    Tunes the kernel directly (the :mod:`repro.kernels.pack.ops`
    wrapper pins the kernel defaults) — a winning rule here is exactly
    what that wrapper should adopt per platform.
    """
    from repro.kernels.pack.kernel import pack as pack_kernel
    from repro.kernels.pack.ref import pack_ref

    with obs.span("space.instance") as sp:
        rng = np.random.default_rng(seed)
        x = rng.standard_normal(n).astype(np.float32)
        idx = rng.integers(0, n, size=m).astype(np.int32)
        sp.set(bytes=x.nbytes + idx.nbytes)
    x, idx = _put(x, idx)

    def build(params: dict):
        bc, ch = params["block_c"], params["chunk"]

        def run():
            return pack_kernel(x, idx, block_c=bc, chunk=ch,
                               interpret=interpret)
        return run

    return ParamSpace(
        "pack",
        [("block_c", tuple(int(v) for v in block_c_values)),
         ("chunk", tuple(int(v) for v in chunk_values))],
        runner=KernelRunner(
            build=build,
            reference=lambda: pack_ref(x, idx)),
        signature=f"pack:n={n}:m={m}:dtype=float32:seed={seed}")


def prng_key(seed):
    """A ``jax.random`` key from a seed or a sequence of seeds, each a
    whole number in [0, 2**64): both 32-bit halves of each are folded in
    (``jax.random.key`` alone would drop the high half)."""
    import jax

    key = jax.random.key(0)
    for s in (seed if isinstance(seed, (list, tuple)) else [seed]):
        s = int(s)
        if not 0 <= s < 1 << 64:
            raise ValueError(f"seed {s} is not in [0, 2**64)")
        key = jax.random.fold_in(key, s & 0xFFFFFFFF)
        key = jax.random.fold_in(key, s >> 32)
    return key


def mla_decode_instance(batch: int, heads: int, width: int, s_max: int,
                        seed, layers: int = 1) -> tuple[list, list]:
    """Per layer, queries q (batch, heads, width) and a feature-major
    latent cache (batch, width, s_max): bfloat16 standard normals drawn
    on the default device from ``seed``, layer l's q from key 2l and its
    cache from key 2l + 1 folded into :func:`prng_key`."""
    import jax
    import jax.numpy as jnp

    key = prng_key(seed)
    qs, caches = [], []
    for layer in range(layers):
        qs.append(jax.random.normal(jax.random.fold_in(key, 2 * layer),
                                    (batch, heads, width), jnp.bfloat16))
        caches.append(jax.random.normal(
            jax.random.fold_in(key, 2 * layer + 1), (batch, width, s_max),
            jnp.bfloat16))
    return qs, caches


def mla_decode_space(*, layers: int = 1, batch: int = 4, heads: int = 16,
                     kv_lora_rank: int = 512, qk_rope_head_dim: int = 64,
                     qk_nope_head_dim: int = 128, s_max: int = 256,
                     min_len: int = 16, max_len: int = 256,
                     order_seed: int = 0,
                     block_k_values=(128, 256, 512, 1024, 2048),
                     block_b_values=(1, 2, 4, 8), seed=0,
                     interpret: bool | None = None) -> ParamSpace:
    """(block_k, block_b) grid for absorbed MLA decode attention
    (:func:`repro.kernels.mla_decode.ops.mla_decode`) over one ragged
    decode batch, bfloat16 as deployed: a candidate's run is one decode
    step's attention over ``layers`` layers
    (:func:`~repro.kernels.mla_decode.ops.mla_decode_layers`), one
    kernel call a layer in one dispatch.

    The instance is drawn on the device from ``seed``
    (:func:`mla_decode_instance`, D = kv_lora_rank + qk_rope_head_dim);
    every token past a sequence's length holds a draw too, as a reused
    cache holds stale entries. The lengths are
    :func:`~repro.kernels.mla_decode.ops.log_uniform_lengths` over
    [min_len, max_len], fixed by ``order_seed`` and not by ``seed``.
    Block values are filtered to divisors of ``s_max`` and ``batch``.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.mla_decode.ops import (log_uniform_lengths,
                                              mla_decode_layers,
                                              mla_decode_ref, softmax_scale)

    if max_len > s_max:
        raise ValueError(f"max_len {max_len} exceeds the cache's {s_max}")
    block_k = _divisors_of(s_max, block_k_values)
    block_b = _divisors_of(batch, block_b_values)
    width = kv_lora_rank + qk_rope_head_dim
    scale = softmax_scale(qk_nope_head_dim, qk_rope_head_dim)
    with obs.span("space.instance", on="device") as sp:
        qs, caches = mla_decode_instance(batch, heads, width, s_max, seed,
                                         layers)
        lengths = jnp.asarray(log_uniform_lengths(batch, min_len, max_len,
                                                  order_seed))
        jax.block_until_ready((qs, caches, lengths))
        sp.set(bytes=sum(a.nbytes for a in qs + caches) + lengths.nbytes)

    def build(params: dict):
        bk, bb = params["block_k"], params["block_b"]

        def run():
            return mla_decode_layers(qs, caches, lengths, block_k=bk,
                                     block_b=bb, scale=scale,
                                     value_dim=kv_lora_rank,
                                     interpret=interpret)
        return run

    return ParamSpace(
        "mla_decode",
        [("block_k", block_k), ("block_b", block_b)],
        # The kernel rounds its probabilities to bfloat16 for the value
        # dot, so its error grows as sequences shorten: largest |kernel
        # - reference| over the 20 candidates on a TPU v5e at 128 x
        # 8,192 (lengths 1,024-8,192) was 7.93e-4, and 2.5e-3 on the
        # CPU at lengths 16-256. The gate allows 5e-3; the float8
        # control misses by 0.11 at the chip's size, and the planted
        # masking, value-feature and scale faults by more than 10x
        # (tests/test_mla_decode.py; PERF.md).
        runner=KernelRunner(
            build=build,
            reference=lambda: jnp.stack([
                mla_decode_ref(q, c, lengths, scale, value_dim=kv_lora_rank)
                for q, c in zip(qs, caches)]),
            atol=5e-3),
        signature=(f"mla_decode:layers={layers}:b={batch}:h={heads}:"
                   f"d={width}:dv={kv_lora_rank}:s_max={s_max}:"
                   f"lengths=loguniform("
                   f"{min_len},{max_len},order={order_seed}):"
                   f"scale={scale!r}:dtype=bfloat16:seed={seed}"))

"""Plain ``jax.numpy`` references for latent attention (MLA) at decode,
in float32 at ``Precision.HIGHEST`` (on a TPU the default multiplies f32
in bfloat16 passes), with no kernel and no batching across sequences.

- :func:`mla_decode_ref`: the absorbed form the kernel computes, one
  sequence at a time. The value gate compares every candidate with it.
- :func:`mla_attention_ref`: the published, unabsorbed layer for one
  decode token from hidden states (DeepSeek-V2, arXiv:2405.04434 §2.1,
  the equations Moonlight-16B-A3B's ``deepseek_v3`` layers follow with
  ``q_lora_rank`` null). :func:`latent_cache`, :func:`decode_query`,
  :func:`absorb_query` and :func:`apply_uv` cut it at the kernel's
  inputs and output, so the kernel path can be tested against it.

Departures from the paper, each immaterial to what is compared:

- no YaRN: Moonlight's ``rope_scaling`` is null, so RoPE is plain and
  the softmax scale is ``(qk_nope + qk_rope) ** -0.5``, with no mscale;
- RoPE rotates halves (``[x1, x2] -> [x1 cos - x2 sin, x2 cos + x1
  sin]``); the published code first de-interleaves the rope features,
  a fixed permutation of ``W``'s rope columns that seeded weights do
  not see;
- the query is projected from the hidden state directly
  (``q_lora_rank`` null), so there is no query latent or its norm.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("scale", "value_dim"))
def _sequence_ref(q, cache, length, *, scale: float, value_dim: int):
    """One sequence: q (H, D), cache (D, S_max) feature-major. Tokens at
    or past ``length`` are dropped before anything reads them: their
    vectors are zeroed and their scores set to -inf. That is what
    slicing ``cache[:, :length]`` computes, at one compiled shape for
    every length."""
    cache = cache.astype(jnp.float32)
    valid = jnp.arange(cache.shape[1]) < length
    cache = jnp.where(valid[None, :], cache, 0.0)
    s = jnp.dot(q.astype(jnp.float32), cache, precision=HI) * scale
    s = jnp.where(valid[None, :], s, -jnp.inf)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    return jnp.dot(p, cache[:value_dim].T, precision=HI)


def mla_decode_ref(q, cache, lengths, scale: float,
                   value_dim: int = 512) -> jax.Array:
    """The absorbed form, sequence by sequence: q (B, H, D), cache
    (B, D, S_max) feature-major, lengths (B,) each in [1, S_max].
    Returns (B, H, value_dim) float32: for each head, the softmax of
    ``q_h · cache[b, :, t] * scale`` over ``t < lengths[b]`` applied to
    ``cache[b, :value_dim, t]``."""
    lengths = jnp.asarray(lengths, jnp.int32)
    return jnp.stack([
        _sequence_ref(q[b], cache[b], lengths[b], scale=scale,
                      value_dim=value_dim)
        for b in range(q.shape[0])])


# -- the published layer, unabsorbed ----------------------------------------

def rms_norm(x, weight, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rope(x, positions, theta: float):
    """Rotary embedding of the last axis of ``x`` (..., T, R) at
    ``positions`` (T,), rotating halves."""
    r = x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.asarray(positions, jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def init_weights(key, *, hidden: int, heads: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int) -> dict:
    """Seeded float32 weights of one MLA layer, scaled by fan-in.

    ``w_dkv`` (hidden, kv_lora_rank + rope): the joint down-projection
    to the latent and the shared positional key; ``kv_norm``: the
    latent's RMSNorm weight; ``w_q`` (hidden, H * (nope + rope));
    ``w_uk`` (H, nope, kv_lora_rank) and ``w_uv`` (H, v, kv_lora_rank):
    the up-projections of the latent to each head's key and value;
    ``w_o`` (H * v, hidden)."""
    ks = jax.random.split(key, 6)
    qk = qk_nope_head_dim + qk_rope_head_dim

    def w(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5

    return {
        "w_dkv": w(ks[0], (hidden, kv_lora_rank + qk_rope_head_dim),
                   hidden),
        "kv_norm": 1.0 + 0.1 * jax.random.normal(ks[1], (kv_lora_rank,)),
        "w_q": w(ks[2], (hidden, heads * qk), hidden),
        "w_uk": w(ks[3], (heads, qk_nope_head_dim, kv_lora_rank),
                  kv_lora_rank),
        "w_uv": w(ks[4], (heads, v_head_dim, kv_lora_rank), kv_lora_rank),
        "w_o": w(ks[5], (heads * v_head_dim, hidden), heads * v_head_dim),
    }


def latent_cache(x, weights: dict, positions, *, eps: float,
                 theta: float):
    """Each token's cache vector ``[RMSNorm(c) | RoPE(k_rope)]`` from
    hidden states x (T, hidden): (T, kv_lora_rank + rope)."""
    dv = weights["kv_norm"].shape[0]
    ckr = jnp.dot(x, weights["w_dkv"], precision=HI)
    c = rms_norm(ckr[:, :dv], weights["kv_norm"], eps)
    return jnp.concatenate([c, rope(ckr[:, dv:], positions, theta)],
                           axis=-1)


def decode_query(x_t, weights: dict, position, *, theta: float):
    """One token's per-head query halves from its hidden state (hidden,):
    ``q_nope`` (H, nope) and ``q_rope`` (H, rope), the latter rotated at
    ``position``."""
    heads, nope, _ = weights["w_uk"].shape
    q = jnp.dot(x_t, weights["w_q"], precision=HI).reshape(heads, -1)
    q_rope = rope(q[None, :, nope:], jnp.asarray([position]), theta)[0]
    return q[:, :nope], q_rope


def absorb_query(q_nope, q_rope, w_uk):
    """The kernel's query: ``[W_uk_h^T q_nope_h | q_rope_h]`` (H, D), so
    that its dot with a cache vector ``[c | k_rope]`` is the published
    score ``q_nope_h · (W_uk_h c) + q_rope_h · k_rope``."""
    q_lat = jnp.einsum("hd,hdc->hc", q_nope, w_uk, precision=HI)
    return jnp.concatenate([q_lat, q_rope], axis=-1)


def apply_uv(o_latent, w_uv):
    """Each head's value from the kernel's latent output (H, dv):
    ``W_uv_h o_h`` (H, v), which is ``Σ_t p_t W_uv_h c_t``."""
    return jnp.einsum("hc,hdc->hd", o_latent, w_uv, precision=HI)


def mla_attention_ref(x, weights: dict, positions, *, eps: float,
                      theta: float):
    """The published MLA layer's output (hidden,) for the last of the
    tokens x (T, hidden) at ``positions`` (T,), attending to all T:
    per-head keys ``[W_uk c_t | k_rope_t]`` and values ``W_uv c_t``
    built from the latent, softmax at ``(nope + rope) ** -0.5``, then
    ``W_o``."""
    x = jnp.asarray(x, jnp.float32)
    heads, nope, dv = weights["w_uk"].shape
    kv = latent_cache(x, weights, positions, eps=eps, theta=theta)
    c, k_rope = kv[:, :dv], kv[:, dv:]
    k_nope = jnp.einsum("hdc,tc->htd", weights["w_uk"], c, precision=HI)
    v = jnp.einsum("hdc,tc->htd", weights["w_uv"], c, precision=HI)
    q_nope, q_rope = decode_query(x[-1], weights, positions[-1],
                                  theta=theta)
    scale = (nope + q_rope.shape[-1]) ** -0.5
    s = (jnp.einsum("hd,htd->ht", q_nope, k_nope, precision=HI)
         + jnp.einsum("hr,tr->ht", q_rope, k_rope, precision=HI)) * scale
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("ht,htd->hd", p, v, precision=HI)
    return jnp.dot(o.reshape(-1), weights["w_o"], precision=HI)

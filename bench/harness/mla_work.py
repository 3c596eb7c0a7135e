"""Work of one MLA decode call, counted from the configuration's sizes
and never from padded arrays or blocks: every block choice is held to
the same count.

Per layer of the call the kernel must read each live token's cache
vector once (Σ lengths × (kv_lora_rank + rope) bfloat16 values), the
queries, and write each head's float32 latent output; it multiplies
each live token's vector with every head's query (D wide) and each
head's probability with its first kv_lora_rank features.
"""
from __future__ import annotations

from harness import peaks
from reference import mla_decode as ref

BF16 = 2
F32 = 4


def shape(sizes: dict) -> dict:
    """The call's sizes: layers, batch, heads, width D, value width,
    and the lengths the configuration's rule gives."""
    return dict(
        layers=sizes["num_hidden_layers"], batch=sizes["batch"],
        heads=sizes["num_attention_heads"],
        width=sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"],
        value_dim=sizes["kv_lora_rank"],
        lengths=ref.lengths(sizes["batch"], sizes["min_len"],
                            sizes["max_len"], sizes["order_seed"]))


def mla_decode_bytes(sizes: dict) -> int:
    s = shape(sizes)
    tokens = int(s["lengths"].sum())
    return s["layers"] * (tokens * s["width"] * BF16
                          + s["batch"] * s["heads"] * s["width"] * BF16
                          + s["batch"] * s["heads"] * s["value_dim"] * F32)


def mla_decode_flops(sizes: dict) -> int:
    s = shape(sizes)
    return (2 * s["layers"] * s["heads"] * int(s["lengths"].sum())
            * (s["width"] + s["value_dim"]))


def least_time_s(device_kind: str, sizes: dict) -> float:
    """Bytes over the HBM bandwidth or bf16 flops over the peak rate of
    ``bench/peaks.json``, whichever is longer."""
    return peaks.least_time_s(device_kind, flops=mla_decode_flops(sizes),
                              bytes_moved=mla_decode_bytes(sizes))

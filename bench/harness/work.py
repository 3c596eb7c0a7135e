"""Work counts of one call, from the problem's sizes and never from
padded arrays, so that every implementation is held to the same count.
"""
from __future__ import annotations


def spmv_bytes(nnz: int, n_rows: int, n_x: int) -> int:
    """HBM bytes one SpMV must move: a float32 value and an int32 column
    for every non-zero, x read once, y written once."""
    return 8 * nnz + 4 * n_x + 4 * n_rows


def spmv_rank_bytes(nnz_rank: int, m: int) -> int:
    """Bytes one of the block-partitioned ranks must move: its
    non-zeros, its own x block and the two neighbour blocks of the halo
    (3m), and its m outputs."""
    return spmv_bytes(nnz_rank, m, 3 * m)


def causal_attention_flops(batch: int, heads: int, head_dim: int,
                           seq: int) -> int:
    """Matmul flops of causal self-attention: q k^T and p v, 2 flops
    per multiply-add each, over the S(S+1)/2 unmasked pairs."""
    return 4 * batch * heads * head_dim * seq * (seq + 1) // 2

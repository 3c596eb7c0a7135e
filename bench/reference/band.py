"""The paper's SpMV input (arXiv:2203.02530, section III), generated
from a seed with numpy alone.

150,000 rows, 1.5M non-zeros uniform in a circulant band of half-width
n/4, in ELL layout: ``vals`` (n, K) float32 and ``cols`` (n, K) int32,
padding entries with value 0 and column = row. Draw for draw the same
stream as the program's own generator, so an instance made there from
a seed is this instance; a change to the program's generator that
changes the values changes the problem, and the check says so.
"""
from __future__ import annotations

import numpy as np


def band_matrix(n: int, nnz: int, seed, half_bandwidth: int | None = None
                ) -> tuple[np.ndarray, np.ndarray]:
    """(vals, cols) of the circulant band matrix drawn from ``seed``."""
    if half_bandwidth is None:
        half_bandwidth = n // 4
    rng = np.random.default_rng(seed)
    per_row = nnz // n
    rem = nnz - per_row * n
    counts = np.full(n, per_row, dtype=np.int64)
    counts[rng.choice(n, size=rem, replace=False)] += 1
    k = int(counts.max())
    offs = rng.integers(-half_bandwidth, half_bandwidth + 1, size=(n, k),
                        dtype=np.int64)
    cols = (np.arange(n)[:, None] + offs) % n
    vals = rng.standard_normal((n, k)).astype(np.float32)
    mask = np.arange(k)[None, :] < counts[:, None]
    vals = np.where(mask, vals, 0.0).astype(np.float32)
    cols = np.where(mask, cols, np.arange(n)[:, None] % n)
    return vals, cols.astype(np.int32)


def vector(n: int, seed) -> np.ndarray:
    """A dense float32 vector of standard normals drawn from ``seed``."""
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)

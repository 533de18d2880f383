"""The plain reference of the matrix cells: host float64 on a sample.

``C = A x B`` is checked on a seeded sample of rows of A and columns of B: only
those slices leave the device, and NumPy multiplies them in float64. Nothing of
the program is imported.
"""

from __future__ import annotations

import numpy as np


def sample_indices(seed: int, n_rows: int, n_cols: int, k: int):
    """``k`` sorted distinct row indices and ``k`` column indices, from the
    seed; they fall on every shard of a sharded product."""
    rng = np.random.default_rng([int(seed), 7])
    rows = np.sort(rng.choice(n_rows, size=min(k, n_rows), replace=False))
    cols = np.sort(rng.choice(n_cols, size=min(k, n_cols), replace=False))
    return rows, cols


def product_sample(a_rows, b_cols) -> np.ndarray:
    """``a_rows`` (k x n) times ``b_cols`` (n x k) in float64."""
    return np.asarray(a_rows, np.float64) @ np.asarray(b_cols, np.float64)


def rel_err(got, ref) -> float:
    """Largest absolute difference over the largest reference entry."""
    got = np.asarray(got, np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.abs(got - ref).max() / np.abs(ref).max())

"""Synthetic datasets matched to the paper's Table-3 statistics.

The seven real datasets (Audio..Trevi) are not shipped offline; each
synthetic twin is a clustered Gaussian mixture whose (n, d) follow
Table 3 (n reduced for CPU tractability — scale factor recorded) and
whose *local intrinsic dimensionality* is controlled by the number of
active directions per cluster (low-rank cluster covariance), matching
the LID/RC regime of the original.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    name: str
    n: int  # reduced for CPU
    d: int
    n_real: int  # the paper's cardinality ×10³
    lid: float  # paper's LID
    clusters: int
    active_dims: int  # low-rank dimensionality per cluster (controls LID)


SPECS = {
    # name           n      d    n_real  LID  clusters active
    "audio": DatasetSpec("audio", 8000, 192, 54, 5.6, 40, 6),
    "deep": DatasetSpec("deep", 10000, 256, 1000, 12.1, 60, 12),
    "nus": DatasetSpec("nus", 8000, 500, 269, 24.5, 40, 24),
    "mnist": DatasetSpec("mnist", 8000, 784, 60, 6.5, 40, 7),
    "gist": DatasetSpec("gist", 10000, 960, 983, 18.9, 60, 19),
    "cifar": DatasetSpec("cifar", 8000, 1024, 50, 9.0, 40, 9),
    "trevi": DatasetSpec("trevi", 8000, 4096, 100, 9.2, 40, 9),
}


# rows built at a time: host memory stays at the output plus
# O(_CHUNK · active_dims · d) at any n
_CHUNK = 65536


def make_dataset(name: str, seed: int = 0, n: int | None = None) -> np.ndarray:
    """``n`` points of the named twin (default: the spec's reduced n).

    Rows are built ``_CHUNK`` at a time; the random streams are drawn in
    the same order for every chunk size, so the points do not depend on
    it.
    """
    spec = SPECS[name]
    n = n or spec.n
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(spec.clusters, spec.d)).astype(np.float32) * 6.0
    # low-rank within-cluster spread → LID ≈ active_dims
    basis = rng.normal(size=(spec.clusters, spec.active_dims, spec.d)).astype(
        np.float32
    )
    basis /= np.linalg.norm(basis, axis=-1, keepdims=True)
    asg = rng.integers(0, spec.clusters, n)
    coeff = rng.normal(size=(n, spec.active_dims)).astype(np.float32)
    pts = np.empty((n, spec.d), np.float32)
    for s in range(0, n, _CHUNK):
        a = asg[s:s + _CHUNK]
        pts[s:s + _CHUNK] = centers[a] + np.einsum(
            "na,nad->nd", coeff[s:s + _CHUNK], basis[a])
    # a pinch of full-rank noise so distances are non-degenerate
    for s in range(0, n, _CHUNK):
        rows = min(_CHUNK, n - s)
        pts[s:s + rows] += (rng.normal(size=(rows, spec.d)).astype(np.float32)
                            * 0.05)
    return pts


def make_queries(data: np.ndarray, n_queries: int, seed: int = 1) -> np.ndarray:
    """Paper §7.1: queries are dataset points (we add a small jitter so
    the exact NN is nontrivial)."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, data.shape[0], n_queries)
    jit = rng.normal(size=(n_queries, data.shape[1])).astype(np.float32)
    scale = 0.05 * np.linalg.norm(data.std(axis=0))
    return data[ids] + jit * scale / np.sqrt(data.shape[1])

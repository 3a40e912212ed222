"""Device-native closest-pair engine (paper §6 on the fused stack).

``core/cp.py`` reproduces Algorithms 3-5 faithfully: a host PM-tree
walk whose radius filter (Alg. 4) bounds pair-verification volume by
``γ·t·ub``.  This module is the same filter with the tree walk replaced
by the device-native shape the rest of the framework already uses
(DESIGN.md §10):

    1. project   one 2-stable coordinate per point (the first column
                 of the m-dim family) — a 1-D key whose pair gap
                 lower-bounds the m-dim projected distance;
    2. sort      points by key; tile the (n, n) upper-triangular pair
                 space into (block, block) tiles — a tile's key gap is
                 its closed-form projected Mindist (Eq. 11 collapses
                 to one subtraction on sorted keys);
    3. join      ``kernels/pair_join``: band-major sweep (diagonal
                 self-joins first, seeding ub exactly like Alg. 4's
                 leaf self-joins), streaming global top-k pair heap in
                 VMEM whose k-th slot is the ub register, tiles with
                 Mindist > γ·t·ub skipped without touching HBM;
    4. emit      map row positions back through the sort permutation,
                 √ the squared distances, report pairs_verified /
                 tiles_pruned.

Approximation contract: identical in kind to Algorithm 4 — every
reported distance is an exact original-space float32 distance; a true
top-k pair is missed only when its 1-D key gap exceeds γ·t·ub, i.e.
with per-pair probability ≤ 2Φ(−γt) ≈ 6e-5 at the defaults (the key
gap of a pair at distance r is |N(0,1)|·r).  ``core/cp.py`` remains
the paper-faithful reference; ``exact_cp`` there is the exact oracle
this engine is parity-tested against.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.obs import trace as otrace

from .estimator import solve_parameters
from .hashing import ProjectionFamily

__all__ = ["CpFusedResult", "cp_fused_search", "cp_join_budget",
           "cp_threshold2"]


@dataclasses.dataclass
class CpFusedResult:
    """(c,k)-ACP answer with the §6 radius-filter work counters."""

    pairs: np.ndarray  # (k', 2) int32 ids, i < j, ascending distance
    distances: np.ndarray  # (k',) float32 original distances
    pairs_verified: int  # pair distance computations issued by the join
    tiles_pruned: int  # tiles skipped by the γ·t·ub filter


def cp_threshold2(c: float, m: int, gamma: float,
                  alpha1: float = 1.0 / math.e) -> float:
    """(γ·t)² — the squared radius-filter multiplier of Algorithm 4.

    t comes from the Eq. 10 solve at (c, m, α₁); γ is the §6.3
    calibration knob (the tree path samples an LCA-radius quantile; the
    tile path has no tree, so γ directly scales the skip threshold —
    γ = 1 already gives per-pair miss probability 2Φ(−t) ≈ 6e-5).
    """
    t = solve_parameters(c, m=m, alpha1=alpha1).t
    return float(gamma * t) ** 2


def cp_join_budget(k: int, n_pairs: int) -> int:
    """Pairs the join keeps for a k-pair answer: 2k, within the
    kernel's k ≤ 128 regime.  The join ranks by norm-trick float32
    distances, whose cancellation error can reorder pairs near the k-th
    (on the Deep twin at n = 16384, k = 10, a k-pair pool lost a true
    top-k pair in 3 of 24 seeds; the 2k pool in none).  The exact
    re-rank of the wider pool makes such a loss rarer; it cannot rule
    it out."""
    return min(max(k, min(2 * k, 128)), n_pairs)


def cp_fused_search(
    data: np.ndarray,
    k: int,
    *,
    m: int = 15,
    c: float = 4.0,
    gamma: float = 1.0,
    seed: int = 0,
    force: str | None = None,
    block_n: int = 128,
    key: np.ndarray | None = None,
) -> CpFusedResult:
    """(c,k)-ACP over ``data`` through the device-native pair join.

    Args:
      data: (n, d) float32 points.
      k: pairs to return (clamped to n·(n−1)/2; short answers are NOT
        padded — ``CpFusedResult`` carries exactly the pairs found,
        matching ``core/cp.py``).
      m / c / seed: projection family size, CP approximation ratio and
        seed — same meaning as ``PMLSH_CP``.
      gamma: radius-filter slack (§6.3); larger = less pruning, lower
        miss probability.
      force: kernel dispatch ("pallas" | "interpret" | "ref" | None).
      key: optional precomputed (n,) sort key (a 2-stable projection of
        the rows); default projects with ``ProjectionFamily(seed)`` and
        takes the first coordinate.  Callers that already hold a
        projection (the flat index) pass its first column so CP shares
        the build-time family.

    Returns ``CpFusedResult``; pair ids are rows of ``data``, each pair
    (i, j) normalized to i < j, rows ascending by distance.
    """
    from repro.kernels import ops as kops

    data = np.asarray(data, dtype=np.float32)
    n, d = data.shape
    k = int(k)
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    kk = min(k, n * (n - 1) // 2)
    if kk == 0:
        return CpFusedResult(np.empty((0, 2), np.int32),
                             np.empty((0,), np.float32), 0, 0)
    with otrace.span("cp.query", n=n, d=d, k=kk):
        with otrace.span("cp.project"):
            if key is None:
                # only the FIRST projection coordinate is needed; project
                # with that one column rather than paying for the full
                # m-dim family
                family = ProjectionFamily.create(d, m, seed=seed)
                key = data @ np.asarray(family.a)[:, 0]
            key = np.asarray(key, dtype=np.float32).reshape(-1)
        if key.shape[0] != n:
            raise ValueError(f"key has {key.shape[0]} entries for n={n}")

        with otrace.span("cp.sort"):
            order = np.argsort(key, kind="stable")
            xs, ks = data[order], key[order]
        with otrace.span("cp.join"):
            thresh2 = cp_threshold2(c, m, gamma)
            d2, pi, pj, stats = kops.pair_join(
                xs, ks, cp_join_budget(kk, n * (n - 1) // 2),
                thresh2=thresh2, force=force, block_n=block_n)
            d2 = np.asarray(d2)
            pi = np.asarray(pi)
            pj = np.asarray(pj)
            stats = np.asarray(stats)

        with otrace.span("cp.reverify"):
            real = pi >= 0
            ids_a = order[pi[real]].astype(np.int64)
            ids_b = order[pj[real]].astype(np.int64)
            pairs = np.stack([np.minimum(ids_a, ids_b),
                              np.maximum(ids_a, ids_b)],
                             axis=1).astype(np.int32)
            # the join ranks pairs by norm-trick distances (MXU form),
            # which cancel catastrophically exactly where CP answers
            # live — between near-duplicates.  Recompute the pool in
            # the stable subtract-then-norm form (2k rows, negligible)
            # and re-sort, so the k reported pairs and distances are
            # what a direct verification gives.
            diff = (data[pairs[:, 0].astype(np.int64)]
                    - data[pairs[:, 1].astype(np.int64)])
            dists = np.sqrt(np.sum(diff.astype(np.float32) ** 2, axis=1)
                            ).astype(np.float32)
            resort = np.argsort(dists, kind="stable")[:kk]
    return CpFusedResult(pairs=pairs[resort], distances=dists[resort],
                         pairs_verified=int(stats[0]),
                         tiles_pruned=int(stats[1]))

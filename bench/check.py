"""The comparison that decides ``correct``.

Each function turns what the timed path returned, and the reference's
answer to the same inputs, into a few named numbers.  A cell holds each
number to a limit of its own, kept in ``bench/limits/<cell>.json``
(``PERF.md`` gives the readings each limit was set from); a run is
correct when every number is at or under its limit.

Numbers:

- ``bad_rows`` / ``bad_pairs``: answers that break the output contract
  (an id out of range or repeated, a distance not finite or out of
  order, a request that never got its answer).  Exact: limit 0.
- ``dist_gap``: the widest gap between a reported distance and the
  float64 distance of the id (or pair) it is reported for, relative to
  the latter.  The program reports exact float32 distances; a path that
  computes them, or ranks by them, in a lower precision reads far above.
- ``recall_miss``: 1 − mean recall@k against the exact answer.
- ``pair_miss``: the largest share of the exact k closest pairs that a
  CP job left out.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np


def knn_numbers(ids, dists, x_host: np.ndarray, queries: np.ndarray,
                exact_ids: np.ndarray) -> dict:
    """ids / dists (Q, k) as served (-1 / non-finite where an answer is
    missing), against the exact ids (Q, k) for the same query rows."""
    ids = np.asarray(ids, np.int64)
    dists = np.asarray(dists, np.float64)
    n, k = x_host.shape[0], ids.shape[1]
    ok_ids = (ids >= 0) & (ids < n)
    dup = np.zeros(len(ids), bool)
    srt = np.sort(ids, axis=1)
    dup |= (np.diff(srt, axis=1) == 0).any(axis=1)
    bad = (~ok_ids.all(axis=1) | dup | ~np.isfinite(dists).all(axis=1)
           | (np.diff(dists, axis=1) < 0).any(axis=1))
    good = ~bad
    gap = 0.0
    if good.any():
        safe = np.where(ok_ids, ids, 0)[good]
        diff = (x_host[safe].astype(np.float64)
                - np.asarray(queries, np.float64)[good][:, None, :])
        true = np.sqrt(np.einsum("qkd,qkd->qk", diff, diff))
        gap = float(np.max(np.abs(dists[good] - true)
                           / np.maximum(true, 1e-30)))
    hits = [len(set(a) & set(b)) for a, b in zip(ids.tolist(),
                                                 exact_ids.tolist())]
    recall = float(np.sum(hits)) / (k * len(ids))
    return {"bad_rows": int(bad.sum()), "dist_gap": gap,
            "recall_miss": 1.0 - recall}


def cp_numbers(jobs, x_host: np.ndarray, exact_pairs: np.ndarray) -> dict:
    """jobs: [(pairs (k, 2), distances (k,)), ...], one per CP job of the
    window, against the exact k closest pairs."""
    n, k = x_host.shape[0], len(exact_pairs)
    want = {tuple(p) for p in np.sort(exact_pairs, axis=1).tolist()}
    bad, gap, miss = 0, 0.0, 0.0
    for pairs, dists in jobs:
        pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
        dists = np.asarray(dists, np.float64).reshape(-1)
        got = [tuple(p) for p in np.sort(pairs, axis=1).tolist()]
        if (len(pairs) != k or len(dists) != k or len(set(got)) != k
                or (pairs < 0).any() or (pairs >= n).any()
                or (pairs[:, 0] == pairs[:, 1]).any()
                or not np.isfinite(dists).all()
                or (np.diff(dists) < 0).any()):
            bad += 1
            continue
        diff = (x_host[pairs[:, 0]].astype(np.float64)
                - x_host[pairs[:, 1]].astype(np.float64))
        true = np.sqrt(np.einsum("pd,pd->p", diff, diff))
        gap = max(gap, float(np.max(np.abs(dists - true)
                                    / np.maximum(true, 1e-30))))
        miss = max(miss, 1.0 - len(want & set(got)) / k)
    return {"bad_pairs": bad, "dist_gap": gap, "pair_miss": miss}


def load_limits(root: Path, workload: str) -> dict:
    return json.loads((root / "bench" / "limits" / f"{workload}.json")
                      .read_text())["limits"]


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers the cell's
    limits name.  A number the check reads but the cell does not hold
    to a limit (where its two readings do not separate; ``PERF.md``
    says which) is not compared; a limit for a number the check does
    not read is an error of the cell's files."""
    missing = sorted(set(limits) - set(numbers))
    if missing:
        raise KeyError(f"limits for numbers the check does not read: "
                       f"{missing}")
    compared = {name: {"value": numbers[name], "limit": limits[name]}
                for name in sorted(limits)}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    return correct, compared

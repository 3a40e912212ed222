"""Sharded ANN/CP scaling benchmark (ISSUE 10, DESIGN.md §15).

Three questions, three sections:

  1. How does fused-query latency move with the shard count?  The
     sharded-flat backend timed at P ∈ {1, 2, 4, 8} on the same data
     (mesh path when enough devices are visible, the emulated twin
     otherwise — same stage functions, so the per-shard work is the
     real quantity either way), with the WorkStats skew
     (max-shard / mean-shard candidates) attached to every row.

  2. What does the counts-only threshold exchange actually move?
     Modeled bytes from the roofline registry: the 32-rung bisection
     exchanges ``rounds·P·B`` int32 counts, while each shard's verify
     touches its full candidate slab — the published
     ``exchange_vs_verify`` summary shows the exchange staying orders
     of magnitude below the verify traffic, which is the argument for
     calibrating a global threshold instead of shipping candidates.

  3. How does the CP pair-join ring scale?  cp_search timed per P with
     the ring-traffic model (points + keys + the global ub register per
     hop) alongside.

Self-gating acceptance: every sharded answer must stay BIT-IDENTICAL
to flat at every P (ids and distances, ANN and CP — exactness is the
backend's contract, so a benchmark that drifts must fail loudly), and
the modeled exchange bytes must stay below the verify bytes at every P.
"""
from __future__ import annotations

import jax
import numpy as np

from .common import csv_row, publish_summary, timer_samples

SHARD_COUNTS = (1, 2, 4, 8)
D = 32
K = 10
B = 8


def _dataset(rng, n):
    centers = rng.normal(size=(16, D)).astype(np.float32) * 4
    data = (centers[rng.integers(0, 16, n)]
            + rng.normal(size=(n, D)).astype(np.float32) * 0.5)
    q = data[rng.integers(0, n, B)] + np.float32(0.05)
    return data, q


def _comm_model(index, n):
    """Modeled bytes per stage for one batched query + one cp round,
    straight from the roofline registry (the same costs the traced
    emulated twin stamps on its exchange/merge spans)."""
    from repro.core.flat_index import candidate_budget
    from repro.core.sharded import BISECT_ROUNDS
    from repro.obs import roofline

    P = index.impl.P
    nl = index.impl.nl
    T = candidate_budget(index.impl.params, n, K)
    cap = min(nl, T)
    exchange = roofline.shard_exchange_cost(P, B, cap, rounds=BISECT_ROUNDS)
    merge = roofline.shard_merge_cost(P, B, min(K, cap))
    verify = roofline.verify_topk_cost(B, cap, D, min(K, cap))
    ring = roofline.shard_ring_cost(P, nl, D, K)
    return {"P": P, "exchange_bytes": int(exchange.bytes),
            "merge_bytes": int(merge.bytes),
            "verify_bytes_per_shard": int(verify.bytes),
            "verify_bytes_total": int(verify.bytes) * P,
            "cp_ring_bytes": int(ring.bytes)}


def run(quick: bool = True):
    from repro.index import IndexConfig, build_index

    rng = np.random.default_rng(0)
    n = 2048 if quick else 8192
    repeats = 5 if quick else 20
    data, queries = _dataset(rng, n)
    out = []

    flat = build_index(data, IndexConfig(backend="flat", seed=0))
    ref = flat.search(queries, K)
    cref = flat.cp_search(6)

    comm, lat = [], {}
    for P in SHARD_COUNTS:
        index = build_index(data, IndexConfig(
            backend="sharded-flat", seed=0,
            options={"shards": P, "emulate": P > jax.device_count()}))
        res, samples = timer_samples(
            lambda idx=index: idx.search(queries, K), repeats=repeats)
        # exactness is the contract — a drifting benchmark fails loudly
        np.testing.assert_array_equal(ref.indices, res.indices)
        np.testing.assert_array_equal(ref.distances, res.distances)
        mean_us = float(np.mean(samples)) * 1e6
        skew = res.stats.max_shard_candidates * P / max(
            res.stats.candidates_selected, 1)
        lat[P] = mean_us
        out.append(csv_row(
            f"ann_P{P}", mean_us,
            f"B={B};k={K};n={n};skew={skew:.2f};"
            f"max_shard={res.stats.max_shard_candidates};"
            f"emulated={int(index.impl.emulated)}"))

        cres, csamples = timer_samples(
            lambda idx=index: idx.cp_search(6), repeats=max(2, repeats // 2))
        np.testing.assert_array_equal(cref.pairs, cres.pairs)
        np.testing.assert_array_equal(cref.distances, cres.distances)
        out.append(csv_row(
            f"cp_P{P}", float(np.mean(csamples)) * 1e6,
            f"k=6;n={n};pairs_verified={cres.stats.pairs_verified};"
            f"tiles_pruned={cres.stats.tiles_pruned};"
            f"max_shard_pairs={cres.stats.max_shard_pairs}"))

        model = _comm_model(index, n)
        comm.append(model)
        assert model["exchange_bytes"] < model["verify_bytes_total"], (
            f"P={P}: threshold exchange ({model['exchange_bytes']}B) not "
            f"below verify traffic ({model['verify_bytes_total']}B) — the "
            "counts-only protocol stopped paying for itself")

    publish_summary("ann_scaling", n=n, B=B, k=K,
                    **{f"p{P}_us": lat[P] for P in SHARD_COUNTS})
    publish_summary("exchange_vs_verify", rows=comm)
    return out

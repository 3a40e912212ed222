#!/usr/bin/env python3
"""Drive PM-LSH's main path once on a TPU and check what comes out.

    python3 chip_smoke.py              # one chip: ANN, served requests, CP
    python3 chip_smoke.py --chips 4    # four chips: sharded-flat vs flat

One chip.  Generates the paper's Deep shape at its published size
(1,000,000 × 256 float32, ``benchmarks/datasets.py`` spec "deep"),
builds ``IndexConfig(backend="flat", c=1.5, m=15)`` through
``repro.index.build_index``, answers a batch of 64 queries at k = 10 on
the fused estimate → select → verify path, then a few single requests
(k ∈ {1, 5, 10}) through ``repro.serve.RequestScheduler``, then a
closest-pair search at k = 10.  It fails unless ANN recall@10 against
an exact answer is at least what the jnp reference path gets on the
same index, every served answer equals the batch path's, the CP answer
equals an exact join over the same points, and the compiled search
program holds Pallas kernels (``tpu_custom_call``).

Four chips.  Builds ``sharded-flat`` over four real chips on the same
data and checks that its answers equal ``flat`` on chip 0, id for id
and bit for bit.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every check passed.  Without a TPU the script exits non-zero and
prints no result.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N_DEEP = 1_000_000  # the paper's Deep cardinality (Table 3)
K = 10
BATCH = 64


def log(phase: str, **kv) -> None:
    items = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{phase}] {items}", flush=True)


def fail(msg: str, code: int = 1):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def tpu_devices(count: int):
    """The TPU devices, or exit: this script measures no CPU."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"no TPU found ({e})")
    if devs[0].platform != "tpu":
        fail(f"no TPU found: JAX sees {devs[0].platform!r} devices")
    if len(devs) < count:
        fail(f"{count} chips asked for, {len(devs)} visible")
    return devs


def deep_data(n: int, seed: int):
    from benchmarks.datasets import make_dataset, make_queries

    t0 = time.perf_counter()
    data = make_dataset("deep", seed=seed, n=n)
    queries = make_queries(data, BATCH, seed=seed + 1)
    log("data", spec="deep", n=n, d=data.shape[1], queries=BATCH, seed=seed,
        seconds=f"{time.perf_counter() - t0:.2f}")
    return data, queries


def recall(ids, exact) -> float:
    import numpy as np

    return float(np.mean([len(set(a) & set(b)) / exact.shape[1]
                          for a, b in zip(ids.tolist(), exact.tolist())]))


def exact_knn(x_dev, data, queries, k: int):
    """Exact k nearest by float64 distance: a HIGHEST-precision device
    scan shortlists 64 rows per query, the host ranks them in float64."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def shortlist(x, q):
        d2 = (jnp.sum(q * q, 1)[:, None] + jnp.sum(x * x, 1)[None, :]
              - 2.0 * jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST))
        return jax.lax.top_k(-d2, 64)[1]

    cand = np.asarray(shortlist(x_dev, jnp.asarray(queries)))
    q64 = queries.astype(np.float64)
    out = np.empty((len(queries), k), np.int64)
    for b in range(len(queries)):
        dist = np.linalg.norm(data[cand[b]].astype(np.float64) - q64[b], axis=1)
        out[b] = cand[b][np.argsort(dist, kind="stable")[:k]]
    return out


def exact_closest_pairs(x_dev, data, k: int, bound: float):
    """The k closest pairs of ``data`` with distance ≤ ``bound``, exact.

    A HIGHEST-precision device scan flags, per row i, the rows j > i
    within ``bound`` plus the norm-trick error; the host recomputes the
    flagged pairs in float64 and keeps the k closest.  Any pair at
    distance ≤ ``bound`` is flagged, so when ``bound`` is at least the
    true k-th closest distance the answer is the exact top k.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = data.shape[0]
    norms = np.sum(data.astype(np.float64) ** 2, axis=1)
    slack = 1e-5 * 2.0 * float(norms.max())  # float32 norm-trick error
    thr2 = bound * bound * (1.0 + 1e-4) + slack
    xn = jnp.sum(x_dev * x_dev, axis=1)
    block = 512

    @jax.jit
    def near(x, xn, rows):
        d2 = (xn[rows][:, None] + xn[None, :]
              - 2.0 * jnp.dot(x[rows], x.T,
                              precision=jax.lax.Precision.HIGHEST))
        return (d2 <= thr2) & (jnp.arange(n)[None, :] > rows[:, None])

    count = jax.jit(lambda x, xn, rows: jnp.sum(near(x, xn, rows), axis=1))

    def blocks(ids):
        for s in range(0, len(ids), block):
            part = ids[s:s + block]
            rows = np.resize(part, block).astype(np.int32)  # pad by repeats
            yield part, rows

    counts = np.concatenate([np.asarray(count(x_dev, xn, rows))[:len(part)]
                             for part, rows in blocks(np.arange(n))])
    flagged = np.flatnonzero(counts)
    pairs, dists = [], []
    for part, rows in blocks(flagged):
        mask = np.asarray(near(x_dev, xn, rows))
        for r, i in enumerate(part):
            js = np.flatnonzero(mask[r])
            d = np.linalg.norm(data[js].astype(np.float64)
                               - data[i].astype(np.float64), axis=1)
            keep = d <= bound * (1.0 + 1e-6)
            pairs += [(int(i), int(j)) for j in js[keep]]
            dists += d[keep].tolist()
    order = np.argsort(dists, kind="stable")[:k]
    return (np.asarray(pairs, np.int64).reshape(-1, 2)[order],
            np.asarray(dists)[order], len(flagged))


def ann_phase(index, data, queries, *, force=None):
    """Batched search through the facade, checked against the exact
    answer and against the jnp reference path on the same index."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.flat_index import candidate_budget
    from repro.core.fused import fused_ann_query

    n = data.shape[0]
    T = candidate_budget(index.impl.params, n, K)
    t0 = time.perf_counter()
    res = index.search(queries, k=K)
    first = time.perf_counter() - t0
    warm = []
    for _ in range(3):
        t0 = time.perf_counter()
        again = index.search(queries, k=K)
        warm.append(time.perf_counter() - t0)
    if not np.array_equal(res.indices, again.indices):
        fail("repeated batch search gave different ids")

    # the kernels ran: the search program the facade dispatches to
    # holds Pallas custom calls, and it gives the facade's answer
    program = fused_ann_query.lower(
        index.impl, jnp.asarray(queries), k=K, T=T, force=force,
        with_count=True).compile()
    n_kernels = program.as_text().count("tpu_custom_call")
    program_ids = np.asarray(program(index.impl, jnp.asarray(queries))[0])

    exact = exact_knn(index.impl.data, data, queries, K)
    rec = recall(res.indices, exact)
    ref_ids = np.concatenate([
        np.asarray(fused_ann_query(index.impl, jnp.asarray(queries[s:s + 16]),
                                   k=K, T=T, force="ref")[0])
        for s in range(0, len(queries), 16)])
    rec_ref = recall(ref_ids, exact)
    d = res.distances
    log("ann", B=len(queries), k=K, T=T, pipeline="fused",
        first_call_s=f"{first:.3f}",
        warm_s=",".join(f"{w:.4f}" for w in warm),
        candidates_selected=res.stats.candidates_selected,
        recall_at_10=f"{rec:.4f}", recall_at_10_ref=f"{rec_ref:.4f}",
        ids_equal_ref=int((res.indices == ref_ids).sum()),
        of=res.indices.size, tpu_custom_calls=n_kernels,
        ids_equal_program=np.array_equal(program_ids, res.indices))
    if (res.indices < 0).any() or (res.indices >= n).any():
        fail("ANN ids out of range")
    if not (np.isfinite(d).all() and (np.diff(d, axis=1) >= 0).all()):
        fail("ANN distances not finite and ascending")
    if n_kernels == 0:
        fail("the compiled search program holds no Pallas kernel")
    if not np.array_equal(program_ids, res.indices):
        fail("the facade's ids differ from the kernel program's")
    if rec < rec_ref:
        fail(f"recall@10 {rec:.4f} below the reference path's {rec_ref:.4f}")
    return res


def serve_phase(data, queries, batch_index, *, force=None):
    """Ragged single requests through the request scheduler; each
    answer must equal the batch path's answer for that query."""
    import numpy as np

    from repro.index import IndexConfig
    from repro.serve import RequestScheduler, RetrievalStep, ServeConfig

    opts = {} if force is None else {"force": force}
    t0 = time.perf_counter()
    step = RetrievalStep(data, np.arange(len(data), dtype=np.int32), k=K,
                         index_config=IndexConfig(backend="flat", c=1.5,
                                                  m=15, options=opts))
    sched = RequestScheduler(step, config=ServeConfig(b_max=8, k_max=16))
    build = time.perf_counter() - t0
    for i, k in enumerate((1, 5, 10, 10, 5)):
        q = queries[i]
        t0 = time.perf_counter()
        resp = sched.submit(q, k=k).result()
        lat = time.perf_counter() - t0
        if not resp.ok:
            fail(f"served request {i} ended {resp.status!r}")
        want = batch_index.search(q[None], k=k)
        same = (np.array_equal(resp.result.indices, want.indices)
                and np.array_equal(resp.result.distances, want.distances))
        log("serve", request=i, k=k, seconds=f"{lat:.3f}",
            cached=resp.cached, equal_to_batch=same)
        if not same:
            fail(f"served request {i} differs from the batch answer")
    log("serve", build_s=f"{build:.2f}", shapes=sorted(sched.compile_shapes))


def cp_phase(index, data):
    """Closest pairs at k = 10 through the facade, checked against an
    exact join over the same points."""
    import numpy as np

    t0 = time.perf_counter()
    res = index.cp_search(K)
    secs = time.perf_counter() - t0
    got = np.sort(res.pairs.astype(np.int64), axis=1)
    t0 = time.perf_counter()
    want, want_d, rows = exact_closest_pairs(
        index.impl.data, data, K, float(res.distances[-1]))
    ref_s = time.perf_counter() - t0
    same = (np.array_equal(got, want)
            and np.allclose(res.distances, want_d, rtol=1e-5, atol=0.0))
    log("cp", n=data.shape[0], k=K, seconds=f"{secs:.2f}",
        pairs_verified=res.stats.pairs_verified,
        tiles_pruned=res.stats.tiles_pruned,
        kth_distance=f"{float(res.distances[-1]):.6f}",
        exact_rows_flagged=rows, exact_seconds=f"{ref_s:.2f}",
        equal_to_exact=same)
    if not same:
        fail(f"CP pairs {got.tolist()} != exact {want.tolist()}")


def single_chip(args) -> None:
    import jax

    from repro.index import IndexConfig, build_index

    data, queries = deep_data(N_DEEP, args.seed)
    t0 = time.perf_counter()
    index = build_index(data, IndexConfig(backend="flat", c=1.5, m=15,
                                          seed=args.seed))
    jax.block_until_ready(index.impl.projected)
    log("build", backend="flat", n=N_DEEP, m=15, c=1.5,
        seconds=f"{time.perf_counter() - t0:.2f}")
    ann_phase(index, data, queries)
    serve_phase(data, queries, index)
    cp_phase(index, data)


def four_chips(args) -> None:
    import numpy as np

    from repro.index import IndexConfig, build_index

    data, queries = deep_data(N_DEEP, args.seed)
    t0 = time.perf_counter()
    flat = build_index(data, IndexConfig(backend="flat", c=1.5, m=15,
                                         seed=args.seed))
    sh = build_index(data, IndexConfig(backend="sharded-flat", c=1.5, m=15,
                                       seed=args.seed,
                                       options={"shards": 4}))
    log("build", flat_device=str(flat.impl.data.devices()),
        seconds=f"{time.perf_counter() - t0:.2f}")
    devs = list(sh.impl.mesh.devices.flat) if sh.impl.mesh is not None else []
    log("mesh", emulated=sh.impl.emulated, devices=len(set(devs)),
        ids=[d.id for d in devs])
    if sh.impl.emulated is not False or len(set(devs)) != 4:
        fail("sharded-flat is not running on a mesh of 4 distinct chips")
    want = flat.search(queries, k=K)
    for rep in range(2):
        t0 = time.perf_counter()
        got = sh.search(queries, k=K)
        secs = time.perf_counter() - t0
        ids_same = np.array_equal(got.indices, want.indices)
        d_same = np.array_equal(got.distances, want.distances)
        log("sharded", call=rep, B=len(queries), k=K, seconds=f"{secs:.3f}",
            ids_equal=ids_same, distances_bit_equal=d_same,
            shards=got.stats.shards,
            max_shard_candidates=got.stats.max_shard_candidates)
        if not (ids_same and d_same):
            fail("sharded-flat answers differ from flat on chip 0")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-flat phase on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the repo's sources are not next to this script ({ROOT})", 2)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    devs = tpu_devices(args.chips)

    import jax

    from repro.compile_cache import enable_compile_cache

    log("device", jax=jax.__version__, platform=devs[0].platform,
        kind=repr(devs[0].device_kind), count=len(devs),
        compile_cache=enable_compile_cache())
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else single_chip)(args)
    log("done", seconds=f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()

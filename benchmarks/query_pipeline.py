"""Fused vs. unfused query pipeline: end-to-end and per-stage latency.

The fused pipeline (DESIGN.md §9) replaces the SELECT top_k with
radius-threshold selection and the VERIFY gather with the gather-free
kernel.  On this CPU container the meaningful comparison is the REF
dispatch path (XLA:CPU-compiled jnp on both sides — same arithmetic,
different algorithms); Pallas wins ride on top on TPU.

Rows report p50/p99 over repeated calls per (n, pipeline) cell plus
stage-level timings for the SELECT step (the CPU-visible delta), and a
summary block records the fused:unfused p50 ratio per n — the
acceptance gate is fused p50 < unfused p50 from n = 32768 up.
"""
from __future__ import annotations

import numpy as np

from .common import (csv_row, latency_quantiles_us, publish_summary,
                     timer_samples, trace_probe)


def run(quick: bool = True):
    import jax
    import jax.numpy as jnp

    from repro.core.flat_index import ann_query, build_flat_index, candidate_budget
    from repro.kernels import ref

    out = []
    B, d, k = 8, 64, 10
    sizes = [8192, 32768] if quick else [8192, 32768, 65536, 131072]
    repeats = 12 if quick else 25
    rng = np.random.default_rng(0)
    speedups = {}

    for n in sizes:
        data = rng.normal(size=(n, d)).astype(np.float32)
        q = (data[rng.integers(0, n, size=B)]
             + 0.1 * rng.normal(size=(B, d))).astype(np.float32)
        index = build_flat_index(data, m=15)
        T = candidate_budget(index.params, n, k)

        cells = {}
        for name, fused in (("unfused", False), ("fused", True)):
            def call(fused=fused):
                i, dd = ann_query(index, q, k=k, T=T, fused=fused)
                return dd.block_until_ready()

            call()  # compile
            (_, samples) = timer_samples(call, repeats=repeats)
            lat = latency_quantiles_us(samples)
            cells[name] = lat
            out.append(csv_row(
                f"pipeline_{name}_n{n}", lat["p50_us"],
                "p99_us=%.1f;T=%d;B=%d;k=%d" % (lat["p99_us"], T, B, k)))

        # parity while we're here (ties-free random data)
        i0, _ = ann_query(index, q, k=k, T=T, fused=False)
        i1, _ = ann_query(index, q, k=k, T=T, fused=True)
        match = float(np.mean(np.asarray(i0) == np.asarray(i1)))

        # stage view: SELECT alone (the algorithmic delta on CPU)
        qp = index.family.project(jnp.asarray(q))
        d2p = ref.pairwise_sq_dist(qp, index.projected)
        d2p.block_until_ready()
        topk = jax.jit(lambda m: jax.lax.top_k(-m, T)[1])
        rsel = jax.jit(lambda m: ref.radius_select(m, T)[1])
        for name, fn in (("topk", topk), ("radius", rsel)):
            fn(d2p).block_until_ready()
            _, s = timer_samples(lambda: fn(d2p).block_until_ready(),
                                 repeats=repeats)
            lat = latency_quantiles_us(s)
            out.append(csv_row(f"select_{name}_n{n}", lat["p50_us"],
                               "p99_us=%.1f;T=%d" % (lat["p99_us"], T)))

        ratio = cells["fused"]["p50_us"] / max(cells["unfused"]["p50_us"], 1e-9)
        speedups[n] = {
            "fused_p50_us": cells["fused"]["p50_us"],
            "unfused_p50_us": cells["unfused"]["p50_us"],
            "fused_over_unfused": ratio,
            "parity_fraction": match,
            "T": T,
        }
        out.append(csv_row(
            f"pipeline_ratio_n{n}", 0.0,
            "fused_over_unfused=%.3f;parity=%.3f" % (ratio, match)))

    # host-step breakdown: one traced facade search AFTER the timed
    # loops; it runs the same jitted program as the untraced calls, and
    # the spans split its wall time into dispatch, wait and answer
    from repro.index import IndexConfig, build_index

    facade = build_index(data, IndexConfig(backend="flat",
                                           options={"fused": True}))
    facade.search(q, k=k)  # compile outside the traced call
    trace_probe("fused_query", lambda: facade.search(q, k=k))

    publish_summary("query_pipeline", B=B, d=d, k=k, sizes=speedups,
                    gate="fused p50 < unfused p50 for n >= 32768")
    return out

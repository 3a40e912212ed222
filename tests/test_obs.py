"""Tests for repro.obs — tracing, roofline, exporters, perf gate
(DESIGN.md §12).

Covers the ISSUE-7 observability contract: span nesting across every
engine (fused ANN, quant, CP, streaming fan-out, serve flush),
near-zero disabled-mode overhead, Chrome-trace schema validity with
≥95% root coverage, roofline attrs on kernel spans, the bounded
latency reservoir, WorkStats round-tripping, and the perf gate's
pass/fail/waiver/cross-device behavior.
"""
import json

import numpy as np
import pytest

from conftest import make_clustered


@pytest.fixture(autouse=True)
def _tracer_off():
    """Every test starts and ends with tracing disabled and the
    process-global collector empty (a failed test must not leak an
    enabled tracer into the rest of the suite)."""
    from repro.obs import trace

    trace.disable()
    trace.get_tracer().drain()
    yield
    trace.disable()
    trace.get_tracer().drain()


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_nesting_parents(self):
        from repro.obs import trace

        with trace.trace() as tr:
            with trace.span("a"):
                with trace.span("b"):
                    with trace.span("c", x=1):
                        pass
                with trace.span("d"):
                    pass
        names = [s.name for s in tr.spans]
        assert names == ["a", "b", "c", "d"]
        a, b, c, d = tr.spans
        assert a.parent == -1
        assert b.parent == 0 and d.parent == 0
        assert c.parent == 1
        assert c.attrs == {"x": 1}
        assert [s.name for s in tr.roots()] == ["a"]

    def test_durations_ordered(self):
        from repro.obs import trace

        with trace.trace() as tr:
            with trace.span("outer"):
                with trace.span("inner"):
                    sum(range(1000))
        outer, inner = tr.spans
        assert outer.duration_s >= inner.duration_s >= 0.0
        assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1

    def test_disabled_span_is_noop(self):
        from repro.obs import trace

        assert not trace.enabled()
        with trace.span("nope"):
            pass
        assert trace.get_tracer().spans == []

    def test_trace_region_disables_and_drains(self):
        from repro.obs import trace

        with trace.trace() as tr:
            assert trace.enabled()
            with trace.span("x"):
                pass
        assert not trace.enabled()
        assert [s.name for s in tr.spans] == ["x"]
        assert trace.get_tracer().spans == []

    def test_nested_trace_regions_rebase_parents(self):
        from repro.obs import trace

        with trace.trace() as outer:
            with trace.span("root"):
                with trace.trace() as inner:
                    with trace.span("sub"):
                        with trace.span("leaf"):
                            pass
        # inner slice: "sub" re-rooted (its parent predates the slice)
        assert [s.name for s in inner.spans] == ["sub", "leaf"]
        assert inner.spans[0].parent == -1
        assert inner.spans[1].parent == 0
        # the outer region still owns the full tree
        assert [s.name for s in outer.spans] == ["root", "sub", "leaf"]
        assert outer.spans[1].parent == 0

    def test_bounded_collector_drops(self):
        from repro.obs.trace import Tracer

        t = Tracer(max_spans=3)
        for i in range(5):
            with t.span(f"s{i}"):
                pass
        assert len(t.spans) == 3
        assert t.dropped == 2

    def test_add_span_explicit_endpoints(self):
        from repro.obs import trace

        with trace.trace() as tr:
            with trace.span("flush"):
                trace.add_span("wait", 10.0, 10.5, rid=7)
        wait = tr.spans[1]
        assert wait.name == "wait" and wait.parent == 0
        assert wait.duration_s == pytest.approx(0.5)
        assert wait.attrs["rid"] == 7

    def test_concrete_rejects_jit_tracers(self):
        import jax
        import jax.numpy as jnp

        from repro.obs import trace

        seen = []

        @jax.jit
        def f(x):
            seen.append(trace.concrete(x))
            return x * 2

        f(jnp.ones(3))
        assert seen == [False]
        assert trace.concrete(np.ones(3), 1.5, None)

    def test_disabled_overhead_under_2pct(self):
        """The acceptance bar: tracing OFF adds <2% to the fused query
        microbench.  Medians over interleaved samples, with a retry to
        absorb scheduler noise on a busy container."""
        import time

        from repro.core.flat_index import (ann_query, build_flat_index,
                                           candidate_budget)
        from repro.obs import trace

        data = make_clustered(4096, 32)
        q = data[:8] + 0.01
        index = build_flat_index(data, m=15)
        T = candidate_budget(index.params, 4096, 10)

        def call():
            i, d = ann_query(index, q, k=10, T=T, fused=True)
            d.block_until_ready()

        call()  # compile
        assert not trace.enabled()

        def median_of(fn, reps):
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                fn()
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

        for attempt in range(3):
            base = median_of(call, 30)
            instrumented = median_of(call, 30)  # same path: flag is off
            overhead = instrumented / base - 1.0
            if overhead < 0.02:
                return
        pytest.fail(f"disabled-tracing overhead {overhead:.1%} >= 2%")


# ---------------------------------------------------------------------------
# engine coverage: every pipeline produces a valid, well-covered tree
# ---------------------------------------------------------------------------


def _trace_of(fn):
    from repro import obs

    with obs.tracing() as tr:
        fn()
    return tr


class TestEngineTraces:
    @pytest.fixture(scope="class")
    def data(self):
        return make_clustered(2048, 24)

    def test_fused_ann_trace(self, data):
        from repro import obs
        from repro.index import IndexConfig, build_index

        idx = build_index(data, IndexConfig(
            backend="flat", options={"fused": True, "force": "interpret"}))
        q = data[:4] + 0.01
        plain = idx.search(q, k=5)
        tr = _trace_of(lambda: idx.search(q, k=5))
        names = [s.name for s in tr.spans]
        assert names[0] == "index.search"
        for stage in ("ann.query", "ann.estimate", "ann.select",
                      "ann.verify"):
            assert stage in names
        assert "kernel.radius_select" in names
        assert obs.coverage(tr) >= 0.95
        # traced twin answers identically to the jit'd pipeline
        traced = idx.search(q, k=5)  # tracer now off again
        np.testing.assert_array_equal(plain.indices, traced.indices)
        obs.validate_chrome_trace(obs.to_chrome_trace(tr))

    def test_quant_ann_trace_parity(self, data):
        from repro import obs
        from repro.index import IndexConfig, build_index

        idx = build_index(data, IndexConfig(
            backend="flat", options={"quant": "sq8", "force": "interpret"}))
        q = data[:4] + 0.01
        plain = idx.search(q, k=5)
        tr = _trace_of(lambda: idx.search(q, k=5))
        names = [s.name for s in tr.spans]
        for stage in ("quant.query", "quant.estimate", "quant.select",
                      "quant.rerank", "quant.verify"):
            assert stage in names
        assert obs.coverage(tr) >= 0.95
        traced = idx.search(q, k=5)
        np.testing.assert_array_equal(plain.indices, traced.indices)

    def test_cp_trace(self, data):
        from repro import obs
        from repro.index import IndexConfig, build_index

        idx = build_index(data, IndexConfig(
            backend="flat", options={"force": "interpret"}))
        tr = _trace_of(lambda: idx.cp_search(3))
        names = [s.name for s in tr.spans]
        for stage in ("index.cp_search", "cp.query", "cp.sort", "cp.join",
                      "cp.reverify", "kernel.pair_join"):
            assert stage in names
        assert obs.coverage(tr) >= 0.95
        # the pair-join kernel span carries its (post-hoc) roofline model
        pj = tr.spans[names.index("kernel.pair_join")]
        assert pj.attrs["bytes"] > 0 and pj.attrs["flops"] > 0
        assert "tiles_pruned" in pj.attrs

    def test_stream_fanout_trace(self, data):
        from repro import obs
        from repro.index import IndexConfig, build_index

        idx = build_index(data[:1024], IndexConfig(
            backend="streaming", options={"delta_threshold": 256}))
        idx.insert(data[1024:1600])
        tr = _trace_of(lambda: idx.search(data[:4], k=5))
        names = [s.name for s in tr.spans]
        assert "stream.search" in names
        assert names.count("stream.segment") == len(idx.segments)
        assert "stream.delta" in names and "stream.merge" in names
        assert obs.coverage(tr) >= 0.95

    def test_serve_flush_trace(self, data):
        from repro import obs
        from repro.serve import RequestScheduler, ServeConfig
        from repro.serve.serve_step import make_retrieval_step

        step, _ = make_retrieval_step(data[:512],
                                      np.arange(512, dtype=np.float32), k=8)
        sched = RequestScheduler(step, config=ServeConfig(
            b_max=8, default_deadline_ms=1e6, max_queue=4096))

        def serve():
            tickets = [sched.submit(data[i], k=4) for i in range(12)]
            sched.drain()
            return [t.result() for t in tickets]

        tr = _trace_of(serve)
        names = [s.name for s in tr.spans]
        for stage in ("serve.flush", "serve.stage", "serve.search",
                      "serve.deliver", "serve.queue_wait", "index.search"):
            assert stage in names
        assert obs.coverage(tr) >= 0.95
        flush = tr.spans[names.index("serve.flush")]
        assert flush.attrs["real"] > 0
        assert "queue_wait_mean_ms" in flush.attrs
        assert flush.attrs["work"]["rounds"] >= 0
        obs.validate_chrome_trace(obs.to_chrome_trace(tr))


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------


class TestRoofline:
    def test_kernel_cost_intensity(self):
        from repro.obs.roofline import KernelCost

        c = KernelCost(bytes=100, flops=400)
        assert c.intensity == 4.0
        assert c.attrs() == {"bytes": 100, "flops": 400, "intensity": 4.0}

    def test_achieved_classification(self):
        from repro.obs.roofline import DevicePeaks, KernelCost, achieved

        peaks = DevicePeaks("cpu", peak_flops=1e12, peak_bw=1e11)  # ridge 10
        mem = achieved(KernelCost(bytes=1000, flops=1000), 1e-6, peaks)
        assert mem["bound"] == "memory"
        comp = achieved(KernelCost(bytes=10, flops=1000), 1e-6, peaks)
        assert comp["bound"] == "compute"
        # fraction of ATTAINABLE ceiling: memory-bound op at full BW
        full_bw = achieved(KernelCost(bytes=int(1e11), flops=int(1e11)),
                           1.0, peaks)
        assert full_bw["fraction_of_peak"] == pytest.approx(1.0)

    def test_models_scale_with_shapes(self):
        from repro.obs import roofline as r

        small = r.pairwise_sq_dist_cost(4, 1000, 32)
        big = r.pairwise_sq_dist_cost(4, 2000, 32)
        assert big.bytes > small.bytes and big.flops == 2 * small.flops - 0 \
            or big.flops > small.flops
        t = r.pair_join_cost(1024, 32, 10)
        pruned = r.pair_join_cost(1024, 32, 10, tiles_visited=3)
        assert pruned.bytes < t.bytes

    def test_kernel_spans_carry_roofline_attrs(self):
        from repro import obs
        from repro.kernels import ops
        from repro.obs import roofline

        d = np.random.default_rng(0).normal(size=(4, 600)).astype(np.float32)
        with obs.tracing() as tr:
            ops.topk_smallest(d, 8)
        (span,) = tr.spans
        expect = roofline.topk_cost(4, 600, 8)
        assert span.attrs["bytes"] == expect.bytes
        assert span.attrs["flops"] == expect.flops

    def test_ops_inside_jit_not_instrumented(self):
        """Kernel instrumentation must skip abstract tracers: an op
        called inside an enclosing jit trace records no span."""
        import jax

        from repro import obs
        from repro.kernels import ops

        d = np.random.default_rng(0).normal(size=(2, 300)).astype(np.float32)

        @jax.jit
        def f(x):
            return ops.topk_smallest(x, 4)[0]

        with obs.tracing() as tr:
            f(d).block_until_ready()
        assert tr.spans == []


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


class TestExport:
    def _sample(self):
        from repro import obs

        with obs.tracing() as tr:
            with obs.span("root", note="hi"):
                with obs.span("kernel.x", bytes=1000, flops=4000,
                              intensity=4.0):
                    sum(range(200_000))
        return tr

    def test_chrome_trace_schema(self, tmp_path):
        from repro import obs

        tr = self._sample()
        obj = obs.to_chrome_trace(tr)
        obs.validate_chrome_trace(obj)
        events = [e for e in obj["traceEvents"] if e["ph"] == "X"]
        assert [e["name"] for e in events] == ["root", "kernel.x"]
        assert events[0]["ts"] == 0.0  # rebased to the earliest span
        # kernel event got its roofline placement merged into args
        assert "achieved_gbps" in events[1]["args"]
        assert events[1]["args"]["bound"] in ("memory", "compute")
        # round-trips through a file as valid JSON
        path = obs.save_chrome_trace(str(tmp_path / "t.json"), tr)
        obs.validate_chrome_trace(json.load(open(path)))

    def test_validate_rejects_bad_traces(self):
        from repro import obs

        with pytest.raises(ValueError):
            obs.validate_chrome_trace({"events": []})
        with pytest.raises(ValueError):
            obs.validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
        with pytest.raises(ValueError):
            obs.validate_chrome_trace({"traceEvents": [
                {"name": "a", "ph": "X", "pid": 1, "tid": 1,
                 "ts": -5.0, "dur": 1.0}]})

    def test_sanitized_args(self):
        from repro import obs
        from repro.obs.trace import Span

        spans = [Span("s", 0.0, 1.0, -1,
                      {"np": np.int64(7), "inf": float("inf"),
                       "nested": {"a": np.float32(1.5)}})]
        obj = obs.to_chrome_trace(spans)
        args = obj["traceEvents"][1]["args"]
        assert args["np"] == 7 and isinstance(args["np"], int)
        assert args["inf"] == "inf"
        json.dumps(obj)  # fully serializable

    def test_coverage_metric(self):
        from repro.obs.export import coverage
        from repro.obs.trace import Span

        # root 10s fully covered by children; leaf roots count as covered
        spans = [Span("r", 0.0, 10.0, -1), Span("a", 0.0, 6.0, 0),
                 Span("b", 6.0, 10.0, 0)]
        assert coverage(spans) == pytest.approx(1.0)
        # a childless root is a standalone measurement: fully covered
        assert coverage([Span("leaf", 0.0, 1.0, -1)]) == 1.0
        # a root whose children explain only part of its wall dilutes it
        spans.extend([Span("half", 0.0, 10.0, -1),
                      Span("bit", 0.0, 2.0, 3)])
        assert coverage(spans) == pytest.approx(0.6)
        assert coverage([]) == 1.0

    def test_stage_summary(self):
        from repro import obs

        tr = self._sample()
        s = obs.stage_summary(tr)
        assert s["n_spans"] == 2 and s["coverage"] >= 0.95
        assert s["stages"]["kernel.x"]["bytes"] == 1000
        assert "achieved_gflops" in s["stages"]["kernel.x"]
        assert "bytes" not in s["stages"]["root"]  # no model → no roofline
        json.dumps(s)


# ---------------------------------------------------------------------------
# satellites: reservoir, WorkStats round-trip, provenance, perf gate
# ---------------------------------------------------------------------------


class TestLatencyReservoir:
    def test_100k_observations_bounded(self):
        from repro.serve.metrics import LatencyReservoir

        r = LatencyReservoir(cap=512)
        for i in range(100_000):
            r.observe(float(i % 1000))
        assert len(r) <= 512
        assert r.count == 100_000

    def test_quantiles_stay_stable(self):
        """Uniform stream: reservoir p50/p99 track the true quantiles."""
        from repro.serve.metrics import LatencyReservoir, _quantiles_us

        r = LatencyReservoir(cap=2048, seed=1)
        rng = np.random.default_rng(0)
        xs = rng.uniform(0.0, 1.0, size=50_000)
        for x in xs:
            r.observe(float(x))
        p50, p99 = _quantiles_us(r)
        assert abs(p50 - 0.5e6) < 0.05e6
        assert abs(p99 - 0.99e6) < 0.03e6

    def test_serve_metrics_memory_bounded(self):
        from repro.serve.metrics import ServeMetrics

        m = ServeMetrics(clock=lambda: 0.0, latency_cap=256)
        for i in range(100_000):
            m.on_complete((8, 16), latency_s=0.001 * (i % 7))
        assert len(m._latencies) <= 256
        assert len(m._buckets[(8, 16)][3]) <= 256
        snap = m.snapshot()
        assert snap.completed == 100_000
        assert snap.p50_us > 0

    def test_small_stream_kept_verbatim(self):
        from repro.serve.metrics import LatencyReservoir

        r = LatencyReservoir(cap=100)
        for x in (1.0, 2.0, 3.0):
            r.observe(x)
        assert r.samples() == [1.0, 2.0, 3.0]

    def test_default_seeds_are_independent(self):
        """Regression: default-seeded reservoirs used to share seed=0,
        so co-resident reservoirs fed the same stream kept/evicted the
        same slots in lockstep — correlated quantile error.  Two fresh
        reservoirs over one stream must now retain different samples."""
        from repro.serve.metrics import LatencyReservoir

        a, b = LatencyReservoir(cap=32), LatencyReservoir(cap=32)
        for i in range(4096):
            v = float(i)
            a.observe(v)
            b.observe(v)
        assert a.samples() != b.samples()
        # explicit seeds still reproduce a single trajectory
        c, d = LatencyReservoir(cap=32, seed=7), LatencyReservoir(
            cap=32, seed=7)
        for i in range(4096):
            c.observe(float(i))
            d.observe(float(i))
        assert c.samples() == d.samples()


class TestWorkStats:
    def test_round_trip(self):
        from repro.index.types import WorkStats

        w = WorkStats(rounds=3, candidates_verified=100,
                      node_distance_computations=7,
                      point_distance_computations=50, pairs_verified=9,
                      tiles_pruned=2)
        d = w.as_dict()
        json.dumps(d)
        assert WorkStats.from_dict(d) == w

    def test_from_dict_tolerates_drift(self):
        from repro.index.types import WorkStats

        w = WorkStats.from_dict({"rounds": 2, "new_counter_from_future": 5})
        assert w.rounds == 2
        assert WorkStats.from_dict({}) == WorkStats()

    def test_numpy_ints_coerced(self):
        from repro.index.types import WorkStats

        w = WorkStats(rounds=np.int64(4))
        assert isinstance(w.as_dict()["rounds"], int)
        json.dumps(w.as_dict())


class TestProvenance:
    def test_fields_present(self):
        import benchmarks.common as common

        p = common.provenance()
        for key in ("git_sha", "timestamp_utc", "jax_version",
                    "device_kind", "hostname"):
            assert p[key]
        from repro.obs.roofline import PEAKS

        assert p["device_kind"] in PEAKS
        json.dumps(p)


class TestPerfGate:
    def _payload(self, module="m", rows=None, prov=True):
        p = {"module": module, "rows": rows or []}
        if prov:
            p["provenance"] = {"device_kind": "cpu", "hostname": "host-a"}
        return p

    def test_passes_identical_trajectory(self):
        from benchmarks.perf_gate import compare

        base = {"m": self._payload(rows=[
            {"name": "r1", "us_per_call": 100.0}])}
        res = compare(base, json.loads(json.dumps(base)))
        assert res.ok and len(res.compared) == 1

    def test_fails_injected_2x_regression(self):
        from benchmarks.perf_gate import compare

        base = {"m": self._payload(rows=[
            {"name": "r1", "us_per_call": 100.0},
            {"name": "r2", "us_per_call": 100.0}])}
        cur = json.loads(json.dumps(base))
        cur["m"]["rows"][0]["us_per_call"] = 200.0
        res = compare(base, cur, threshold=0.25)
        assert not res.ok
        assert [c.name for c in res.regressions] == ["r1"]
        assert res.regressions[0].delta == pytest.approx(1.0)

    def test_within_threshold_passes(self):
        from benchmarks.perf_gate import compare

        base = {"m": self._payload(rows=[
            {"name": "r1", "us_per_call": 100.0}])}
        cur = json.loads(json.dumps(base))
        cur["m"]["rows"][0]["us_per_call"] = 120.0  # +20% < 25%
        assert compare(base, cur, threshold=0.25).ok

    def test_waiver_respected(self):
        from benchmarks.perf_gate import compare

        base = {"m": self._payload(rows=[
            {"name": "r1", "us_per_call": 100.0}])}
        cur = json.loads(json.dumps(base))
        cur["m"]["rows"][0]["us_per_call"] = 500.0
        res = compare(base, cur, waivers={("m", "r1")})
        assert res.ok and len(res.waived) == 1

    def test_cross_device_skipped(self):
        from benchmarks.perf_gate import compare

        base = {"m": self._payload(rows=[
            {"name": "r1", "us_per_call": 100.0}])}
        cur = json.loads(json.dumps(base))
        cur["m"]["rows"][0]["us_per_call"] = 1000.0
        cur["m"]["provenance"]["device_kind"] = "tpu"
        res = compare(base, cur)
        assert res.ok and res.skipped and not res.compared

    def test_cross_machine_skipped_unless_allowed(self):
        from benchmarks.perf_gate import compare

        base = {"m": self._payload(rows=[
            {"name": "r1", "us_per_call": 100.0}])}
        cur = json.loads(json.dumps(base))
        cur["m"]["rows"][0]["us_per_call"] = 1000.0
        cur["m"]["provenance"]["hostname"] = "host-b"
        assert compare(base, cur).ok  # skipped
        res = compare(base, cur, allow_cross_machine=True)
        assert not res.ok

    def test_quality_rows_never_gate(self):
        from benchmarks.perf_gate import compare

        base = {"m": self._payload(rows=[
            {"name": "q", "recall": 0.99},
            {"name": "z", "us_per_call": 0.0}])}
        res = compare(base, json.loads(json.dumps(base)))
        assert res.ok and not res.compared

    def test_self_test(self):
        from benchmarks.perf_gate import self_test

        assert self_test()

    def test_gate_over_committed_trajectory(self):
        """The committed BENCH files pass a self-comparison — the
        exact invocation CI runs."""
        from benchmarks.perf_gate import load_bench_dir, compare

        committed = load_bench_dir(".")
        if not committed:
            pytest.skip("no committed BENCH files in cwd")
        assert compare(committed, committed).ok

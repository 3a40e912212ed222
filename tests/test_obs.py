"""Tests for repro.obs — tracing, roofline, exporters, perf gate
(DESIGN.md §12).

Covers span nesting across the engines (CP, streaming fan-out, serve
flush) and the span trees of a search, a CP job and a served request
with their shared ids; spans recorded during a ``jax.profiler``
session, on the profile's clock; traced searches that return the
untraced bits; near-zero disabled-mode overhead; the compile counter;
Chrome-trace schema validity with ≥95% root coverage, the roofline
models, the bounded latency reservoir, WorkStats round-tripping, and
the perf gate's pass/fail/waiver/cross-device behavior.
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

    def test_disabled_overhead_under_2pct(self):
        """The acceptance bar: with neither ``obs.tracing()`` nor a
        profiler session on, the facade's five span sites (each a flag
        check plus ``TraceAnnotation.is_enabled()``) add <2% to the
        fused query microbench.  Medians over interleaved samples, with
        a retry to absorb scheduler noise on a busy container."""
        import time

        from repro.core.flat_index import (ann_query, build_flat_index,
                                           candidate_budget)
        from repro.obs import trace

        data = make_clustered(4096, 32)
        q = data[:8] + 0.01
        index = build_flat_index(data, m=15)
        T = candidate_budget(index.params, 4096, 10)

        def call():
            i, d, c = ann_query(index, q, k=10, T=T, fused=True,
                                with_count=True)
            return np.asarray(i), np.asarray(d), np.asarray(c)

        def instrumented():
            with trace.span("index.search"):
                with trace.span("ann.dispatch"):
                    i, d, c = ann_query(index, q, k=10, T=T, fused=True,
                                        with_count=True)
                with trace.span("ann.wait"):
                    i = np.asarray(i)
                with trace.span("ann.answer"):
                    d = np.asarray(d)
                with trace.span("ann.counts"):
                    c = np.asarray(c)
            return i, d, c

        call()  # compile
        assert not trace.recording()

        for attempt in range(3):
            base, inst = [], []
            for _ in range(40):
                for fn, out in ((call, base), (instrumented, inst)):
                    t0 = time.perf_counter()
                    fn()
                    out.append(time.perf_counter() - t0)
            overhead = float(np.median(inst)) / float(np.median(base)) - 1.0
            if overhead < 0.02:
                break
        else:
            pytest.fail(f"disabled-tracing overhead {overhead:.1%} >= 2%")
        assert trace.get_tracer().spans == []


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

    def test_cp_trace(self, data):
        from repro import obs
        from repro.index import IndexConfig, build_index

        idx = build_index(data, IndexConfig(
            backend="flat", options={"force": "interpret"}))
        tr = _trace_of(lambda: idx.cp_search(3))
        names = [s.name for s in tr.spans]
        for stage in ("index.cp_search", "cp.d2h", "cp.sort", "cp.gather",
                      "cp.h2d", "cp.join", "cp.reverify"):
            assert stage in names
        assert obs.coverage(tr) >= 0.95

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
# the profiler's clock: spans record during a jax.profiler session too
# ---------------------------------------------------------------------------


def _profile(tmp_path, fn):
    """Run ``fn`` inside a jax.profiler session; returns the session's
    ProfileData."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    return ProfileData.from_file(path)


class TestProfilerSpans:
    def test_profiler_session_records_without_tracing(self, tmp_path):
        from repro.obs import trace

        with trace.span("before"):
            pass
        assert trace.get_tracer().spans == []  # neither switch on

        def body():
            assert trace.recording() and not trace.enabled()
            with trace.span("outer"):
                with trace.span("inner"):
                    pass

        _profile(tmp_path, body)
        assert not trace.recording()
        with trace.span("after"):
            pass
        spans = trace.get_tracer().drain()
        assert [s.name for s in spans] == ["outer", "inner"]
        assert spans[1].parent == 0

    def test_span_stamps_map_onto_the_profile(self, tmp_path):
        """Each span's in-memory stamps, moved onto the profile by
        ``profile_start_time``, fall within 1 ms of its annotation."""
        import time

        from repro.obs import trace

        def body():
            with trace.span("probe.outer", call=5):
                time.sleep(0.003)
                with trace.span("probe.inner"):
                    time.sleep(0.003)

        data = _profile(tmp_path, body)
        spans = {s.name: s for s in trace.get_tracer().drain()}
        start = None
        events = {}
        for plane in data.planes:
            if plane.name == "Task Environment":
                start = dict(plane.stats)["profile_start_time"]
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("probe."):
                        events[ev.name] = ev
        assert start is not None and set(events) == set(spans)
        for name, sp in spans.items():
            ev = events[name]
            assert abs(trace.to_unix_ns(sp.t0) - start - ev.start_ns) < 1e6
            assert abs(trace.to_unix_ns(sp.t1) - start - ev.end_ns) < 1e6
        # the annotation carries the span's id attrs, the child's too
        assert dict(events["probe.inner"].stats)["call"] == 5


@pytest.mark.parametrize("backend,options", [
    ("flat", {"fused": True, "force": "interpret"}),
    ("flat-pq", {"fused": True}),
    ("sharded-flat", {}),
])
def test_traced_search_is_bit_identical(backend, options, tmp_path):
    """A traced search runs the same jitted program as an untraced one:
    the same ids and the same distance bits, under ``obs.tracing()``
    and under a profiler session."""
    from repro import obs
    from repro.index import IndexConfig, build_index

    data = make_clustered(2048, 24, seed=3)
    q = data[:6] + 0.01
    idx = build_index(data, IndexConfig(backend=backend, options=options))
    plain = idx.search(q, k=5)
    with obs.tracing() as tr:
        traced = idx.search(q, k=5)
    assert "ann.dispatch" in [s.name for s in tr.spans]
    out = {}
    _profile(tmp_path, lambda: out.setdefault("r", idx.search(q, k=5)))
    for res in (traced, out["r"]):
        np.testing.assert_array_equal(plain.indices, res.indices)
        np.testing.assert_array_equal(plain.distances.view(np.uint32),
                                      res.distances.view(np.uint32))


class TestSpanTrees:
    @pytest.fixture(scope="class")
    def data(self):
        return make_clustered(2048, 24, seed=4)

    @staticmethod
    def _children(spans, parent_name):
        (pi,) = [i for i, s in enumerate(spans) if s.name == parent_name]
        return pi, [s for s in spans if s.parent == pi]

    def test_search_tree_shares_the_call_id(self, data):
        from repro import obs
        from repro.index import IndexConfig, build_index

        idx = build_index(data, IndexConfig(
            backend="flat", options={"fused": True, "force": "interpret"}))
        with obs.tracing() as tr:
            idx.search(data[:4] + 0.01, k=5)
        pi, kids = self._children(tr.spans, "index.search")
        assert tr.spans[pi].parent == -1
        assert [s.name for s in kids] == ["ann.dispatch", "ann.wait",
                                          "ann.answer", "ann.counts"]
        call = tr.spans[pi].attrs["call"]
        assert all(s.attrs["call"] == call for s in kids)

    def test_cp_search_tree_shares_the_call_id(self, data):
        from repro import obs
        from repro.index import IndexConfig, build_index

        idx = build_index(data, IndexConfig(
            backend="flat", options={"force": "interpret"}))
        with obs.tracing() as tr:
            idx.search(data[:2], k=3)  # another call, another id
            idx.cp_search(3)
        pi, kids = self._children(tr.spans, "index.cp_search")
        assert [s.name for s in kids] == ["cp.d2h", "cp.sort", "cp.gather",
                                          "cp.h2d", "cp.join", "cp.reverify"]
        call = tr.spans[pi].attrs["call"]
        assert all(s.attrs["call"] == call for s in kids)
        (search,) = [s for s in tr.spans if s.name == "index.search"]
        assert search.attrs["call"] != call

    def test_served_request_tree_shares_the_rid(self, data):
        from repro import obs
        from repro.serve import RequestScheduler, ServeConfig
        from repro.serve.serve_step import make_retrieval_step

        step, _ = make_retrieval_step(data[:512],
                                      np.arange(512, dtype=np.float32), k=8)
        sched = RequestScheduler(step, config=ServeConfig(
            b_max=8, default_deadline_ms=1e6, max_queue=4096, cache=False))
        with obs.tracing() as tr:
            tickets = [sched.submit(data[i], k=4) for i in range(3)]
            sched.drain()
        rids = [t.id for t in tickets]
        spans = tr.spans
        submits = [s for s in spans if s.name == "serve.submit"]
        assert [s.attrs["rid"] for s in submits] == rids
        assert all(s.parent == -1 for s in submits)
        pi, kids = self._children(spans, "serve.flush")
        assert spans[pi].attrs["rids"] == rids
        waits = [s for s in kids if s.name == "serve.queue_wait"]
        assert [s.attrs["rid"] for s in waits] == rids
        for sub, wait in zip(submits, waits):
            assert wait.t0 == sub.t0 and wait.t1 >= sub.t1
        names = [s.name for s in kids]
        assert names.index("serve.stage") < names.index("serve.search") \
            < names.index("serve.deliver")
        si, inner = self._children(spans, "serve.search")
        assert [s.name for s in inner] == ["index.search"]


def test_jit_compiles_counted_once_per_shape():
    """``jit_compiles_total`` counts a backend compile of a new shape
    once and a warm call not at all; a compile inside a traced region
    leaves a ``jit.compile`` span."""
    import jax
    import jax.numpy as jnp

    from repro import obs

    f = jax.jit(lambda x: x * 3 + 1)
    a, b = jnp.ones(5), jnp.ones(7)

    def count():
        c = obs.get_registry().get("jit_compiles_total")
        return 0.0 if c is None else c.get()

    c0 = count()
    f(a).block_until_ready()
    c1 = count()
    f(a).block_until_ready()
    c2 = count()
    with obs.tracing() as tr:
        f(b).block_until_ready()
    c3 = count()
    assert (c1 - c0, c2 - c1, c3 - c2) == (1.0, 0.0, 1.0)
    (sp,) = [s for s in tr.spans if s.name == "jit.compile"]
    assert sp.duration_s > 0.0


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
        few = r.verify_topk_cost(4, 100, 32, 10)
        more = r.verify_topk_cost(4, 400, 32, 10)
        assert more.bytes > few.bytes and more.flops > few.flops

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

"""The readers of the program's own names on a synthetic run: device ops
by their named scopes, and program spans from ``repro.obs.trace``."""
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from devtrace import Trace  # noqa: E402

MS = 1_000_000  # ns
SEL = "jit(ann_query)/jit(fused_ann_query)/select"


def reader(name):
    return run.load_reader(name, BENCH.parent)


@pytest.fixture
def tracer():
    """The process's span collector, emptied before and after."""
    from repro.obs.trace import get_tracer

    tr = get_tracer()
    tr.drain()
    yield tr
    tr.drain()


def _profile():
    """A serialized profile of one search: the ops' name stacks sit in
    their event metadata, as on a TPU, one of them interned."""
    from jax.profiler import ProfileData

    ops = [  # (name, name stack, start ms, end ms)
        ("%pairwise_sq_dist_pallas.1", "jit(ann_query)/jit(fused_ann_query)/"
         "estimate/jit(pairwise_sq_dist_pallas)/pairwise_sq_dist_pallas/"
         "pallas_call:", 0, 1),
        ("%radius_select_pallas.1", SEL + "/jit(radius_select_pallas)/radius/"
         "radius_select_pallas/pallas_call:", 1, 2),
        # the exact sort's reroute and, nested in it, its branch
        ("%cond.1", SEL + "/finish/cond:", 2, 6),
        ("%sort.5", SEL + "/finish/cond/branch_1_fun/sort:", 3, 5),
        ("%fusion.28", SEL + "/finish/slice:", 6, 8),
        ("%verify_topk_pallas.1", "jit(ann_query)/jit(fused_ann_query)/"
         "verify/jit(verify_topk_pallas)/kernel/verify_topk_pallas/"
         "pallas_call:", 8, 9)]
    events, meta = [], []
    for i, (name, stack, s, e) in enumerate(ops, start=1):
        events.append(f"events {{ metadata_id: {i} offset_ps: {s * MS * 1000}"
                      f" duration_ps: {(e - s) * MS * 1000} }}")
        stat = (f"ref_value: 100" if name == "%cond.1"
                else f'str_value: "{stack}"')
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{name}" stats {{ metadata_id: 7 {stat} }} }} }}')
    text = (f'planes {{ id: 1 name: "/device:TPU:0" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 '
            f'{" ".join(events)} }} {" ".join(meta)} '
            f'stat_metadata {{ key: 7 value {{ id: 7 name: "tf_op" }} }} '
            f'stat_metadata {{ key: 100 value {{ id: 100 '
            f'name: "{SEL}/finish/cond:" }} }} }} '
            f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 1 name: "python" '
            f'timestamp_ns: 0 events {{ metadata_id: 1 offset_ps: 0 '
            f'duration_ps: {10 * MS * 1000} }} }} event_metadata {{ key: 1 '
            f'value {{ id: 1 name: "bench.window" }} }} }}')
    return ProfileData.text_proto_to_serialized_xspace(text)


def test_device_ops_carry_their_name_stacks():
    import xplane

    devices, spans = xplane.read(_profile())
    (ops,) = devices.values()
    assert [o.name for o in ops][-2:] == ["%fusion.28",
                                          "%verify_topk_pallas.1"]
    assert ops[2].tf_op == SEL + "/finish/cond:"  # the interned string
    assert (ops[2].start, ops[2].end) == (2 * MS, 6 * MS)
    assert xplane.window(spans) == (0, 10 * MS)
    # a scope's seconds are the union of its ops: select [1, 8] ms; the
    # reroute [2, 6] and its branch inside it count once
    win = xplane.window(spans)
    assert xplane.scope_seconds(devices, win, re.compile(
        r"(^|/)select/")) == pytest.approx(7e-3)
    assert xplane.scope_seconds(devices, win, re.compile(
        r"/finish/cond")) == pytest.approx(4e-3)


def test_the_runs_profile_is_found_by_its_window(tmp_path):
    import xplane

    # none under <root>/.bench_trace yet
    assert xplane.this_runs_profile(
        Trace({}, [("bench.window", 0, 10 * MS)]), str(tmp_path)) is None
    keep = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "t"
    keep.mkdir(parents=True)
    (keep / "host.xplane.pb").write_bytes(_profile())
    devices, win = xplane.this_runs_profile(
        Trace({}, [("bench.window", 5, 5 + 10 * MS)]), str(tmp_path))
    assert win == (0, 10 * MS) and len(devices["/device:TPU:0"]) == 6
    # another window length: another run's profile
    assert xplane.this_runs_profile(
        Trace({}, [("bench.window", 0, 9 * MS)]), str(tmp_path)) is None


def test_cp_host_share_of_the_jobs(tracer):
    for t in (0.0, 10.0):  # two jobs of 10 s
        tracer.add_span("index.cp_search", t, t + 10.0)
        tracer.add_span("cp.d2h", t, t + 0.5)
        tracer.add_span("cp.sort", t + 0.5, t + 0.75)
        tracer.add_span("cp.gather", t + 0.75, t + 1.0)
        tracer.add_span("cp.h2d", t + 1.0, t + 1.25)
        tracer.add_span("cp.join", t + 1.25, t + 9.75)
        tracer.add_span("cp.reverify", t + 9.75, t + 10.0)
    ctx = SimpleNamespace(trace=None)
    assert reader("cp.host_share").read(ctx) == pytest.approx(15.0)


def test_cp_host_share_without_spans_is_none(tracer):
    tracer.add_span("index.search", 0.0, 1.0)
    assert reader("cp.host_share").read(SimpleNamespace(trace=None)) is None


def test_queue_wait_p95_is_the_nearest_rank(tracer):
    for rid in range(40):  # waits of 1..40 ms
        tracer.add_span("serve.queue_wait", 5.0, 5.0 + (rid + 1) / 1e3,
                        rid=rid)
    tracer.add_span("serve.flush", 0.0, 9.0)  # not a wait
    # nearest rank: the ceil(0.95 · 40) = 38th smallest
    assert reader("serve.queue_wait_p95_ms").read(
        SimpleNamespace(trace=None)) == pytest.approx(38.0)


def test_queue_wait_without_spans_is_none(tracer):
    assert reader("serve.queue_wait_p95_ms").read(
        SimpleNamespace(trace=None)) is None

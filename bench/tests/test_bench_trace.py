"""Trace reduction on a small synthetic trace: busy union, per-name
sums, idle gaps named by the host spans, and the read of planes."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import devtrace  # noqa: E402
from devtrace import Op, Trace  # noqa: E402

MS = 1_000_000  # ns


def _trace():
    ops = [Op("pairwise_sq_dist_pallas.1", "jit(f)/jit(pairwise_sq_dist_pallas)/pallas_call", 1 * MS, 3 * MS),
           Op("fusion.2", "jit(f)/jit(radius_select_pallas)/gather", 2 * MS, 4 * MS),
           Op("verify_topk_pallas.1", "jit(f)/jit(verify_topk_pallas)/pallas_call", 6 * MS, 9 * MS),
           Op("copy.9", "", 11 * MS, 12 * MS)]  # ends past the window
    spans = [("bench.window", 0, 10 * MS),
             ("bench.search", 0, 10 * MS),
             ("bench.submit", 4 * MS, 5 * MS)]
    return Trace({"/device:TPU:0": ops}, spans)


def test_busy_union_clips_to_the_window():
    tr = _trace()
    # [1, 4] overlapping ops merge; [6, 9]; copy.9 lies outside
    assert tr.busy_s() == pytest.approx(6e-3)
    assert tr.window_s == pytest.approx(10e-3)
    ctx = type("C", (), {"trace": tr})
    assert devtrace.idle_percent(ctx) == pytest.approx(40.0)


def test_per_name_sums_match_hlo_or_op_name():
    tr = _trace()
    assert tr.op_s([r"^pairwise_sq_dist_pallas"]) == pytest.approx(2e-3)
    assert tr.op_s([r"jit\(radius_select_pallas\)"]) == pytest.approx(2e-3)
    assert tr.op_s([r"verify", r"radius"]) == pytest.approx(5e-3)
    assert tr.top_ops(2) == [["verify_topk_pallas.1", pytest.approx(3e-3)],
                             ["pairwise_sq_dist_pallas.1",
                              pytest.approx(2e-3)]]


def test_idle_gaps_go_to_the_innermost_covering_span():
    gaps = dict((k, v) for k, v in _trace().idle_gaps())
    # idle: [0, 1] and [9, 10] under bench.search, [4, 6] half under
    # bench.submit (inner, 1 ms) and half under search: submit covers
    # 1 ms of it, search 2 ms, so the whole gap goes to search
    assert gaps == {"bench.search": pytest.approx(4e-3)}
    spans = [("bench.window", 0, 10 * MS), ("bench.pump", 4 * MS, 6 * MS),
             ("bench.search", 0, 10 * MS)]
    tr = Trace(_trace().devices, spans)
    gaps = dict((k, v) for k, v in tr.idle_gaps())
    assert gaps["bench.pump"] == pytest.approx(2e-3)  # equal cover: inner
    assert gaps["bench.search"] == pytest.approx(2e-3)


def test_a_trace_without_a_window_span_is_refused():
    with pytest.raises(ValueError):
        Trace({}, [("bench.search", 0, 1)])


TEXT = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000
             stats { metadata_id: 7 str_value: "jit(f)/jit(g)/pallas_call" } }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "g_pallas.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.3" } }
  event_metadata { key: 3 value { id: 3 name: "jit_f" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction(f)" } }
}
'''


def test_reads_device_ops_and_bench_spans_from_a_profile():
    from jax.profiler import ProfileData

    tr = Trace.from_profile(ProfileData.from_text_proto(TEXT))
    ops = tr.devices["/device:TPU:0"]
    assert [(o.name, o.start, o.end) for o in ops] == [
        ("g_pallas.1", 1000, 3000), ("fusion.3", 6000, 7000)]
    assert ops[0].op_name == "jit(f)/jit(g)/pallas_call"
    assert tr.spans == [("bench.window", 0, 10000)]  # only bench. spans
    assert tr.busy_s() == pytest.approx(3e-6)
    assert tr.op_s([r"jit\(g\)"]) == pytest.approx(2e-6)


def test_phase_time_between_kernels_of_one_program_run():
    """Layers of one fused program are timed by the order of its
    top-level operations: boundaries skip ops nested in a loop."""
    ops = [Op("%copy.1 = f32[8,15] copy()", "", 0, 1 * MS),
           Op("%pairwise_sq_dist_pallas.1 = f32[...]", "", 1 * MS, 2 * MS),
           Op("%while.3 = (...) while()", "", 2 * MS, 6 * MS),
           # nested in the loop: looks like verify's relayout, is not
           Op("%reshape.9 = f32[64,1,99] reshape()", "", 3 * MS, 4 * MS),
           Op("%reshape.19 = f32[1000,1,256] reshape()", "", 7 * MS, 8 * MS),
           Op("%verify_topk_pallas.1 = (...)", "", 8 * MS, 9 * MS),
           Op("%sqrt.2 = f32[8,10] sqrt()", "", 9 * MS, 10 * MS)]
    mods = [Op("jit_ann_query(123)", "", 0, 10 * MS),
            Op("jit_other(5)", "", 10 * MS, 12 * MS)]
    tr = Trace({"/device:TPU:0": ops}, [("bench.window", 0, 20 * MS)],
               {"/device:TPU:0": mods})
    est = (r"^%pairwise_sq_dist_pallas", "end")
    first = (r"^%reshape[\w.-]* = f32\[\d+,1,\d+\]|^%verify_topk_pallas",
             "start")
    mod = r"^jit_ann_query\b"
    assert tr.phase_s(mod, None, est) == pytest.approx(2e-3)
    # 2 → 7 ms, of which 2 → 6 busy
    assert tr.phase_s(mod, est, first) == pytest.approx(4e-3)
    assert tr.phase_s(mod, first, (r"^%verify_topk_pallas", "end")) == \
        pytest.approx(2e-3)
    assert tr.phase_s(r"^jit_none") == 0.0
    assert tr.phase_s(mod, (r"^%absent", "end")) == 0.0

"""Each per-layer reader's work count against hand arithmetic, and the
roofline share it makes of a measured time."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from devtrace import Op, Trace  # noqa: E402

PEAKS = {"flops_per_s": 1.97e14, "bytes_per_s": 8.19e11}
ANN = {"calls": 2, "B": 64, "k": 10, "n": 1_000_000, "d": 256, "m": 15,
       "T": 96_704, "rerouted": 0}


def reader(name):
    return run.load_reader(name, BENCH.parent)


def test_estimate_work():
    flops, nbytes = reader("estimate.roofline").work(ANN)
    assert flops == 2 * (2 * 64 * 1_000_000 * 15)
    assert nbytes == 2 * 4 * (1_000_000 * 15 + 64 * 15 + 64 * 1_000_000)


def test_select_work():
    flops, nbytes = reader("select.roofline").work(ANN)
    assert flops == 2 * 64 * 1_000_000
    assert nbytes == 2 * (4 * 64 * 1_000_000 + 8 * 64 * 96_704)


def test_verify_work():
    flops, nbytes = reader("verify.roofline").work(ANN)
    assert flops == 2 * (2 * 64 * 96_704 * 256)
    assert nbytes == 2 * (4 * (64 * 96_704 * 256 + 64 * 256) + 8 * 64 * 10)


def test_pair_join_work():
    c = {"jobs": 3, "n": 269_000, "d": 500, "k": 10,
         "pairs_verified": 4_000_000_000}
    flops, nbytes = reader("pair_join.roofline").work(c)
    assert flops == 2 * 500 * 4_000_000_000
    assert nbytes == 3 * 4 * 269_000 * 500


def test_counter_shares():
    ctx = SimpleNamespace(trace=None, counters={
        "cp": {"jobs": 2, "n": 1001, "d": 8, "k": 10,
               "pairs_verified": 100_100},
        "serve": {"real_slots": 30, "padded_slots": 40, "cache_hits": 0}})
    # n(n-1)/2 = 500,500 pairs a job, two jobs
    assert reader("cp.pair_share").read(ctx) == pytest.approx(10.0)
    assert reader("serve.pad_share").read(ctx) == pytest.approx(25.0)
    assert reader("estimate.roofline").read(ctx) is None  # no ann counters


@pytest.mark.parametrize("calls,rerouted,share", [(31, 0, 0.0),
                                                   (31, 2, 200 / 31),
                                                   (4, 4, 100.0)])
def test_reroute_share(calls, rerouted, share):
    ctx = SimpleNamespace(trace=None, counters={
        "ann": dict(ANN, calls=calls, rerouted=rerouted)})
    assert reader("select.reroute_share").read(ctx) == pytest.approx(share)


def test_reroute_share_needs_a_batch():
    read = reader("select.reroute_share").read
    assert read(SimpleNamespace(counters={})) is None
    assert read(SimpleNamespace(counters={"ann": dict(ANN, calls=0)})) is None


def test_roofline_share_of_a_measured_time():
    # estimate: 2·2·64·1M·15 flops = 3.84e9 → 19.5 µs at peak; bytes
    # 2·4·(15M + 960 + 64M) = 632M → 772 µs at 819 GB/s: memory-bound
    ms = 1_000_000
    ops = [Op("%pad.18 = f32[1000064,128] pad()", "", 0, 1 * ms),
           Op("%pairwise_sq_dist_pallas.1 = f32[64,1000064]", "", 1 * ms,
              2 * ms),
           Op("%pairwise_sq_dist_pallas.1 = f32[64,1000064]", "", 5 * ms,
              7 * ms),
           Op("%radius_select_pallas.1 = (...)", "", 7 * ms, 8 * ms)]
    mods = [Op("jit_ann_query(1)", "", 0, 3 * ms),
            Op("jit_ann_query(1)", "", 5 * ms, 8 * ms)]
    tr = Trace({"/device:TPU:0": ops}, [("bench.window", 0, 10 * ms)],
               {"/device:TPU:0": mods})
    ctx = SimpleNamespace(trace=tr, counters={"ann": ANN}, peaks=PEAKS)
    least = 2 * 4 * (1_000_000 * 15 + 64 * 15 + 64 * 1_000_000) / 8.19e11
    assert reader("estimate.roofline").read(ctx) == pytest.approx(
        100 * least / 4e-3)
    # no matching device time: the metric is left out, never 0
    assert reader("verify.roofline").read(ctx) is None


def test_device_idle_readers():
    ms = 1_000_000
    tr = Trace({"/device:TPU:0": [Op("x", "", 0, 3 * ms)]},
               [("bench.window", 0, 4 * ms)])
    for name in ("device_idle.ann", "device_idle.serve", "device_idle.cp"):
        assert reader(name).read(SimpleNamespace(trace=tr)) == \
            pytest.approx(25.0)
        assert reader(name).read(SimpleNamespace(trace=None)) is None

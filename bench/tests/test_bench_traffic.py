"""The open-loop generator: a fixed amount of work for every seed, and
latency counted from each request's due time."""
import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import drivers  # noqa: E402

MIX = {"kind": "open", "k": 10, "rate_per_s": 25.0}


def test_every_seed_replays_one_trace_rotated():
    a, qa = drivers.schedule(MIX, 40.0, 1)
    b, qb = drivers.schedule(MIX, 40.0, 2**31 + 5)
    assert len(a) == len(b) == 1000
    assert not np.array_equal(a, b)
    u = (np.arange(1000) + 0.5) / 1000
    gaps = np.round(-np.log1p(-u) / 25.0, 9)
    for t in (a, b):  # every gap is one of the same 1000 quantiles
        assert t[0] == 0.0 and (np.diff(t) > 0).all() and t[-1] < 40.0
        assert np.isin(np.round(np.diff(t), 9), gaps).all()
    # the mean rate is the configured one
    assert len(a) / 40.0 == pytest.approx(25.0)
    # one trace, rotated: the query rows tell the shift, and the gaps
    # follow their requests
    shift = (qb[0] - qa[0]) % 1000
    assert np.array_equal(qb, np.roll(qa, -shift))
    ga = np.diff(np.concatenate([a, [np.nan]]))
    gb = np.diff(np.concatenate([b, [np.nan]]))
    keep = ~np.isnan(np.roll(ga, -shift)) & ~np.isnan(gb)
    assert np.allclose(np.roll(ga, -shift)[keep], gb[keep])
    assert sorted(qa) == list(range(1000))  # every query new


@pytest.mark.parametrize("rate,seconds", [(16.0, 51.0), (0.5, 1.0),
                                          (30.0, 0.1)])
def test_count_follows_rate_and_window(rate, seconds):
    """rate·seconds requests (at least one), all due inside the window,
    and the same schedule on a second call with the same seed."""
    mix = dict(MIX, rate_per_s=rate)
    t, rows = drivers.schedule(mix, seconds, 11)
    assert len(t) == len(rows) == max(round(rate * seconds), 1)
    assert t[0] == 0.0 and (t < max(seconds, 1 / rate)).all()
    t2, rows2 = drivers.schedule(mix, seconds, 11)
    assert np.array_equal(t, t2) and np.array_equal(rows, rows2)


def test_latency_runs_from_the_due_time():
    due = [0.0, 0.1, 0.2, 0.3]
    # the generator stalled: requests 1 and 2 went out late, together
    delivered = [0.05, 0.45, 0.45, math.inf]
    ok = [True, True, False, True]
    lat = drivers.latencies(due, delivered, ok)
    assert lat[0] == pytest.approx(0.05)
    assert lat[1] == pytest.approx(0.35)  # not 0.45 - 0.35 from the send
    assert lat[2] == math.inf  # failed counts as missing any limit
    assert lat[3] == math.inf  # never delivered


def test_nearest_rank_tail():
    v = list(range(1, 101))
    assert drivers.nearest_rank(v, 0.95) == 95
    assert drivers.nearest_rank(v, 0.5) == 50
    assert drivers.nearest_rank([1.0, math.inf], 0.95) == math.inf

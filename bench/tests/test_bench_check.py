"""The kNN comparison on hand-made answers: the contract's breaks are bad
rows, the order is judged on the ids against the float64 truth, and
floats an ulp out of order between neighbours that tie to float32
rounding are no fault."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from check import judge, knn_numbers, load_limits  # noqa: E402

KNN_CELLS = ("deep1m.ann-batch", "deep1m.serve-open")


def _answer(x, q):
    """Each query's exact 10 nearest ids, ties by lower id, with their
    float32 distances, ascending."""
    diff = x[None].astype(np.float64) - q[:, None].astype(np.float64)
    true = np.sqrt((diff ** 2).sum(-1))
    ids = np.argsort(true, axis=1, kind="stable")[:, :10]
    return ids, np.take_along_axis(true, ids, 1).astype(np.float32)


def _case():
    rng = np.random.default_rng(0)
    x = (rng.normal(size=(2048, 256)) * 6).astype(np.float32)
    q = (x[:2] + rng.normal(size=(2, 256)) * 0.1).astype(np.float32)
    return (x, q) + _answer(x, q)


def test_exact_answer_reads_nothing():
    x, q, ids, dists = _case()
    got = knn_numbers(ids, dists, x, q, ids)
    assert got["bad_rows"] == 0 and got["rank_gap"] == 0.0
    assert got["recall_miss"] == 0.0 and got["dist_gap"] < 1e-7


def test_floats_an_ulp_out_of_order_are_no_fault():
    """A row copied onto a neighbour of the first query ties with it;
    the pair is reported in order, its floats an ulp apart the wrong
    way round, as two float32 computations of one distance can give."""
    x, q, ids, _ = _case()
    x[-1] = x[ids[0, 4]]
    ids, dists = _answer(x, q)
    tie = list(ids[0]).index(len(x) - 1)
    assert dists[0, tie - 1] == dists[0, tie]
    d = dists.copy()
    d[0, tie - 1] = np.nextafter(d[0, tie - 1], np.float32(np.inf))
    got = knn_numbers(ids, d, x, q, ids)
    assert got["bad_rows"] == 0 and got["rank_gap"] == 0.0
    assert got["dist_gap"] < 1e-6
    for cell in KNN_CELLS:
        assert judge(got, load_limits(BENCH.parent, cell))[0] is True


@pytest.mark.parametrize("cell", KNN_CELLS)
def test_ids_out_of_order_are_not_correct(cell):
    """Two neighbours swapped, each with its own float, as an order
    decided in a lower precision reports them: no float strays from its
    id's distance, and the cell's limit on ``rank_gap`` catches it."""
    x, q, ids, dists = _case()
    sw_i, sw_d = ids.copy(), dists.copy()
    sw_i[0, [3, 4]] = sw_i[0, [4, 3]]
    sw_d[0, [3, 4]] = sw_d[0, [4, 3]]
    got = knn_numbers(sw_i, sw_d, x, q, ids)
    assert got["bad_rows"] == 0 and got["recall_miss"] == 0.0
    assert got["dist_gap"] < 1e-6
    true = np.sort(dists[0, [3, 4]].astype(np.float64))
    assert got["rank_gap"] == pytest.approx((true[1] - true[0]) / true[1],
                                            rel=1e-5)
    correct, compared = judge(got, load_limits(BENCH.parent, cell))
    assert correct is False
    assert compared["rank_gap"]["value"] > compared["rank_gap"]["limit"]


@pytest.mark.parametrize("fault", ["out_of_range", "repeated",
                                   "not_finite", "missing"])
def test_contract_breaks_are_bad_rows(fault):
    x, q, ids, dists = _case()
    ids, dists = ids.copy(), dists.copy().astype(np.float64)
    if fault == "out_of_range":
        ids[1, 2] = len(x)
    elif fault == "repeated":
        ids[1, 2] = ids[1, 1]
    elif fault == "not_finite":
        dists[1, 9] = np.nan
    else:  # a request that never got its answer
        ids[1], dists[1] = -1, np.inf
    got = knn_numbers(ids, dists, x, q, ids)
    assert got["bad_rows"] == 1
    assert got["rank_gap"] == 0.0  # judged on the rows that keep the contract

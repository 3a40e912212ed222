"""On-device data generation: deterministic in the seed, held-out query
streams, and the configuration's (n, d) at full size (by shape only:
the full set is made on the chip, never here)."""
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import data  # noqa: E402

CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIGS)
def test_full_size_shape(name):
    c = cfg(name)
    out = jax.eval_shape(lambda: data.dataset(c, 7))
    assert out.shape == (c["n"], c["d"]) and out.dtype == np.float32


@pytest.mark.parametrize("name", CONFIGS)
def test_same_seed_same_points(name):
    c = dict(cfg(name), n=2048)
    big = 2**31 + 12345  # seeds past 32 signed bits
    a = np.asarray(data.dataset(c, big))
    assert np.array_equal(a, np.asarray(data.dataset(c, big)))
    # every seed serves a data set of its own
    assert not np.array_equal(a, np.asarray(data.dataset(c, big + 1)))
    assert not np.array_equal(
        a, np.asarray(data.dataset(c, big + (1 << 32))))  # high word counts
    assert np.isfinite(a).all()
    q = np.asarray(data.queries(c, big, 64))
    assert q.shape == (64, c["d"])
    assert not np.array_equal(q, np.asarray(data.queries(c, big, 64, 2)))
    assert np.array_equal(q, np.asarray(data.queries(c, big, 64)))


def test_twin_has_the_configured_local_dimension():
    c = dict(cfg("deep1m"), n=4096)
    x = np.asarray(data.dataset(c, 3), np.float64)
    twin = c["twin"]
    # the rows nearest one row spread over about active_dims directions
    # (plus the small full-rank noise)
    d2 = ((x - x[0]) ** 2).sum(1)
    near = x[np.argsort(d2)[:40]]
    s = np.linalg.svd(near - near.mean(0), compute_uv=False)
    energy = np.cumsum(s ** 2) / np.sum(s ** 2)
    assert np.searchsorted(energy, 0.95) + 1 <= twin["active_dims"] + 2


def test_negative_seed_and_data_stream_are_refused():
    c = dict(cfg("deep1m"), n=16)
    with pytest.raises(ValueError):
        data.root_key(-1)
    with pytest.raises(ValueError):
        data.queries(c, 1, 4, stream=0)


@pytest.mark.parametrize("count", [1, 64, 1000])
def test_queries_are_held_out_draws_of_the_mixture(count):
    """Queries lie among the data's clusters but are none of its rows,
    and a longer stream starts with the shorter one."""
    c = dict(cfg("deep1m"), n=2048)
    x = np.asarray(data.dataset(c, 5), np.float64)
    q = np.asarray(data.queries(c, 5, count), np.float64)
    assert q.shape == (count, c["d"])
    d2 = ((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    nearest = d2.min(1)
    assert (nearest > 0).all()
    # a cluster's points lie about noise·sqrt(2d) plus their spread
    # along its directions apart; centres lie center_scale·sqrt(2d) apart
    twin = c["twin"]
    assert np.median(np.sqrt(nearest)) < twin["center_scale"] * np.sqrt(
        2 * c["d"]) / 4

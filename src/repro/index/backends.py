"""Backend adapters: every probing mechanism behind one protocol.

Four first-party backends realize the PM-LSH contract:

  pmtree    — the paper-faithful host index (Algorithms 1-5, counted work)
  flat      — the device-native dense estimate→select→verify pipeline
  flat-pq   — the flat pipeline over PQ codes with an ADC rerank tier
  sharded   — the flat pipeline sharded over a mesh (tournament merge)

(the mutable ``streaming`` backend registers from ``repro.stream``)
and every competitor from the §7 study registers under the same
protocol through thin adapters, so sweeps are a registry iteration.
Host backends loop over the batch internally; device backends are
batched end-to-end under jit.

Closest-pair (§6) is served by every first-party backend: pmtree walks
the PM-tree radius filter on the host, sharded runs the distributed
ring join, and flat / flat-pq / streaming route through the
device-native ``cp_fused`` engine (Algorithm 4's radius filter as
pair-join tile masking, DESIGN.md §10) — flat-pq generating candidates
from code-estimated distances and exact-verifying the survivors.
"""
from __future__ import annotations

import inspect

import numpy as np

from repro.core.ann import PMLSH
from repro.core.baselines import (
    ACPP,
    LScan,
    LSBTree,
    MkCP,
    MultiProbe,
    NLJ,
    QALSH,
    RLSH,
    SRS,
)
from repro.core.cp import PMLSH_CP
from repro.core.estimator import solve_parameters
from repro.core.flat_index import (
    ann_query,
    answer_distances,
    build_flat_index,
    candidate_budget,
)
from repro.obs import trace as otrace

from .config import IndexConfig
from .registry import register_backend
from .types import CpSearchResult, SearchResult, WorkStats, pack_batch

__all__ = ["BaseIndex"]


def _ctor_kwargs(cls, config: IndexConfig, **common) -> dict:
    """config.options + common kwargs, filtered to what cls.__init__
    accepts (constructors with **kwargs take everything)."""
    kw = {**common, **config.options}
    params = inspect.signature(cls.__init__).parameters
    if any(p.kind == p.VAR_KEYWORD for p in params.values()):
        return kw
    return {k: v for k, v in kw.items() if k in params}


class BaseIndex:
    """Common construction / validation shared by all adapters."""

    backend_name = "base"
    capabilities: frozenset = frozenset()

    def __init__(self, data: np.ndarray, config: IndexConfig | None = None):
        self.config = config or IndexConfig()
        self.data = np.asarray(data, dtype=np.float32)
        if self.data.ndim != 2:
            raise ValueError(f"data must be (n, d), got {self.data.shape}")
        self.n, self.d = self.data.shape
        self._build()

    def _build(self) -> None:  # pragma: no cover - overridden
        raise NotImplementedError

    # -- ANN -------------------------------------------------------------

    def search(self, queries, k: int | None = None) -> SearchResult:
        if "ann" not in self.capabilities:
            raise NotImplementedError(
                f"backend {self.backend_name!r} does not support ANN search"
            )
        q = np.atleast_2d(np.asarray(queries, dtype=np.float32))
        if q.shape[-1] != self.d:
            raise ValueError(f"queries have d={q.shape[-1]}, index d={self.d}")
        k = int(k if k is not None else self.config.default_k)
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        # non-finite query rows would poison any distance pipeline
        # (NaN propagates through every comparison, returning arbitrary
        # neighbors with no signal): substitute a benign zero row for
        # the backend, then mask those rows to the sentinel answer
        # (-1 / +inf) and count them in WorkStats.queries_rejected
        bad_rows = ~np.isfinite(q).all(axis=1)
        n_bad = int(bad_rows.sum())
        if n_bad:
            q = np.where(bad_rows[:, None], np.float32(0.0), q)
        with otrace.span("index.search", backend=self.backend_name,
                         B=int(q.shape[0]), k=k) as sp:
            res = self._search(q, min(k, self.n))
            if n_bad:
                # np.where builds fresh arrays — backends may hand back
                # read-only views of device buffers
                res = SearchResult(
                    np.where(bad_rows[:, None], np.int32(-1), res.indices),
                    np.where(bad_rows[:, None], np.float32(np.inf),
                             res.distances),
                    stats=res.stats)
                res.stats.queries_rejected += n_bad
            if sp is not None:
                sp.attrs["work"] = res.stats.as_dict()
        if res.k < k:  # k > n: keep the (B, k) contract via padding
            pad_i = np.full((res.batch, k), -1, dtype=np.int32)
            pad_d = np.full((res.batch, k), np.inf, dtype=np.float32)
            pad_i[:, : res.k] = res.indices
            pad_d[:, : res.k] = res.distances
            res = SearchResult(pad_i, pad_d, stats=res.stats)
        return res

    def _search(self, q: np.ndarray, k: int) -> SearchResult:
        raise NotImplementedError

    # -- CP --------------------------------------------------------------

    def cp_search(self, k: int) -> CpSearchResult:
        if "cp" not in self.capabilities:
            raise NotImplementedError(
                f"backend {self.backend_name!r} does not support closest-pair"
            )
        with otrace.span("index.cp_search", backend=self.backend_name,
                         k=int(k)) as sp:
            res = self._cp_search(int(k))
            if sp is not None:
                sp.attrs["work"] = res.stats.as_dict()
        return res

    def _cp_search(self, k: int) -> CpSearchResult:
        raise NotImplementedError

    # -- storage accounting ----------------------------------------------

    def bytes_per_point(self) -> float:
        """Bytes/point of the index's DISTANCE storage — what the
        search tiers read to score a point (raw float32 here; codes +
        amortized codebooks for quantized backends).  The m-dim
        projection (4m bytes, identical across variants) and any
        retained raw rerank vectors are excluded — see
        ``raw_bytes_per_point``."""
        return 4.0 * self.d

    def raw_bytes_per_point(self) -> float:
        """Bytes/point of full-precision vectors kept for exact
        verification (0 when a quantized backend dropped them)."""
        return 4.0 * self.d

    def __repr__(self) -> str:
        return (f"{type(self).__name__}(backend={self.backend_name!r}, "
                f"n={self.n}, d={self.d})")


# ---------------------------------------------------------------------------
# first-party backends
# ---------------------------------------------------------------------------


@register_backend("pmtree", capabilities=("ann", "cp"))
class PMTreeBackend(BaseIndex):
    """Paper-faithful PM-tree index (host DFS, full work counters)."""

    def _build(self) -> None:
        # both trees are built on first use: CP-only callers never pay
        # for the ANN tree and vice versa
        self._ann_impl: PMLSH | None = None
        self._cp_impl: PMLSH_CP | None = None

    @property
    def impl(self) -> PMLSH:
        if self._ann_impl is None:
            cfg = self.config
            kw = _ctor_kwargs(PMLSH, cfg, m=cfg.m, c=cfg.c, seed=cfg.seed)
            self._ann_impl = PMLSH(self.data, **kw)
        return self._ann_impl

    def _search(self, q: np.ndarray, k: int) -> SearchResult:
        rows, stats = [], WorkStats()
        for qi in q:
            r = self.impl.ann_query(qi, k=k)
            rows.append((r.indices, r.distances))
            stats += WorkStats(
                rounds=r.rounds,
                candidates_verified=r.candidates_verified,
                node_distance_computations=r.stats.node_distance_computations,
                point_distance_computations=r.stats.point_distance_computations,
            )
        return SearchResult(*pack_batch(rows, k), stats=stats)

    def _cp_search(self, k: int) -> CpSearchResult:
        if self._cp_impl is None:
            cfg = self.config
            kw = _ctor_kwargs(PMLSH_CP, cfg, m=cfg.m, c=cfg.cp_c,
                              seed=cfg.seed)
            self._cp_impl = PMLSH_CP(self.data, **kw)
        r = self._cp_impl.cp_query(k=k, T=self.config.options.get("cp_T"))
        return CpSearchResult(
            r.pairs, r.distances,
            stats=WorkStats(rounds=r.nodes_examined,
                            candidates_verified=r.pairs_verified,
                            pairs_verified=r.pairs_verified),
        )


@register_backend("flat", capabilities=("ann", "cp"))
class FlatBackend(BaseIndex):
    """Device-native dense pipeline (DESIGN.md §3), jit'd and batched.

    Closest-pair queries (``cp_search``) run the device-native engine
    (DESIGN.md §10): the build-time projection's first coordinate sorts
    the points, and the pair-join kernel sweeps the (n, n) tile space
    with Algorithm 4's γ·t·ub radius filter as tile masking.  Quantized
    indexes generate candidate pairs from code-estimated distances and
    exact-verify the R best against the raw rows (codes-only indexes
    answer from the estimates).  ``options={"cp_gamma": γ}`` widens or
    tightens the filter; ``{"cp_rerank": R}`` sizes the quantized
    rerank tier.

    Queries run the fused estimate→select→verify pipeline (DESIGN.md
    §9: radius-threshold selection + gather-free verification) when the
    index is large enough for the threshold passes to beat the sort
    (default: n ≥ 8192, the measured CPU break-even);
    ``options={"fused": True/False}`` pins either pipeline (identical
    answers on ties-free data — the toggle is a perf knob, not a
    semantics knob).

    With ``options={"quant": "sq8"|"pq", ...}`` the verify tier goes
    through quantized storage (DESIGN.md §8): a codec is trained at
    build time, every point is encoded, and queries rerank the T
    LSH-selected candidates by asymmetric (ADC) distance on the codes
    before exact-verifying only the best ``rerank`` rows (default
    adaptive: max(4k, T/3), floor 64 — ADC ordering noise grows with
    the candidate pool, so a fixed budget starves recall at large n).
    Codec hyper-parameters nest under the codec's name, e.g.
    ``options={"quant": "pq", "pq": {"m_codebooks": 32}}``; with
    ``store_raw=False`` the raw float vectors are dropped entirely and
    answers come straight from ADC estimates.
    """

    def _build(self) -> None:
        import jax.numpy as jnp

        cfg = self.config
        self.impl = build_flat_index(self.data, m=cfg.m, seed=cfg.seed,
                                     c=cfg.c)
        self.use_kernels = bool(cfg.options.get("use_kernels", True))
        fused = cfg.options.get("fused")  # None → auto by index size
        self.fused = None if fused is None else bool(fused)
        # explicit kernel dispatch mode ("pallas"|"interpret"|"ref");
        # None derives it from use_kernels (tests force "interpret")
        self.force = cfg.options.get("force")
        self.codec = self.codes = None
        rerank = cfg.options.get("rerank")
        self.rerank = None if rerank is None else int(rerank)
        self.store_raw = bool(cfg.options.get("store_raw", True))
        qname = cfg.options.get("quant")
        if qname is None:
            return
        from repro.quant import train_codec

        copts = dict(cfg.options.get(qname) or {})
        seed = copts.pop("seed", cfg.seed)  # codec-level seed wins
        self.codec = train_codec(str(qname), self.data, seed=seed, **copts)
        self.codes = jnp.asarray(self.codec.encode(self.data))
        if not self.store_raw:
            # codes ARE the point storage now: drop both float copies
            import dataclasses as _dc

            self.impl = _dc.replace(
                self.impl, data=jnp.zeros((0, self.d), jnp.float32))
            self.data = np.empty((0, self.d), dtype=np.float32)

    def _record_select(self, counts, T: int) -> int:
        """Stash the last batch's per-query select survivor counts —
        the drift monitor (``obs.drift``) reads them off segment
        backends, and ROADMAP item 2's adaptive termination will.
        Returns the batch sum for ``WorkStats.candidates_selected``."""
        self.last_select_counts = np.asarray(counts, dtype=np.int64)
        self.last_select_budget = int(T)
        return int(self.last_select_counts.sum())

    def _search(self, q: np.ndarray, k: int) -> SearchResult:
        T = candidate_budget(self.impl.params, self.n, k)
        B = q.shape[0]
        # auto policy: the fused pipeline's O(n) threshold passes beat
        # the O(n·T) sort once n is past the fixed-cost break-even; the
        # fused verify kernel's answer network also caps k
        fused = (self.fused if self.fused is not None
                 else self.n >= 8192) and k <= 128
        force = (self.force if self.force is not None
                 else (None if self.use_kernels else "ref"))
        traced = otrace.enabled()
        if self.codec is None:
            if traced and fused:
                # stage-by-stage eager twin: same math, per-stage spans
                from repro.core.fused import fused_ann_query_traced

                ids, dd, cnt = fused_ann_query_traced(
                    self.impl, q, k=k, T=T, force=force, with_count=True)
            elif traced:
                # the unfused pipeline stays one jit call: a single
                # span bounds it, including host materialization
                with otrace.span("ann.query", B=B, n=self.n, k=k, T=T,
                                 fused=False):
                    ids, dd, cnt = otrace.block(ann_query(
                        self.impl, q, k=k, T=T,
                        use_kernels=self.use_kernels, fused=False,
                        force=force, with_count=True))
                    ids, dd = np.asarray(ids), np.asarray(dd)
            else:
                ids, dd, cnt = ann_query(self.impl, q, k=k, T=T,
                                         use_kernels=self.use_kernels,
                                         fused=fused, force=force,
                                         with_count=True)
            # canonical answer floats (shared with sharded-flat) — the
            # in-pipeline d² only ranked the candidates
            ids = np.asarray(ids)
            dd = np.asarray(answer_distances(self.impl.data, ids, q))
            return SearchResult(
                ids, dd,
                stats=WorkStats(rounds=B, candidates_verified=B * T,
                                candidates_selected=self._record_select(
                                    cnt, T)),
            )
        from repro.quant import quant_ann_query
        from repro.quant.search import quant_ann_query_traced

        rerank = (self.rerank if self.rerank is not None
                  else max(4 * k, T // 3, 64))
        R = min(max(rerank, k), T)
        query_fn = quant_ann_query_traced if traced else quant_ann_query
        ids, dd, cnt = query_fn(
            self.impl, self.codec, self.codes, q, k=k, T=T, R=R,
            store_raw=self.store_raw, force=force, fused=fused,
            with_count=True,
        )
        return SearchResult(
            np.asarray(ids), np.asarray(dd),
            stats=WorkStats(
                rounds=B,
                candidates_verified=B * R if self.store_raw else 0,
                candidates_selected=self._record_select(cnt, T),
                point_distance_computations=B * T,  # the ADC rerank tier
            ),
        )

    def _cp_search(self, k: int) -> CpSearchResult:
        from repro.core.cp_fused import cp_fused_search

        cfg = self.config
        gamma = float(cfg.options.get("cp_gamma", 1.0))
        force = (self.force if self.force is not None
                 else (None if self.use_kernels else "ref"))
        key = np.asarray(self.impl.projected)[:, 0]
        if self.codec is None:
            r = cp_fused_search(np.asarray(self.impl.data), k, m=cfg.m,
                                c=cfg.cp_c, gamma=gamma, force=force, key=key)
            return CpSearchResult(
                r.pairs, r.distances,
                stats=WorkStats(candidates_verified=r.pairs_verified,
                                pairs_verified=r.pairs_verified,
                                tiles_pruned=r.tiles_pruned),
            )
        from repro.quant import quant_cp_search

        if self.store_raw and getattr(self, "_cp_recon", None) is None:
            # codes are immutable: decode once and reuse across queries.
            # Codes-only indexes keep the per-call decode instead — they
            # chose the small-footprint regime, so the reconstruction
            # must stay transient.
            self._cp_recon = np.asarray(self.codec.decode(self.codes),
                                        dtype=np.float32)
        R = cfg.options.get("cp_rerank")
        pairs, dd, est, verified, pruned = quant_cp_search(
            self.codec, self.codes, key, k,
            raw=(self.data if self.store_raw else None),
            R=None if R is None else int(R),
            c=cfg.cp_c, m=cfg.m, gamma=gamma, force=force,
            recon=getattr(self, "_cp_recon", None))
        return CpSearchResult(
            pairs, dd,
            stats=WorkStats(candidates_verified=verified,
                            point_distance_computations=est,
                            pairs_verified=verified if self.store_raw else est,
                            tiles_pruned=pruned),
        )

    def bytes_per_point(self) -> float:
        if self.codec is None:
            return 4.0 * self.d
        per_point = self.codec.bytes_per_point
        codebook = getattr(self.codec, "codebook_bytes", 0)
        return per_point + codebook / max(self.n, 1)

    def raw_bytes_per_point(self) -> float:
        if self.codec is not None and not self.store_raw:
            return 0.0
        return 4.0 * self.d


@register_backend("flat-pq", capabilities=("ann", "quant", "cp"))
class FlatPQBackend(FlatBackend):
    """The flat pipeline with PQ codes + ADC rerank pre-wired: PQ is
    trained at build time unless the config already names a codec, so
    ``build_index(data, backend="flat-pq")`` is the one-liner for the
    quantized tier (≈16× smaller point storage at default settings)."""

    def _build(self) -> None:
        if "quant" not in self.config.options:
            self.config = self.config.with_options(quant="pq")
        super()._build()


@register_backend("sharded", capabilities=("ann", "cp"))
class ShardedBackend(BaseIndex):
    """The flat pipeline sharded over a device mesh ('data' axis):
    per-shard estimate→select→verify, one all-gather tournament merge.

    options: devices (mesh width, default all local devices), and the
    usual flat/CP knobs.  The candidate budget is the same T = βn + k
    as every other PM-LSH backend, split T/P per shard.
    """

    def _build(self) -> None:
        import jax

        from repro.compat import make_mesh
        from repro.core.distributed import DistributedFlatIndex

        cfg = self.config
        devices = int(cfg.options.get("devices", len(jax.devices())))
        self.mesh = cfg.options.get("mesh") or make_mesh((devices,), ("data",))
        self.params = solve_parameters(cfg.c, m=cfg.m)
        self.impl = DistributedFlatIndex(self.data, self.mesh, m=cfg.m,
                                         seed=cfg.seed)
        self._cp_impl = None

    def _search(self, q: np.ndarray, k: int) -> SearchResult:
        T = candidate_budget(self.params, self.n, k)
        ids, dd = self.impl.query(q, k=k, T=T)
        P = self.mesh.shape["data"]
        local_T = self.impl.local_budget(T, k)
        return SearchResult(
            ids, dd,
            stats=WorkStats(rounds=q.shape[0],
                            candidates_verified=q.shape[0] * P * local_T),
        )

    def _cp_search(self, k: int) -> CpSearchResult:
        if self._cp_impl is None:
            from repro.core.distributed import DistributedCP

            cfg = self.config
            self._cp_impl = DistributedCP(self.data, self.mesh, m=cfg.m,
                                          c=cfg.cp_c, seed=cfg.seed)
        pairs, dd, verified = self._cp_impl.cp_query(k=k, with_stats=True)
        return CpSearchResult(
            pairs, dd, stats=WorkStats(candidates_verified=verified,
                                       pairs_verified=verified))


@register_backend("sharded-flat", capabilities=("ann", "cp"))
class ShardedFlatBackend(BaseIndex):
    """The FUSED pipeline sharded over a device mesh with an exact
    global candidate set (DESIGN.md §15, ``core/sharded.py``).

    Unlike the legacy ``sharded`` backend (pre-fused local top-T'
    heuristic), answers are bit-identical to ``flat`` on ties-free
    data: shards exchange only per-shard survivor counts to calibrate
    one global select threshold, verify locally, and merge one
    all-gather of k.  CP runs the ring pair-join under a global ub
    register with tile-level radius pruning on cross-shard tiles.

    options: ``shards`` (logical shard count; defaults to the visible
    device count, and more than that is an error), ``emulate`` (the
    host-emulated multi-shard path — parity tests on one device),
    ``cp_gamma`` / ``rerank`` / ``force`` as on ``flat``.

    WorkStats: summed counters match the single-device run
    (candidates_selected sums shard survivor counts = realized T·B;
    pairs_verified counts each pair on exactly one shard) and the
    sharded fields report mesh width + max-shard skew.
    """

    quant: str | None = None

    def _build(self) -> None:
        from repro.core.sharded import ShardedFlatIndex

        cfg = self.config
        self.force = cfg.options.get("force")
        copts = dict(cfg.options.get("pq") or {}) if self.quant else None
        self.impl = ShardedFlatIndex(
            self.data,
            shards=cfg.options.get("shards"),
            mesh=cfg.options.get("mesh"),
            m=cfg.m, seed=cfg.seed, c=cfg.c,
            emulate=bool(cfg.options.get("emulate", False)),
            quant=self.quant, quant_opts=copts,
            rerank=cfg.options.get("rerank"),
            force=self.force,
            cp_tile=int(cfg.options.get("cp_tile", 128)),
        )
        self.params = self.impl.params
        import jax.numpy as jnp

        self._data_jnp = jnp.asarray(self.data)

    def _search(self, q: np.ndarray, k: int) -> SearchResult:
        T = candidate_budget(self.params, self.n, k)
        B = q.shape[0]
        if otrace.enabled():
            ids, dd, counts = self.impl.query_traced(q, k, T)
        else:
            ids, dd, counts = self.impl.query(q, k, T)
        # canonical answer floats — same shared program as ``flat``, so
        # id-parity implies bit-identical distances (DESIGN.md §15)
        dd = np.asarray(answer_distances(self._data_jnp, ids, q))
        per_shard = counts.sum(axis=1)  # (P,) survivor totals
        selected = int(per_shard.sum())
        stats = WorkStats(
            rounds=B,
            candidates_verified=selected,
            candidates_selected=selected,
            shards=self.impl.P,
            max_shard_candidates=int(per_shard.max()),
        )
        if self.quant:
            # the ADC tier scored every survivor; exact verification
            # touched only the reranked survivors per shard
            cap = min(self.impl.nl, T)
            R_l = min(self.impl._rerank_budget(k, T), cap)
            stats.point_distance_computations = selected
            stats.candidates_verified = int(
                np.minimum(counts, R_l).sum())
        return SearchResult(np.asarray(ids), np.asarray(dd), stats=stats)

    def _cp_search(self, k: int) -> CpSearchResult:
        from repro.core.cp_fused import cp_threshold2

        cfg = self.config
        gamma = float(cfg.options.get("cp_gamma", 1.0))
        thresh2 = (np.inf if not np.isfinite(gamma)
                   else cp_threshold2(cfg.cp_c, cfg.m, gamma))
        pairs, dd, pair_counts, pruned = self.impl.cp_query(
            k, thresh2=float(thresh2), traced=otrace.enabled())
        verified = int(pair_counts.sum())
        return CpSearchResult(
            pairs, dd,
            stats=WorkStats(candidates_verified=verified,
                            pairs_verified=verified,
                            tiles_pruned=pruned,
                            shards=self.impl.P,
                            max_shard_pairs=int(pair_counts.max())))


@register_backend("sharded-flat-pq", capabilities=("ann", "cp", "quant"))
class ShardedFlatPQBackend(ShardedFlatBackend):
    """``sharded-flat`` with per-shard PQ codebooks: each shard trains
    its own codec on the rows it stores, survivors are ADC-reranked
    shard-locally, and only the best R rows per shard pay an exact
    verification (raw rows are retained — the quantized tier is a
    bandwidth lever here, not a storage-drop lever, so ``cp_search``
    and the recall floor stay exact-verified; codebook options nest
    under ``options={"pq": {...}}`` as on ``flat``)."""

    quant = "pq"

    def bytes_per_point(self) -> float:
        per_point = self.impl.codecs[0].bytes_per_point
        codebook = sum(getattr(c, "codebook_bytes", 0)
                       for c in self.impl.codecs)
        return per_point + codebook / max(self.n, 1)


# ---------------------------------------------------------------------------
# §7 competitor baselines — generic host adapters
# ---------------------------------------------------------------------------


class _HostBaseline(BaseIndex):
    """Adapter over the baseline contract:
    query(q, k) -> (ids, dist, work) / cp_query(k) -> (pairs, dist, work).
    """

    impl_cls: type = None  # set per registered subclass

    def _build(self) -> None:
        cfg = self.config
        kw = _ctor_kwargs(self.impl_cls, cfg, c=cfg.c, seed=cfg.seed)
        self.impl = self.impl_cls(self.data, **kw)

    def _search(self, q: np.ndarray, k: int) -> SearchResult:
        rows, work = [], 0
        for qi in q:
            ids, dd, w = self.impl.query(qi, k)
            rows.append((ids, dd))
            work += int(w)
        return SearchResult(
            *pack_batch(rows, k),
            stats=WorkStats(rounds=q.shape[0], candidates_verified=work),
        )

    def _cp_search(self, k: int) -> CpSearchResult:
        pairs, dd, work = self.impl.cp_query(k)
        return CpSearchResult(
            pairs, dd, stats=WorkStats(candidates_verified=int(work),
                                       pairs_verified=int(work)))


_BASELINES = [
    # (registry name, implementation, capabilities)
    ("multiprobe", MultiProbe, ("ann",)),
    ("qalsh", QALSH, ("ann",)),
    ("srs", SRS, ("ann",)),
    ("rlsh", RLSH, ("ann",)),
    ("lscan", LScan, ("ann",)),
    ("lsb_tree", LSBTree, ("ann", "cp")),
    ("acp_p", ACPP, ("cp",)),
    ("mkcp", MkCP, ("cp",)),
    ("nlj", NLJ, ("cp",)),
]

for _name, _impl, _caps in _BASELINES:
    register_backend(_name, capabilities=_caps)(
        type(
            f"{_impl.__name__}Backend",
            (_HostBaseline,),
            {"impl_cls": _impl,
             "__doc__": f"Registry adapter over baselines.{_impl.__name__}."},
        )
    )

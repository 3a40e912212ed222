"""StreamingIndex — the mutable LSM-style index behind the facade.

Layout (DESIGN.md §7):

    inserts → delta buffer ──flush──▶ sealed segment (static backend)
    deletes → dropped from delta, or tombstoned against a segment
    search  → fan-out over segments + delta, one top-k merge
              (repro.kernels topk), tombstones filtered at merge time
    compaction → when segments pile up or rot, the smallest are
              rebuilt — live rows only — into one larger segment

Id discipline: every inserted row gets a monotonically increasing
GLOBAL id (its row in the append-only vector store).  Ids are never
recycled, so payload stores indexed by id (kNN-LM values) stay valid
across flushes and compactions.  Exactly one source — the delta or one
segment — owns a live id at any time, so the merge never sees
duplicates.

Registered as backend ``"streaming"`` with capabilities
``("ann", "stream", "cp")``; build it over (possibly empty) seed data
via the ordinary facade call and mutate from there (``cp_search`` joins
the live rows — all segments plus the delta — through the fused CP
engine, DESIGN.md §10):

    index = build_index(data, IndexConfig(backend="streaming"))
    ids = index.insert(new_rows)        # visible to search immediately
    index.delete(ids[:2])               # never returned again
    index.flush()                       # seal the delta eagerly

options: ``delta_threshold`` (flush trigger, default 512),
``segment_backend`` (default "pmtree"; "flat" when ``quant`` is set),
``max_segments`` (compaction trigger, default 4), ``max_dead_fraction``
(segment rot trigger, default 0.5), ``use_kernels`` (delta-scan
dispatch, default True), ``durability`` (crash consistency, DESIGN.md
§14: ``{"dir": path, "sync": True, "snapshot_every": 0}`` attaches a
``repro.resilience`` WAL — every mutation is logged before memory
changes — plus atomic snapshots every N records; rebuild after a crash
with ``repro.resilience.recover(dir)``).  Unrecognized options (e.g. ``fused``,
``quant``, ``rerank``) pass through to the segment backend, so the
per-segment fan-out of a ``"flat"``/``"flat-pq"``-segmented index runs
the fused estimate→select→verify pipeline (DESIGN.md §9) — by size
auto-policy on big compacted segments, or pinned via
``options={"fused": True}``.

Quantized segments: with ``options={"quant": "sq8"|"pq", ...}`` sealed
segments are served by the quantized flat backend (DESIGN.md §8) —
each seal trains a codec on exactly the rows it freezes, and
compaction re-trains codebooks over the merged live rows, so codebook
drift is bounded by segment lifetime.  The delta buffer always stays
float32 (exact scan): quantization is a property of SEALED data only.
"""
from __future__ import annotations

import numpy as np

from repro.obs import trace as otrace
from repro.resilience import chaos

from repro.index.backends import BaseIndex
from repro.index.registry import register_backend
from repro.index.types import CpSearchResult, SearchResult, WorkStats

from .delta import DeltaBuffer
from .segment import Segment

__all__ = ["StreamingIndex"]


@register_backend("streaming", capabilities=("ann", "stream", "cp"))
class StreamingIndex(BaseIndex):
    """Mutable Index: static-backend segments + delta + tombstones."""

    # -- construction ----------------------------------------------------

    def _build(self) -> None:
        opts = self.config.options
        self.delta_threshold = int(opts.get("delta_threshold", 512))
        # quantization lives in the flat backend's verify tier, so a
        # quant request flips the default segment backend to "flat" —
        # and an explicit backend that would silently ignore the quant
        # options is rejected rather than served as float32
        default_segment = "flat" if opts.get("quant") else "pmtree"
        self.segment_backend = str(opts.get("segment_backend",
                                            default_segment))
        if opts.get("quant") and self.segment_backend not in ("flat",
                                                              "flat-pq"):
            raise ValueError(
                f"segment_backend {self.segment_backend!r} cannot honor "
                "quantized segments; use 'flat' or 'flat-pq'")
        self.max_segments = int(opts.get("max_segments", 4))
        self.max_dead_fraction = float(opts.get("max_dead_fraction", 0.5))
        self._force = None if opts.get("use_kernels", True) else "ref"
        if self.delta_threshold < 1:
            raise ValueError("delta_threshold must be >= 1")
        if self.max_segments < 2:
            raise ValueError("max_segments must be >= 2")

        self._store = np.empty((0, self.d), dtype=np.float32)
        self._alive = np.empty((0,), dtype=bool)
        self._owner = np.empty((0,), dtype=np.int64)  # -1 delta, else serial
        self._total = 0  # ids ever assigned == rows used in the store
        self._n_live = 0
        self.delta = DeltaBuffer(self.d)
        self.segments: list[Segment] = []
        self._by_serial: dict[int, Segment] = {}
        self.n_flushes = 0
        self.n_compactions = 0
        # projection-drift monitor (obs.drift, DESIGN.md §13): inserted
        # rows feed the projected-coordinate moments (host-side matmul
        # against the build-time family — no jax dispatch per insert),
        # and the per-segment fan-out feeds the select kernel's
        # survivor counts into the occupancy histogram.  The first
        # baseline rows (seed data + earliest inserts) freeze the
        # build-time reference the live EWMA is compared against.
        self.drift = None
        self._drift_proj = None
        if bool(opts.get("drift", True)):
            from repro.core.hashing import ProjectionFamily
            from repro.obs.drift import DriftMonitor

            fam = ProjectionFamily.create(self.d, self.config.m,
                                          seed=self.config.seed)
            self._drift_proj = np.asarray(fam.a, dtype=np.float32)
            self.drift = DriftMonitor(
                baseline_rows=int(opts.get("drift_baseline", 256)))
        # durability (DESIGN.md §14): WAL-before-memory logging + atomic
        # snapshots, attached BEFORE the seed insert so seed rows are
        # logged too.  A dir that already holds a durable index must go
        # through resilience.recover(), not a fresh build.
        self.durability = None
        dur = opts.get("durability")
        if dur:
            from repro.resilience.recovery import DurabilityManager

            self.durability = DurabilityManager(
                dur["dir"], d=self.d, config=self.config, fresh=True,
                sync=bool(dur.get("sync", True)),
                snapshot_every=int(dur.get("snapshot_every", 0)),
                snapshot_keep=int(dur.get("snapshot_keep", 2)))
        if self.data.shape[0]:
            self.insert(self.data)
        # the append-only store owns the rows now; keeping BaseIndex's
        # seed array would double memory and expose a stale snapshot
        self.data = self._store[:0]

    # BaseIndex assigns ``self.n = data.shape[0]`` at build; for a
    # mutable index n is the LIVE count, so shadow it with a property.
    @property
    def n(self) -> int:  # type: ignore[override]
        return self._n_live

    @n.setter
    def n(self, _value) -> None:
        pass

    # -- mutation --------------------------------------------------------

    def insert(self, points) -> np.ndarray:
        """Append rows; returns their new global ids (int64, (n,)).
        Inserted points are visible to ``search`` immediately (delta
        scan); the delta is flushed once it reaches ``delta_threshold``.
        """
        x = np.atleast_2d(np.asarray(points, dtype=np.float32))
        if x.shape[-1] != self.d:
            raise ValueError(f"points have d={x.shape[-1]}, index d={self.d}")
        cnt = x.shape[0]
        if cnt == 0:
            return np.empty((0,), dtype=np.int64)
        ids = np.arange(self._total, self._total + cnt, dtype=np.int64)
        # WAL-before-memory: the record is durable before any state
        # changes, so a crash here loses nothing already acknowledged
        if self.durability is not None:
            self.durability.log_insert(self._total, x)
        chaos.hit("stream.apply")
        self._grow_to(self._total + cnt)
        self._store[ids] = x
        self._alive[ids] = True
        self._owner[ids] = -1
        self._total += cnt
        self._n_live += cnt
        self.delta.insert(ids, x)
        if self.drift is not None:
            self.drift.observe_rows(x @ self._drift_proj)
        if len(self.delta) >= self.delta_threshold:
            self.flush()
        return ids

    def delete(self, ids) -> int:
        """Tombstone ids; returns how many were live.  Ids still in the
        delta are dropped physically; sealed ids are filtered at merge
        time until compaction rebuilds their segment.  Unknown (never
        assigned) ids raise KeyError; re-deleting is a no-op.
        """
        ids = np.unique(np.asarray(ids, dtype=np.int64).reshape(-1))
        if ids.size and (ids[0] < 0 or ids[-1] >= self._total):
            bad = ids[(ids < 0) | (ids >= self._total)]
            raise KeyError(f"unknown ids {bad.tolist()} "
                           f"(assigned range is [0, {self._total}))")
        targets = ids[self._alive[ids]]
        if targets.size == 0:
            return 0
        if self.durability is not None:
            self.durability.log_delete(targets)
        chaos.hit("stream.apply")
        self._alive[targets] = False
        self._n_live -= int(targets.size)
        in_delta = self.delta.delete(targets)
        sealed = np.setdiff1d(targets, in_delta, assume_unique=True)
        for serial in self._owner[sealed]:
            self._by_serial[int(serial)].dead += 1
        self._maybe_compact()
        return int(targets.size)

    def flush(self) -> None:
        """Seal the delta into an immutable segment (no-op when empty)."""
        if len(self.delta) == 0:
            return
        if chaos.dropped("stream.flush"):
            return  # injected lost flush: rows stay served from delta
        # build the segment BEFORE draining so a failed build (bad
        # segment_backend, ...) leaves every live row still served —
        # and is never WAL'd, so replay cannot re-raise it
        seg = Segment(self.delta.ids, self.delta.vectors, self.config,
                      self.segment_backend)
        if self.durability is not None:
            self.durability.log_flush()
        chaos.hit("stream.apply")
        ids, _ = self.delta.take()
        self._owner[ids] = seg.serial
        self._by_serial[seg.serial] = seg
        self.segments.append(seg)
        self.n_flushes += 1
        self._maybe_compact()
        if self.durability is not None:
            self.durability.maybe_snapshot(self)

    # -- compaction ------------------------------------------------------

    def _maybe_compact(self) -> None:
        victims = {s.serial: s for s in self.segments
                   if s.dead_fraction > self.max_dead_fraction}
        if len(self.segments) >= self.max_segments:
            # fold the smallest runs into one, leaving the big ones be:
            # post-compaction count settles at max_segments - 1
            by_live = sorted(self.segments, key=lambda s: (s.live, s.serial))
            n_merge = len(self.segments) - self.max_segments + 2
            for s in by_live[:n_merge]:
                victims[s.serial] = s
        if victims:
            self._compact(list(victims.values()))

    def _compact(self, victims: list[Segment]) -> None:
        """Rebuild ``victims`` into one segment holding only live rows."""
        live = np.concatenate([s.ids[self._alive[s.ids]] for s in victims])
        live.sort()
        # build the replacement BEFORE dropping the victims: a failed
        # build must leave every live row still owned by a source
        seg = (Segment(live, self._store[live], self.config,
                       self.segment_backend) if live.size else None)
        # compaction is a deterministic consequence of the op sequence;
        # its WAL record is an audit marker and replays as a no-op
        if self.durability is not None:
            self.durability.log_compact()
        gone = {s.serial for s in victims}
        self.segments = [s for s in self.segments if s.serial not in gone]
        for serial in gone:
            del self._by_serial[serial]
        if seg is not None:
            self._owner[live] = seg.serial
            self._by_serial[seg.serial] = seg
            self.segments.append(seg)
        self.n_compactions += 1

    # -- search ----------------------------------------------------------

    def _search(self, q: np.ndarray, k: int) -> SearchResult:
        B = q.shape[0]
        stats = WorkStats()
        id_blocks, dist_blocks = [], []
        with otrace.span("stream.search") as sp:
            if sp is not None:
                sp.attrs.update(B=B, k=k, segments=len(self.segments),
                                delta=len(self.delta))
            for si, seg in enumerate(self.segments):
                # widen by the segment's tombstone count so filtering
                # dead rows at merge time cannot starve the per-segment
                # top-k
                with otrace.span("stream.segment") as ssp:
                    if ssp is not None:
                        ssp.attrs.update(serial=seg.serial, size=seg.size,
                                         dead=seg.dead,
                                         backend=self.segment_backend)
                    gids, dd, st = seg.search(q, k + seg.dead)
                id_blocks.append(gids)
                dist_blocks.append(dd)
                stats += st
                # flat segments stash their last select survivor counts
                # (realized T) — the drift monitor's occupancy signal
                counts = getattr(seg.index, "last_select_counts", None)
                if self.drift is not None and counts is not None:
                    self.drift.observe_survivors(
                        counts, getattr(seg.index, "last_select_budget", 0))
            with otrace.span("stream.delta") as dsp:
                if dsp is not None:
                    dsp.attrs["size"] = len(self.delta)
                gids, dd, st = self.delta.search(q, k, force=self._force)
            id_blocks.append(gids)
            dist_blocks.append(dd)
            stats += st

            with otrace.span("stream.merge"):
                gids = np.concatenate(id_blocks, axis=1)  # (B, S) int64
                dd = np.concatenate(dist_blocks, axis=1).astype(np.float32)
                if k == 0 or gids.shape[1] == 0:
                    return SearchResult(np.empty((B, 0), np.int32),
                                        np.empty((B, 0), np.float32),
                                        stats=stats)

                # tombstones (and per-source -1 padding) applied at
                # merge time
                invalid = (gids < 0) | ~self._alive[np.maximum(gids, 0)]
                dd = np.where(invalid, np.inf, dd)

                from repro.kernels import ops

                kk = min(k, gids.shape[1])
                vals, cols = ops.topk_smallest(dd, kk, force=self._force)
                vals = np.asarray(vals, np.float32)
                cols = np.asarray(cols, np.int64)
                merged = np.take_along_axis(gids, cols, axis=1)
                merged = np.where(np.isinf(vals), -1, merged)
        return SearchResult(merged.astype(np.int32), vals, stats=stats)

    # -- closest pair ----------------------------------------------------

    def _cp_search(self, k: int) -> CpSearchResult:
        """(c,k)-ACP over the LIVE rows (DESIGN.md §10).

        Sources are gathered segment-by-segment, delta last, and the
        concatenation feeds ONE fused pair join — the engine's
        band-major tile sweep then covers every cross-source block
        (segment×segment, delta×segment, delta×delta) under a single
        γ·t·ub radius filter and one global ub register, instead of a
        per-source-pair fan-out that would re-seed ub from scratch.
        Tombstones are masked at gather time: dead rows never enter the
        join, so no post-filter widening is needed.
        """
        from repro.core.cp_fused import cp_fused_search

        with otrace.span("stream.cp_gather") as sp:
            if sp is not None:
                sp.attrs.update(segments=len(self.segments),
                                delta=len(self.delta))
            blocks, gids = [], []
            for seg in self.segments:  # sealed runs first, delta last
                live = seg.ids[self._alive[seg.ids]]
                if live.size:
                    blocks.append(self._store[live])
                    gids.append(live)
            if len(self.delta):
                blocks.append(self.delta.vectors)
                gids.append(self.delta.ids)
            if not blocks or sum(b.shape[0] for b in blocks) < 2:
                return CpSearchResult(np.empty((0, 2), np.int32),
                                      np.empty((0,), np.float32))
            x = np.concatenate(blocks, axis=0)
            gid = np.concatenate(gids)
        cfg = self.config
        r = cp_fused_search(
            x, k, m=cfg.m, c=cfg.cp_c,
            gamma=float(cfg.options.get("cp_gamma", 1.0)),
            seed=cfg.seed, force=self._force)
        pairs = gid[r.pairs.astype(np.int64)]
        pairs = np.stack([pairs.min(axis=1), pairs.max(axis=1)],
                         axis=1).astype(np.int32)
        return CpSearchResult(
            pairs, r.distances,
            stats=WorkStats(candidates_verified=r.pairs_verified,
                            pairs_verified=r.pairs_verified,
                            tiles_pruned=r.tiles_pruned))

    # -- durability ------------------------------------------------------

    def snapshot(self):
        """Write an atomic on-disk snapshot now and rotate the WAL
        (requires ``options={"durability": {...}}``).  Returns the
        committed snapshot directory."""
        if self.durability is None:
            raise RuntimeError(
                "snapshot() requires options={'durability': {'dir': ...}}")
        return self.durability.snapshot(self)

    def close(self) -> None:
        """Flush and close the WAL handle (no-op without durability)."""
        if self.durability is not None:
            self.durability.close()

    # -- introspection ---------------------------------------------------

    @property
    def segment_count(self) -> int:
        return len(self.segments)

    @property
    def delta_size(self) -> int:
        return len(self.delta)

    @property
    def total_assigned(self) -> int:
        """Ids ever assigned (monotone; tombstones included)."""
        return self._total

    def drift_report(self):
        """Current :class:`repro.obs.drift.DriftReport` (None when the
        monitor is disabled via ``options={"drift": False}``)."""
        return None if self.drift is None else self.drift.report()

    def bytes_per_point(self) -> float:
        """Resident distance-storage bytes per LIVE point: sealed
        segments (possibly quantized) charge every stored row —
        tombstoned-but-uncompacted rows still occupy storage — plus the
        float32 delta, divided by the live count."""
        if self.n == 0:
            return 0.0
        seg_bytes = sum(s.bytes_per_point() * s.size for s in self.segments)
        return (seg_bytes + 4.0 * self.d * len(self.delta)) / self.n

    def raw_bytes_per_point(self) -> float:
        """Float32 bytes per live point resident in the append-only
        store.  The streaming index ALWAYS retains raw rows (compaction
        rebuilds — and codebook re-training — need them), so quantized
        segments shrink the verify-tier reads, not total residency;
        codes-only capacity wins need a static index with
        ``store_raw=False``."""
        if self.n == 0:
            return 0.0
        return 4.0 * self.d * self._total / self.n

    def live_ids(self) -> np.ndarray:
        """Global ids currently alive (ascending, int64)."""
        return np.flatnonzero(self._alive[: self._total]).astype(np.int64)

    def get_vectors(self, ids) -> np.ndarray:
        """Rows of the append-only store for ``ids`` (alive or not)."""
        return self._store[np.asarray(ids, dtype=np.int64)].copy()

    def _grow_to(self, need: int) -> None:
        cap = self._store.shape[0]
        if need <= cap:
            return
        new = max(need, cap * 2, 1024)
        store = np.empty((new, self.d), dtype=np.float32)
        store[:cap] = self._store[:cap]
        alive = np.zeros((new,), dtype=bool)
        alive[:cap] = self._alive
        owner = np.full((new,), -1, dtype=np.int64)
        owner[:cap] = self._owner
        self._store, self._alive, self._owner = store, alive, owner

    def __repr__(self) -> str:
        return (f"StreamingIndex(n={self.n}, d={self.d}, "
                f"segments={self.segment_count}, delta={self.delta_size}, "
                f"flushes={self.n_flushes}, "
                f"compactions={self.n_compactions})")

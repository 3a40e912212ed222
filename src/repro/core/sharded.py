"""Sharded fused ANN/CP engine over a device mesh (DESIGN.md §15).

``core/distributed.py`` shards the PRE-fused pipeline: every shard runs
a local rank-T' top-k and the merge exchanges (P × T') full candidate
payloads.  That wastes wire (candidates, not counts) and — worse — its
local rank cut is only a heuristic split of the global budget, so its
answers are not bit-identical to the single-device index.

This module shards the FUSED pipeline (DESIGN.md §9) with an exact
global candidate set:

  ANN   Points are row-sharded.  Each shard computes its slice of the
        projected distances (ESTIMATE), then all shards cooperatively
        calibrate ONE global radius threshold τ: a bisection on the
        float32 bit-ordering of the projected distances where each rung
        exchanges only per-shard survivor COUNTS (a psum of (B,) int32
        per rung — 32 rungs pin τ to the exact T-th smallest projected
        distance, because nonnegative float32 values order like their
        int32 bit patterns).  Survivors under τ are exactly the global
        top-T, so each shard compacts its survivors locally
        (cumsum+searchsorted, the radius-select idiom), verifies them
        with the gather-free kernel into a device-local top-k, and one
        all-gather-of-k merge finishes.  On ties-free data the answer
        is bit-identical to the flat backend: the candidate set is the
        same set, the verify math is the same elementwise direct
        difference, and the final top-k compares the same floats.

  CP    Points are sharded in globally key-sorted order (contiguous
        chunks of the 1-D projection key).  Round 0 is the intra-shard
        self-join; rounds 1..P-1 ring-rotate (ppermute) the blocks and
        join own×received under tile-level radius pruning
        (gap² > (γt)²·ub²) against ONE global ub register, re-exchanged
        (all-gather of each shard's running top-k) between rounds —
        Algorithm 4's filter expressed as a collective schedule, at
        tile granularity like the single-device pair join.  The final
        winners are re-verified on the host in the subtract-then-norm
        form and stably re-sorted, exactly like ``cp_fused_search``.

Both programs exist twice with identical math:

  * a ``shard_map`` program over a real device mesh (via
    ``repro.compat``), jit-compiled end to end;
  * an EMULATED path — a host loop over logical shard blocks running
    the same per-shard jnp stage functions, with psum/pmax/all-gather
    replaced by exact host reductions.  It serves single-device runs
    at any logical shard count.

Exactness of the threshold exchange: int sums (psum of counts) and
float max (pmax) are associative bit-exactly, and the bisection state
is integer, so the mesh and emulated paths agree bit-for-bit; both
reproduce the flat backend's top-T candidate set whenever the T-th and
(T+1)-th smallest projected distances differ (the ties-free contract
every select path in this repo already carries).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat

from .cp_fused import cp_join_budget
from .estimator import solve_parameters
from .hashing import ProjectionFamily

__all__ = ["ShardedFlatIndex", "BISECT_ROUNDS"]

#: bisection rungs on the int32 bit-ordering of nonneg float32 values —
#: 32 covers the full pattern range, pinning τ to an exact ulp
BISECT_ROUNDS = 32


# ---------------------------------------------------------------------------
# layout
# ---------------------------------------------------------------------------


def pad_rows(arr: np.ndarray, shards: int, fill: float = 0.0,
             multiple: int = 1) -> np.ndarray:
    """Pad (n, ...) up so every shard gets the same whole row count
    (optionally a multiple of the CP tile).  Padding rows are benign
    fill — every consumer masks by global id < n."""
    n = arr.shape[0]
    nl = -(-max(n, 1) // shards)
    nl = -(-nl // multiple) * multiple
    pad = nl * shards - n
    if pad == 0:
        return np.asarray(arr)
    filler = np.full((pad,) + arr.shape[1:], fill, arr.dtype)
    return np.concatenate([np.asarray(arr), filler])


def _device_put_sharded(arr: np.ndarray, mesh: Mesh, axis: str):
    from repro.launch.sharding import index_row_pspec

    return jax.device_put(jnp.asarray(arr),
                          NamedSharding(mesh, index_row_pspec(arr.ndim, axis)))


# ---------------------------------------------------------------------------
# per-shard ANN stage math (shared verbatim by the mesh program and the
# emulated path — parity between the two is parity of these)
# ---------------------------------------------------------------------------


def _estimate_block(proj_blk, qp, gid0: int, n_valid: int):
    """Local slice of the projected squared distances, padding rows
    masked to +inf.  Same norm-trick + clamp as the ref estimate."""
    qn = jnp.sum(qp * qp, axis=-1, keepdims=True)  # (B, 1)
    xn = jnp.sum(proj_blk * proj_blk, axis=-1)  # (nl,)
    d2p = jnp.maximum(qn + xn[None, :] - 2.0 * jnp.dot(
        qp, proj_blk.T, precision=jax.lax.Precision.HIGHEST), 0.0)
    nl = proj_blk.shape[0]
    valid = (gid0 + jnp.arange(nl)) < n_valid
    return jnp.where(valid[None, :], d2p, jnp.inf)


def _count_le_bits(d2p, tau_bits):
    """Per-row survivor count under the float32 whose bits are
    ``tau_bits`` — the quantity each bisection rung exchanges."""
    tau = jax.lax.bitcast_convert_type(tau_bits, jnp.float32)
    return jnp.sum((d2p <= tau[:, None]).astype(jnp.int32), axis=1)


def _bisect_step(lo, hi, global_count, T: int):
    """One rung: shrink the integer bracket toward the minimal bits
    whose global survivor count reaches T."""
    mid = lo + (hi - lo) // 2
    ge = global_count >= T
    return jnp.where(ge, lo, mid), jnp.where(ge, mid, hi)


def _bisect_mid(lo, hi):
    return lo + (hi - lo) // 2


def _compact_block(d2p, tau, cap: int):
    """Compact local survivors (d2p ≤ τ) into ``cap`` slots of local
    positions (-1 padding), preserving row order — the radius-select
    compaction idiom.  Also returns the per-row survivor count."""
    nl = d2p.shape[1]
    mask = d2p <= tau[:, None]
    cnt = jnp.sum(mask.astype(jnp.int32), axis=1)
    cs = jnp.cumsum(mask.astype(jnp.int32), axis=1)
    ranks = jnp.arange(1, cap + 1, dtype=jnp.int32)
    g = jax.vmap(lambda c: jnp.searchsorted(c, ranks, side="left"))(cs)
    ok = g < nl
    cand = jnp.where(ok, jnp.minimum(g, nl - 1), -1).astype(jnp.int32)
    return cand, cnt


def _merge_topk(d2_pool, gid_pool, k: int):
    """The all-gather-of-k merge: final top-k over the P·k_l pooled
    (distance², global id) pairs.  See ``kernels/merge.py`` for the
    standalone kernel + oracle."""
    from repro.kernels import merge as kmerge

    return kmerge.merge_topk(d2_pool, gid_pool, k)


# ---------------------------------------------------------------------------
# ANN: shard_map program
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("mesh", "k", "T", "axis", "n_valid",
                                   "force"))
def _ann_program(data_sh, proj_sh, qp, q, *, mesh: Mesh, k: int, T: int,
                 axis: str, n_valid: int, force: str | None):
    from repro.kernels import ops as kops

    P_ = mesh.shape[axis]
    nl = data_sh.shape[0] // P_
    cap = min(nl, T)  # a shard can hold at most min(nl, T) survivors
    k_l = min(k, cap)  # k > per-shard-n edge: the local answer shrinks

    def local(data_blk, proj_blk, qp_rep, q_rep):
        B = q_rep.shape[0]
        shard = jax.lax.axis_index(axis)
        gid0 = shard * nl
        d2p = _estimate_block(proj_blk, qp_rep, gid0, n_valid)

        # threshold exchange: counts-only bisection to the exact global
        # T-th smallest projected distance (int bracket on float bits)
        row_max = jnp.max(jnp.where(jnp.isfinite(d2p), d2p, 0.0), axis=1)
        hi = jax.lax.bitcast_convert_type(jax.lax.pmax(row_max, axis),
                                          jnp.int32)
        lo = jnp.full_like(hi, -1)

        def rung(_, lh):
            lo, hi = lh
            cnt = jax.lax.psum(_count_le_bits(d2p, _bisect_mid(lo, hi)), axis)
            return _bisect_step(lo, hi, cnt, T)

        lo, hi = jax.lax.fori_loop(0, BISECT_ROUNDS, rung, (lo, hi))
        tau = jax.lax.bitcast_convert_type(hi, jnp.float32)

        # local select + gather-free verify into a device-local top-k
        cand, cnt_loc = _compact_block(d2p, tau, cap)
        d2l, locl = kops.verify_topk(data_blk, q_rep, cand, k_l, force=force)
        gidl = jnp.where(locl >= 0, locl + gid0, -1)

        # one all-gather of k per shard + merge (value-replicated)
        d2_pool = jax.lax.all_gather(d2l, axis, axis=1).reshape(B, P_ * k_l)
        gid_pool = jax.lax.all_gather(gidl, axis, axis=1).reshape(B, P_ * k_l)
        counts = jax.lax.all_gather(cnt_loc, axis, axis=0)  # (P, B)
        ids, dd = _merge_topk(d2_pool, gid_pool, k)
        return ids, dd, counts

    return compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(), P()),
        out_specs=(P(), P(), P()),
    )(data_sh, proj_sh, qp, q)


@partial(jax.jit, static_argnames=("mesh", "k", "T", "R", "axis", "n_valid",
                                   "force"))
def _ann_pq_program(data_sh, proj_sh, codes_sh, luts_sh, qp, q, *, mesh: Mesh,
                    k: int, T: int, R: int, axis: str, n_valid: int,
                    force: str | None):
    """The ANN program with a shard-local ADC rerank tier: survivors are
    scored on the shard's OWN PQ codebook (per-shard codebooks — each
    trained on the rows it encodes), the best R_l rerank candidates are
    exact-verified against the raw rows, then the same k-merge."""
    from repro.kernels import ops as kops

    P_ = mesh.shape[axis]
    nl = data_sh.shape[0] // P_
    cap = min(nl, T)
    R_l = min(R, cap)
    k_l = min(k, R_l)

    def local(data_blk, proj_blk, codes_blk, lut_blk, qp_rep, q_rep):
        B = q_rep.shape[0]
        shard = jax.lax.axis_index(axis)
        gid0 = shard * nl
        d2p = _estimate_block(proj_blk, qp_rep, gid0, n_valid)
        row_max = jnp.max(jnp.where(jnp.isfinite(d2p), d2p, 0.0), axis=1)
        hi = jax.lax.bitcast_convert_type(jax.lax.pmax(row_max, axis),
                                          jnp.int32)
        lo = jnp.full_like(hi, -1)

        def rung(_, lh):
            lo, hi = lh
            cnt = jax.lax.psum(_count_le_bits(d2p, _bisect_mid(lo, hi)), axis)
            return _bisect_step(lo, hi, cnt, T)

        lo, hi = jax.lax.fori_loop(0, BISECT_ROUNDS, rung, (lo, hi))
        tau = jax.lax.bitcast_convert_type(hi, jnp.float32)
        cand, cnt_loc = _compact_block(d2p, tau, cap)

        # shard-local ADC rerank on the shard's own codebook
        lut = lut_blk[0]  # (B, S, V); leading shard dim is 1 in-shard
        codes_c = codes_blk[jnp.maximum(cand, 0)]  # (B, cap, S)
        adc = kops.adc_dist(codes_c, lut, force=force)  # (B, cap)
        adc = jnp.where(cand < 0, jnp.inf, adc)
        _, rsel = jax.lax.top_k(-adc, R_l)
        cand_r = jnp.take_along_axis(cand, rsel, axis=1)  # (B, R_l)

        d2l, locl = kops.verify_topk(data_blk, q_rep, cand_r, k_l,
                                     force=force)
        gidl = jnp.where(locl >= 0, locl + gid0, -1)
        d2_pool = jax.lax.all_gather(d2l, axis, axis=1).reshape(B, P_ * k_l)
        gid_pool = jax.lax.all_gather(gidl, axis, axis=1).reshape(B, P_ * k_l)
        counts = jax.lax.all_gather(cnt_loc, axis, axis=0)
        ids, dd = _merge_topk(d2_pool, gid_pool, k)
        return ids, dd, counts

    return compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None),
                  P(axis, None, None, None), P(), P()),
        out_specs=(P(), P(), P()),
    )(data_sh, proj_sh, codes_sh, luts_sh, qp, q)


# ---------------------------------------------------------------------------
# CP: per-shard join math + shard_map ring program
# ---------------------------------------------------------------------------


def _join_block(a_pts, a_norm, a_key, a_sgid, b_pts, b_norm, b_key, b_sgid,
                ub2, *, k: int, n_valid: int, thresh2: float, tile: int):
    """Dense masked join of two key-sorted blocks under tile-level
    radius pruning: a (tile × tile) pair tile whose 1-D key gap
    satisfies gap² > thresh2·ub² cannot contain a top-k pair (the key
    gap lower-bounds every pair's projected gap), so the whole tile is
    masked out and counted pruned.  Valid pairs are sgid_a < sgid_b —
    which also makes the self-join (a is b) upper-triangular and counts
    every cross pair on exactly one shard of the ring.

    Returns (top-k d² ascending, sgid_i, sgid_j, pairs_verified,
    tiles_pruned) for this block pair."""
    nl = a_pts.shape[0]
    nt = nl // tile
    d2 = jnp.maximum(
        a_norm[:, None] + b_norm[None, :] - 2.0 * jnp.dot(
            a_pts, b_pts.T, precision=jax.lax.Precision.HIGHEST), 0.0)
    pv = ((a_sgid[:, None] < n_valid) & (b_sgid[None, :] < n_valid)
          & (a_sgid[:, None] < b_sgid[None, :]))

    # tile-level radius filter against the global ub register
    a_kmin = a_key.reshape(nt, tile).min(axis=1)
    a_kmax = a_key.reshape(nt, tile).max(axis=1)
    b_kmin = b_key.reshape(nt, tile).min(axis=1)
    b_kmax = b_key.reshape(nt, tile).max(axis=1)
    gap = jnp.maximum(
        jnp.maximum(b_kmin[None, :] - a_kmax[:, None],
                    a_kmin[:, None] - b_kmax[None, :]), 0.0)
    prune = (gap * gap) > (thresh2 * ub2)  # (nt, nt)
    tile_pv = pv.reshape(nt, tile, nt, tile).any(axis=(1, 3))
    keep = jnp.broadcast_to(
        ~prune[:, None, :, None], (nt, tile, nt, tile)).reshape(nl, nl)

    use = pv & keep
    pairs_verified = jnp.sum(use)
    tiles_pruned = jnp.sum(prune & tile_pv)
    d2m = jnp.where(use, d2, jnp.inf).reshape(-1)
    kb = min(k, nl * nl)  # a block pair holds at most nl² pairs
    neg, idx = jax.lax.top_k(-d2m, kb)
    ai, bi = idx // nl, idx % nl
    d_out, i_out, j_out = -neg, a_sgid[ai], b_sgid[bi]
    if kb < k:  # pad to the fixed pool width; inf entries merge away
        pad = k - kb
        d_out = jnp.concatenate([d_out, jnp.full((pad,), jnp.inf,
                                                 d_out.dtype)])
        i_out = jnp.concatenate([i_out, jnp.zeros((pad,), i_out.dtype)])
        j_out = jnp.concatenate([j_out, jnp.zeros((pad,), j_out.dtype)])
    return d_out, i_out, j_out, pairs_verified, tiles_pruned


def _global_ub2(gathered, k: int):
    """ub² = the k-th best pair distance² across all shards' running
    top-k pools (``gathered`` is the all-gathered (P·k,) pool)."""
    neg, _ = jax.lax.top_k(-gathered, k)
    return -neg[k - 1]


@partial(jax.jit, static_argnames=("mesh", "k", "axis", "n_valid", "thresh2",
                                   "tile"))
def _cp_program(data_sh, key_sh, *, mesh: Mesh, k: int, axis: str,
                n_valid: int, thresh2: float, tile: int):
    P_ = mesh.shape[axis]
    nl = data_sh.shape[0] // P_

    def local(data_blk, key_blk):
        key_blk = key_blk.reshape(-1)
        shard = jax.lax.axis_index(axis)
        sgid = shard * nl + jnp.arange(nl)
        norm = jnp.sum(data_blk * data_blk, axis=-1)

        # round 0: intra-shard self-join (no ub yet → no pruning)
        b_d, b_i, b_j, pv, tp = _join_block(
            data_blk, norm, key_blk, sgid, data_blk, norm, key_blk, sgid,
            jnp.float32(jnp.inf), k=k, n_valid=n_valid, thresh2=thresh2,
            tile=tile)
        ub2 = _global_ub2(jax.lax.all_gather(b_d, axis).reshape(-1), k)

        perm = [(i, (i + 1) % P_) for i in range(P_)]

        def hop(carry, _):
            best_d, best_i, best_j, pv, tp, ub2, r_pts, r_norm, r_key, r_sgid \
                = carry
            r_pts = jax.lax.ppermute(r_pts, axis, perm)
            r_norm = jax.lax.ppermute(r_norm, axis, perm)
            r_key = jax.lax.ppermute(r_key, axis, perm)
            r_sgid = jax.lax.ppermute(r_sgid, axis, perm)
            d, i_, j_, pvh, tph = _join_block(
                data_blk, norm, key_blk, sgid, r_pts, r_norm, r_key, r_sgid,
                ub2, k=k, n_valid=n_valid, thresh2=thresh2, tile=tile)
            cat_d = jnp.concatenate([best_d, d])
            cat_i = jnp.concatenate([best_i, i_])
            cat_j = jnp.concatenate([best_j, j_])
            neg, sel = jax.lax.top_k(-cat_d, k)
            best_d, best_i, best_j = -neg, cat_i[sel], cat_j[sel]
            # the global ub register: one small all-gather between rounds
            ub2 = _global_ub2(
                jax.lax.all_gather(best_d, axis).reshape(-1), k)
            return (best_d, best_i, best_j, pv + pvh, tp + tph, ub2,
                    r_pts, r_norm, r_key, r_sgid), None

        carry = (b_d, b_i, b_j, pv, tp, ub2, data_blk, norm, key_blk, sgid)
        (b_d, b_i, b_j, pv, tp, *_), _ = jax.lax.scan(hop, carry, None,
                                                      length=P_ - 1)

        # final merge across shards
        all_d = jax.lax.all_gather(b_d, axis).reshape(-1)
        all_i = jax.lax.all_gather(b_i, axis).reshape(-1)
        all_j = jax.lax.all_gather(b_j, axis).reshape(-1)
        neg, sel = jax.lax.top_k(-all_d, k)
        pair_counts = jax.lax.all_gather(pv, axis)  # (P,) per-shard skew
        return (-neg, all_i[sel], all_j[sel], pair_counts,
                jax.lax.psum(tp, axis))

    return compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis)),
        out_specs=(P(), P(), P(), P(), P()),
    )(data_sh, key_sh)


# ---------------------------------------------------------------------------
# the index
# ---------------------------------------------------------------------------


class ShardedFlatIndex:
    """Row-sharded fused PM-LSH index (ANN + CP + optional per-shard PQ).

    Args:
      data: (n, d) float32 points.
      shards: logical shard count P.  The index builds a 1-D submesh
        over the first P devices and runs the jit'd ``shard_map``
        programs; more shards than visible devices is an error.
      emulate: run the emulated host path instead — identical math
        over P logical blocks (so parity tests cover P ∈ {2,4,8} even
        on one device).
      m / seed / c: projection family size, seed, ANN ratio — same
        meaning as ``build_flat_index``.
      quant: None or "pq" — per-shard PQ codebooks + shard-local ADC
        rerank tier (raw rows are kept for exact verification).
      quant_opts: codec kwargs (e.g. ``{"m_codebooks": 8}``).
      rerank: rerank budget R (None → the flat-pq adaptive default).
      force: kernel dispatch override, as everywhere else.
    """

    def __init__(self, data: np.ndarray, *, shards: int | None = None,
                 mesh: Mesh | None = None, m: int = 15, seed: int = 0,
                 c: float = 1.5, axis: str = "data", emulate: bool = False,
                 quant: str | None = None, quant_opts: dict | None = None,
                 rerank: int | None = None, force: str | None = None,
                 cp_tile: int = 128):
        data = np.asarray(data, np.float32)
        self.n, self.d = data.shape
        self.axis = axis
        self.m = int(m)
        self.seed = int(seed)
        self.force = force
        self.rerank = rerank
        self.cp_tile = int(cp_tile)
        self.family = ProjectionFamily.create(self.d, m, seed=seed)
        self.params = solve_parameters(c, m=m)

        if mesh is not None:
            self.P = int(mesh.shape[axis])
        elif shards is not None:
            self.P = int(shards)
        else:
            self.P = len(jax.devices())
        if self.P < 1:
            raise ValueError(f"shards must be >= 1, got {self.P}")

        proj = np.asarray(self.family.project(data), np.float32)
        self._data_np = data
        self._key_np = proj[:, 0]  # CP sort key (shared build family)
        data_p = pad_rows(data, self.P)
        proj_p = pad_rows(proj, self.P)
        self.nl = data_p.shape[0] // self.P
        self._data_blocks = data_p.reshape(self.P, self.nl, self.d)
        self._proj_blocks = proj_p.reshape(self.P, self.nl, self.m)

        if self.P > len(jax.devices()) and not emulate:
            raise ValueError(
                f"{self.P} shards need {self.P} devices, only "
                f"{len(jax.devices())} visible; pass emulate=True to run "
                "the host-emulated shards instead")
        self.emulated = bool(emulate)
        if self.emulated:
            self.mesh = None
        elif mesh is not None:
            self.mesh = mesh
        else:
            from repro.launch.mesh import make_data_mesh

            self.mesh = make_data_mesh(self.P, axis)
            self._data_sh = _device_put_sharded(data_p, self.mesh, axis)
            self._proj_sh = _device_put_sharded(proj_p, self.mesh, axis)

        # per-shard PQ codebooks (quantized tier)
        self.codecs = None
        if quant is not None:
            if quant != "pq":
                raise ValueError(
                    f"sharded quant tier supports 'pq', got {quant!r}")
            self._train_shard_codecs(dict(quant_opts or {}))

        self._cp_built = False  # key-sorted CP layout is built lazily

    # -- build helpers ----------------------------------------------------

    def _train_shard_codecs(self, opts: dict) -> None:
        """One PQ codec per shard, each trained on the rows it encodes
        (S is uniform across shards — it depends only on d — so the
        codes stack (P, nl, S); V may shrink on a small tail shard, and
        the mesh program's stacked LUTs are +inf-padded up to max V,
        entries no code can reference)."""
        from repro.quant.codec import train_pq

        opts.setdefault("m_codebooks", 16)
        self.codecs = []
        blocks = []
        for p in range(self.P):
            valid = min(self.nl, max(self.n - p * self.nl, 0))
            rows = self._data_blocks[p][: max(valid, 1)]
            codec = train_pq(rows, seed=self.seed + p, **opts)
            self.codecs.append(codec)
            blocks.append(np.asarray(codec.encode(self._data_blocks[p]),
                                     np.uint8))
        self._codes_blocks = np.stack(blocks)  # (P, nl, S)
        if not self.emulated:
            self._codes_sh = _device_put_sharded(
                self._codes_blocks.reshape(self.P * self.nl, -1),
                self.mesh, self.axis)

    def _build_cp_layout(self) -> None:
        if self._cp_built:
            return
        order = np.argsort(self._key_np, kind="stable")
        xs = self._data_np[order]
        ks = self._key_np[order]
        tile = max(1, min(self.cp_tile, -(-self.n // self.P)))
        xs_p = pad_rows(xs, self.P, multiple=tile)
        ks_p = pad_rows(ks.reshape(-1, 1), self.P, fill=np.inf,
                        multiple=tile).reshape(-1)
        self.cp_order = order
        self.cp_nl = xs_p.shape[0] // self.P
        self.cp_tile_eff = tile
        self._cp_data_blocks = xs_p.reshape(self.P, self.cp_nl, self.d)
        self._cp_key_blocks = ks_p.reshape(self.P, self.cp_nl)
        if not self.emulated:
            self._cp_data_sh = _device_put_sharded(xs_p, self.mesh, self.axis)
            self._cp_key_sh = _device_put_sharded(ks_p, self.mesh, self.axis)
        self._cp_built = True

    # -- ANN --------------------------------------------------------------

    def _rerank_budget(self, k: int, T: int) -> int:
        rerank = (self.rerank if self.rerank is not None
                  else max(4 * k, T // 3, 64))
        return min(max(int(rerank), k), T)

    def query(self, q: np.ndarray, k: int, T: int):
        """Batched (c,k)-ANN.  Returns (ids (B,k) int32, dists (B,k)
        float32, counts (P,B) int64 per-shard select survivor counts)."""
        q = np.atleast_2d(np.asarray(q, np.float32))
        qp = jnp.asarray(self.family.project(q))
        qj = jnp.asarray(q)
        if self.emulated:
            ids, dd, counts = self._query_emulated(qj, qp, k=k, T=T)
        elif self.codecs is not None:
            luts = self._stacked_luts(qj)
            with self.mesh:
                ids, dd, counts = _ann_pq_program(
                    self._data_sh, self._proj_sh, self._codes_sh, luts,
                    qp, qj, mesh=self.mesh, k=k, T=T,
                    R=self._rerank_budget(k, T), axis=self.axis,
                    n_valid=self.n, force=self.force)
        else:
            with self.mesh:
                ids, dd, counts = _ann_program(
                    self._data_sh, self._proj_sh, qp, qj, mesh=self.mesh,
                    k=k, T=T, axis=self.axis, n_valid=self.n,
                    force=self.force)
        return (np.asarray(ids, np.int32), np.asarray(dd, np.float32),
                np.asarray(counts, np.int64))

    def _stacked_luts(self, qj):
        luts = [codec.lookup_tables(qj) for codec in self.codecs]  # (B,S,V_p)
        vmax = max(t.shape[-1] for t in luts)
        luts = [jnp.pad(t, ((0, 0), (0, 0), (0, vmax - t.shape[-1])),
                        constant_values=jnp.inf) if t.shape[-1] < vmax else t
                for t in luts]
        return jax.device_put(
            jnp.stack(luts),
            NamedSharding(self.mesh, P(self.axis, None, None, None)))

    # the emulated path: the same stage math over logical shard blocks,
    # with exact host reductions in place of the mesh collectives
    def _query_emulated(self, qj, qp, *, k: int, T: int):
        from repro.kernels import ops as kops

        P_, nl = self.P, self.nl
        cap = min(nl, T)
        pq = self.codecs is not None
        R_l = min(self._rerank_budget(k, T), cap) if pq else cap
        k_l = min(k, R_l if pq else cap)

        # estimate
        d2ps = [_estimate_block(jnp.asarray(self._proj_blocks[p]),
                                qp, p * nl, self.n)
                for p in range(P_)]
        # select: the counts-only bisection to one global threshold
        row_max = [jnp.max(jnp.where(jnp.isfinite(d), d, 0.0), axis=1)
                   for d in d2ps]
        hi0 = row_max[0]
        for r in row_max[1:]:
            hi0 = jnp.maximum(hi0, r)  # pmax
        hi = jax.lax.bitcast_convert_type(hi0, jnp.int32)
        lo = jnp.full_like(hi, -1)
        for _ in range(BISECT_ROUNDS):
            mid = _bisect_mid(lo, hi)
            cnt = _count_le_bits(d2ps[0], mid)
            for d in d2ps[1:]:
                cnt = cnt + _count_le_bits(d, mid)  # psum
            lo, hi = _bisect_step(lo, hi, cnt, T)
        tau = jax.lax.bitcast_convert_type(hi, jnp.float32)
        cands, cnts = [], []
        for p in range(P_):
            cand, cnt_loc = _compact_block(d2ps[p], tau, cap)
            cands.append(cand)
            cnts.append(cnt_loc)
        counts = jnp.stack(cnts)  # (P, B) — the counts all-gather
        # verify, shard by shard
        d2s, gids = [], []
        for p in range(P_):
            cand = cands[p]
            if pq:
                lut = self.codecs[p].lookup_tables(qj)
                codes = jnp.asarray(self._codes_blocks[p])[
                    jnp.maximum(cand, 0)]
                adc = kops.adc_dist(codes, lut, force=self.force)
                adc = jnp.where(cand < 0, jnp.inf, adc)
                _, rsel = jax.lax.top_k(-adc, R_l)
                cand = jnp.take_along_axis(cand, rsel, axis=1)
            d2l, locl = kops.verify_topk(
                jnp.asarray(self._data_blocks[p]), qj, cand, k_l,
                force=self.force)
            d2s.append(d2l)
            gids.append(jnp.where(locl >= 0, locl + p * nl, -1))
        # merge
        d2_pool = jnp.concatenate(d2s, axis=1)
        gid_pool = jnp.concatenate(gids, axis=1)
        ids, dd = _merge_topk(d2_pool, gid_pool, k)
        return ids, dd, counts

    # -- CP ---------------------------------------------------------------

    def cp_query(self, k: int, *, thresh2: float):
        """(c,k)-ACP via the sharded ring join.  Returns (pairs (k',2)
        int32 original ids i<j ascending by exact distance, distances
        (k',) float32, pair_counts (P,) int64, tiles_pruned int)."""
        k = int(k)
        kk = min(k, self.n * (self.n - 1) // 2)
        if kk == 0:
            return (np.empty((0, 2), np.int32), np.empty((0,), np.float32),
                    np.zeros((self.P,), np.int64), 0)
        self._build_cp_layout()
        kj = cp_join_budget(kk, self.n * (self.n - 1) // 2)
        if self.emulated:
            fd, fi, fj, pair_counts, tp = self._cp_emulated(
                kj, thresh2=thresh2)
        else:
            with self.mesh:
                fd, fi, fj, pair_counts, tp = _cp_program(
                    self._cp_data_sh, self._cp_key_sh, mesh=self.mesh, k=kj,
                    axis=self.axis, n_valid=self.n, thresh2=float(thresh2),
                    tile=self.cp_tile_eff)
        fd = np.asarray(fd)
        fi = np.asarray(fi)
        fj = np.asarray(fj)
        # host re-verification, exactly like cp_fused_search: map sorted
        # positions back through the permutation, recompute the pool
        # subtract-then-norm, stable re-sort, keep the k best
        real = np.isfinite(fd) & (fi >= 0)
        ids_a = self.cp_order[fi[real]].astype(np.int64)
        ids_b = self.cp_order[fj[real]].astype(np.int64)
        pairs = np.stack([np.minimum(ids_a, ids_b),
                          np.maximum(ids_a, ids_b)], axis=1).astype(np.int32)
        diff = (self._data_np[pairs[:, 0].astype(np.int64)]
                - self._data_np[pairs[:, 1].astype(np.int64)])
        dists = np.sqrt(np.sum(diff.astype(np.float32) ** 2, axis=1)
                        ).astype(np.float32)
        resort = np.argsort(dists, kind="stable")[:kk]
        return (pairs[resort], dists[resort],
                np.asarray(pair_counts, np.int64), int(tp))

    def _cp_emulated(self, k: int, *, thresh2: float):
        P_, nl, tile = self.P, self.cp_nl, self.cp_tile_eff
        blocks = [(jnp.asarray(self._cp_data_blocks[p]),
                   jnp.asarray(self._cp_key_blocks[p]),
                   jnp.arange(p * nl, (p + 1) * nl)) for p in range(P_)]
        norms = [jnp.sum(b[0] * b[0], axis=-1) for b in blocks]

        best = []
        pv_cnt = [jnp.int32(0)] * P_
        tp_cnt = jnp.int32(0)
        for p in range(P_):  # round 0: each shard's self-join
            pts, key, sgid = blocks[p]
            d, i_, j_, pv, tp = _join_block(
                pts, norms[p], key, sgid, pts, norms[p], key, sgid,
                jnp.float32(jnp.inf), k=k, n_valid=self.n,
                thresh2=thresh2, tile=tile)
            best.append((d, i_, j_))
            pv_cnt[p] = pv_cnt[p] + pv
            tp_cnt = tp_cnt + tp
        ub2 = _global_ub2(jnp.concatenate([b[0] for b in best]), k)
        recv = list(range(P_))  # recv[p]: which block shard p holds
        for r in range(1, P_):
            recv = [recv[(p - 1) % P_] for p in range(P_)]  # the ring hop
            for p in range(P_):
                pts, key, sgid = blocks[p]
                rp, rk, rs = blocks[recv[p]]
                d, i_, j_, pv, tp = _join_block(
                    pts, norms[p], key, sgid, rp, norms[recv[p]], rk,
                    rs, ub2, k=k, n_valid=self.n, thresh2=thresh2,
                    tile=tile)
                cat_d = jnp.concatenate([best[p][0], d])
                cat_i = jnp.concatenate([best[p][1], i_])
                cat_j = jnp.concatenate([best[p][2], j_])
                neg, sel = jax.lax.top_k(-cat_d, k)
                best[p] = (-neg, cat_i[sel], cat_j[sel])
                pv_cnt[p] = pv_cnt[p] + pv
                tp_cnt = tp_cnt + tp
            ub2 = _global_ub2(jnp.concatenate([b[0] for b in best]), k)
        # merge
        all_d = jnp.concatenate([b[0] for b in best])
        all_i = jnp.concatenate([b[1] for b in best])
        all_j = jnp.concatenate([b[2] for b in best])
        neg, sel = jax.lax.top_k(-all_d, k)
        fd, fi, fj = -neg, all_i[sel], all_j[sel]
        pair_counts = jnp.stack(pv_cnt)
        return fd, fi, fj, pair_counts, int(tp_cnt)

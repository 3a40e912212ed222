"""Pallas TPU kernel: radius-threshold candidate selection.

The PM-LSH SELECT step wants the T = βn + k projected-nearest points.
``topk.py`` streams a selection network that is O(k²) per tile — great
for the final answer (k ≤ 128), hopeless for the candidate budget
(T in the thousands).  ``lax.top_k`` handles any T but pays O(n·T)
sort work and materializes ordering state for the full row.

This kernel exploits what the paper already gives us: the tunable
confidence interval (Lemma 3 / Eq. 9) turns the rank T into a RADIUS —
the T-th smallest projected distance is within a few of the paper's
``r·c^i`` range-query rungs of the Lemma-2 seed estimate.  Selection
then needs no sort at all, only branch-free O(n) threshold passes:

  phase 0        one pass counts survivors of L ladder rungs
                 τ0·c^{2(i−L0)} simultaneously (the paper's radius
                 doubling schedule, squared space) and brackets the
                 T-th smallest value between two rungs;
  phases 1..I    bisection passes shrink the bracket: count(d ≤ mid)
                 vs T keeps the invariant count(lo) < T ≤ count(hi);
  final phase    one pass packs each tile's survivors (d ≤ hi) to the
                 front of its own output tile (``pack_front``): a 0/1
                 mask times a triangle of ones on the MXU ranks them,
                 a one-hot moves each to its rank.  Outside the
                 kernel the per-tile runs are joined into a dense
                 (B, T_pad) buffer by their running survivor totals.

The caller finishes with one top_k over the T_pad ≈ 1.1·T compacted
columns (``ops.radius_select``), so total ordering work drops from
O(n·T) to O(T_pad·T) while the threshold passes stay O(n) stream reads.

Exactness: the bracket invariant guarantees every true top-T element
survives the threshold, and compaction preserves ascending-index order,
so the finishing top_k reproduces ``lax.top_k`` exactly — including its
lowest-index tie-break — whenever the survivor count fits T_pad.  A
pathological tie cluster (> T_pad − T equal values straddling the T-th
smallest) overflows the buffer, and overflow truncates in INDEX order —
the dropped high-index survivors may be strictly nearer than kept ones,
so an overflowed buffer is NOT a valid candidate set.  The kernel
therefore yields the exact per-row survivor counts and the dispatch
wrapper (``ops.radius_select``) reroutes any overflowed batch to the
exact sort, keeping parity unconditional.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["radius_select_kernel", "radius_select_pallas", "pack_front",
           "tile_ranks"]


def radius_select_kernel(
    tau0_ref, d_ref, ov_ref, oi_ref,
    cnt_ref, lad_ref, lo_ref, hi_ref, dmax_ref,
    *, T: int, block_n: int, L: int, L0: int, c2: float, iters: int,
    n_tiles: int, Bh: int,
):
    p = pl.program_id(0)  # phase: 0 ladder, 1..iters bisect, last compact
    j = pl.program_id(1)  # tile along n
    last = n_tiles - 1
    d = d_ref[...]  # (Bh, bN), padding carries +inf
    real = d < jnp.inf
    lane = jax.lax.broadcasted_iota(jnp.int32, (Bh, 128), 1)

    def rung(l):  # ladder threshold l: τ0·c^{2(l−L0)}, squared units
        return tau0_ref[:, :1] * (c2 ** (l - L0))

    @pl.when((p == 0) & (j == 0))
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        lad_ref[...] = jnp.zeros_like(lad_ref)
        dmax_ref[...] = jnp.zeros_like(dmax_ref)

    # -- phase 0: count all L ladder rungs in one data pass ---------------
    @pl.when(p == 0)
    def _ladder():
        tile_cnt = jnp.zeros((Bh, 128), jnp.float32)  # rung l in lane l
        for l in range(L):
            c = jnp.sum(((d <= rung(l)) & real).astype(jnp.float32), axis=1,
                        keepdims=True)
            tile_cnt = jnp.where(lane == l, c, tile_cnt)
        lad_ref[...] += tile_cnt
        dmax_ref[...] = jnp.maximum(
            dmax_ref[...],
            jnp.max(jnp.where(real, d, -jnp.inf), axis=1, keepdims=True))

        @pl.when(j == last)
        def _bracket():
            # counts grow with the rung, so the smallest rung holding >= T
            # survivors is the number of rungs holding fewer (L: none)
            below = (lane < L) & (lad_ref[...] < T)
            first = jnp.sum(below.astype(jnp.float32), axis=1, keepdims=True)
            any_ge = first < L
            at_first = sum(jnp.where(first == l, rung(l), 0.0)
                           for l in range(L))
            below_first = sum(jnp.where(first == l + 1, rung(l), 0.0)
                              for l in range(L))
            dmax = dmax_ref[:, :1]
            # the data max rescues a seed so low the whole ladder
            # undershoots, and one so high rung 0 overshoots
            hi = jnp.minimum(jnp.where(any_ge, at_first, dmax), dmax)
            lo = jnp.where(any_ge, below_first, rung(L - 1))
            lo = jnp.minimum(lo, hi)
            hi_ref[...] = jnp.broadcast_to(hi, hi_ref.shape)
            lo_ref[...] = jnp.broadcast_to(lo, lo_ref.shape)
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    # -- phases 1..iters: one bisection step per data pass ----------------
    @pl.when((p >= 1) & (p <= iters))
    def _bisect():
        mid = 0.5 * (lo_ref[:, :1] + hi_ref[:, :1])
        cnt_ref[...] += jnp.broadcast_to(
            jnp.sum((d <= mid) & real, axis=1,
                    keepdims=True).astype(jnp.float32), cnt_ref.shape)

        @pl.when(j == last)
        def _update():
            ge = cnt_ref[:, :1] >= T
            hi_ref[...] = jnp.where(ge, jnp.broadcast_to(mid, hi_ref.shape),
                                    hi_ref[...])
            lo_ref[...] = jnp.where(ge, lo_ref[...],
                                    jnp.broadcast_to(mid, lo_ref.shape))
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    # -- final phase: pack each tile's survivors (d <= hi) to its front ----
    @pl.when(p == iters + 1)
    def _compact():
        def group(g, _):  # 8 rows at a time bound the (8, bN, bN) one-hot
            r = pl.multiple_of(g * 8, 8)
            dg = d_ref[pl.ds(r, 8), :]
            mask = (dg <= hi_ref[pl.ds(r, 8), :1]) & (dg < jnp.inf)
            vals, src, keep = pack_front(dg, mask)
            ov_ref[pl.ds(r, 8), :] = jnp.where(keep, vals, jnp.inf)
            oi_ref[pl.ds(r, 8), :] = jnp.where(keep, j * block_n + src, -1)
            return 0

        jax.lax.fori_loop(0, Bh // 8, group, 0)


def tile_ranks(mask: jax.Array) -> jax.Array:
    """Per row, how many set entries precede each lane: an exclusive
    running count, as a 0/1 mask times a strictly upper triangle of
    ones — exact on the MXU (inputs 0/1, sums ≤ lanes, float32
    accumulation)."""
    w = mask.shape[1]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
           < jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
           ).astype(jnp.float32)
    return jnp.dot(mask.astype(jnp.float32), tri,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def pack_front(vals: jax.Array, mask: jax.Array):
    """Move each row's masked entries of ``vals`` (R, W) to the row's
    front, in lane order.  Returns (packed vals, their source lanes,
    keep) where ``keep`` marks the first count(row) lanes; lanes past
    it hold zeros."""
    R, W = vals.shape
    rank = tile_ranks(mask)
    # one-hot (row, dst, src) moves survivor src to slot rank[src];
    # one term per slot, so the masked sums are exact
    dst = jax.lax.broadcasted_iota(jnp.int32, (R, W, W), 1)
    src = jax.lax.broadcasted_iota(jnp.int32, (R, W, W), 2)
    onehot = mask[:, None, :] & (rank[:, None, :] == dst)
    packed = jnp.sum(jnp.where(onehot, vals[:, None, :], 0.0), axis=2)
    lanes = jnp.sum(jnp.where(onehot, src, 0), axis=2)
    keep = (jax.lax.broadcasted_iota(jnp.int32, (R, W), 1)
            < jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True))
    return packed, lanes, keep


@functools.partial(
    jax.jit,
    static_argnames=("T", "T_pad", "block_n", "ladder", "iters", "c2",
                     "interpret"),
)
def radius_select_pallas(
    d: jax.Array,
    tau0: jax.Array,
    T: int,
    *,
    T_pad: int,
    block_n: int = 128,
    ladder: int = 16,
    iters: int = 14,
    c2: float = 2.25,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Compact the T smallest of each row of d (B, N) into T_pad slots.

    Args:
      d: (B, N) float32 distances (non-negative; +inf allowed as padding).
      tau0: (B,) per-row threshold seed in d's (squared) units — e.g. the
        Eq. 9 / Lemma 2 estimate of the T-th projected distance.  The
        rung ladder spans tau0·c2^±(ladder/2), so any seed within a few
        orders of magnitude works; a hopeless seed falls back to the
        observed [0, max(d)] bracket.
      T: selection rank (the guarantee target).
      T_pad: compaction buffer width, ≥ T; slack absorbs the unresolved
        bisection window and boundary ties.
      ladder / iters / c2: rung count, bisection passes, squared radius
        growth factor (c² in the paper's r·c^i schedule).

    Returns (vals (B, T_pad), idx (B, T_pad), count (B,)): survivors in
    ascending-INDEX order, padded with +inf / -1; count is the exact
    per-row survivor total.  count ≤ T_pad: the T smallest are all in
    the buffer — finish with a top_k over the T_pad columns
    (``ops.radius_select`` does).  count > T_pad: the buffer
    OVERFLOWED and was truncated in index order, so it may have lost
    true top-T members — callers MUST discard it and fall back to an
    exact selection (the dispatch wrapper does; see module doc).
    """
    B, N = d.shape
    assert 1 <= T <= N, f"T={T} out of range for N={N}"
    assert T_pad >= T, f"T_pad={T_pad} < T={T}"
    L = min(ladder, 128)
    bN = min(block_n, _ceil_mult(N, 128))
    Bh = _ceil_mult(B, 8)
    Np = _ceil_mult(N, bN)
    n_tiles = Np // bN
    P = iters + 2
    kern = functools.partial(
        radius_select_kernel, T=T, block_n=bN, L=L, L0=L // 2,
        c2=c2, iters=iters, n_tiles=n_tiles, Bh=Bh)
    # the packed tiles are written only in the last phase; before it the
    # output block index stays put, so nothing is written back early
    out_tile = pl.BlockSpec(
        (Bh, bN), lambda p, j: (0, jnp.where(p == P - 1, j, 0)))
    with jax.named_scope("radius"):
        dp = jnp.full((Bh, Np), jnp.inf, jnp.float32).at[:B, :N].set(d)
        t0 = jnp.zeros((Bh, 128), jnp.float32).at[:B, :].set(
            jnp.broadcast_to(
                jnp.maximum(jnp.asarray(tau0, jnp.float32), 1e-30)[:, None],
                (B, 128)))
        vals_t, idx_t = pl.pallas_call(
            kern,
            grid=(P, n_tiles),
            in_specs=[
                pl.BlockSpec((Bh, 128), lambda p, j: (0, 0)),
                pl.BlockSpec((Bh, bN), lambda p, j: (0, j)),
            ],
            out_specs=[out_tile, out_tile],
            out_shape=[
                jax.ShapeDtypeStruct((Bh, Np), jnp.float32),
                jax.ShapeDtypeStruct((Bh, Np), jnp.int32),
            ],
            scratch_shapes=[
                pltpu.VMEM((Bh, 128), jnp.float32),  # bisection count
                pltpu.VMEM((Bh, 128), jnp.float32),  # ladder counts (lane l)
                pltpu.VMEM((Bh, 128), jnp.float32),  # bracket lo
                pltpu.VMEM((Bh, 128), jnp.float32),  # bracket hi
                pltpu.VMEM((Bh, 128), jnp.float32),  # running data max
            ],
            interpret=interpret,
            name="radius_select_pallas",
        )(t0, dp)
    return _concat_tiles(vals_t[:B], idx_t[:B], bN, T_pad)


@jax.named_scope("compact")
def _concat_tiles(vals_t, idx_t, bN: int, T_pad: int):
    """Join each row's front-packed tile runs into its first T_pad slots.

    vals_t / idx_t: (B, n_tiles·bN), tile t's survivors packed to the
    front of its bN columns, (+inf, -1) behind them.  Slot s of a row
    is read from the tile whose running survivor total first exceeds s.
    """
    B, Np = idx_t.shape
    per_tile = jnp.sum(idx_t.reshape(B, Np // bN, bN) >= 0, axis=2,
                       dtype=jnp.int32)  # (B, n_tiles)
    upto = jnp.cumsum(per_tile, axis=1)  # survivors in tiles 0..t
    count = upto[:, -1]
    slot = jnp.arange(T_pad, dtype=jnp.int32)
    tile = jax.vmap(lambda u: jnp.searchsorted(u, slot, side="right"))(upto)
    tile = jnp.minimum(tile, Np // bN - 1).astype(jnp.int32)
    start = jnp.take_along_axis(upto - per_tile, tile, axis=1)
    col = tile * bN + slot[None, :] - start
    live = slot[None, :] < count[:, None]
    vals = jnp.where(live, jnp.take_along_axis(vals_t, col, axis=1), jnp.inf)
    idx = jnp.where(live, jnp.take_along_axis(idx_t, col, axis=1), -1)
    return vals, idx, count


def _ceil_mult(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m

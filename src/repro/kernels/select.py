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
  join phase     one pass packs each tile's survivors (d ≤ hi) to the
                 front of the tile (``pack_front``: a 0/1 mask times a
                 triangle of ones on the MXU ranks them, a one-hot
                 moves each to its rank) and writes the run into the
                 row's dense buffer at the row's running survivor
                 total, an SMEM scalar: rolled by the total mod 128
                 lanes, into the 128-lane chunk it points at and the
                 next.  The value and id buffers, (rows, chunks, 128),
                 stay in VMEM across the pass and go to HBM once, at
                 its end.  A batch whose buffers would not fit the VMEM
                 budget is joined in row blocks, one pass each.

The caller finishes with one top_k over the T_pad ≈ 1.1·T compacted
columns (``ops.radius_select``), so total ordering work drops from
O(n·T) to O(T_pad·T) while the threshold passes stay O(n) stream reads.

Exactness: the bracket invariant guarantees every true top-T element
survives the threshold, and the join walks the tiles in index order and
each run in lane order, so the buffer is ascending in index and the
finishing top_k reproduces ``lax.top_k`` exactly — including its
lowest-index tie-break — whenever the survivor count fits T_pad.  A
pathological tie cluster (> T_pad − T equal values straddling the T-th
smallest) overflows the buffer: a run that starts at slot T_pad or
later lands in spill chunks that are never read, so overflow truncates
in INDEX order —
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
    tau0_ref, d_ref, ov_hbm, oi_hbm, ocnt_ref,
    cnt_ref, lad_ref, lo_ref, hi_ref, dmax_ref, vbuf, ibuf, off_ref, sem,
    *, T: int, T_pad: int, block_n: int, L: int, L0: int, c2: float,
    iters: int, n_tiles: int, Bh: int, R: int,
):
    p = pl.program_id(0)  # 0 ladder, 1..iters bisect, then one join a block
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

    # -- then one pass per block of R rows: join the survivors (d <= hi) --
    # into each row's buffer at its running survivor total
    @pl.when(p > iters)
    def _join():
        base = (p - iters - 1) * R  # first row of this block

        @pl.when(j == 0)
        def _reset():
            def row(r, _):
                off_ref[r] = 0
                vbuf[r] = jnp.full(vbuf.shape[1:], jnp.inf, jnp.float32)
                ibuf[r] = jnp.full(ibuf.shape[1:], -1, jnp.int32)
                return 0

            jax.lax.fori_loop(0, R, row, 0)

        chunk = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        spill = -(-T_pad // 128)  # the first chunk wholly past T_pad

        def group(g, _):  # 8 rows at a time bound the (8, bN, bN) one-hot
            r = pl.multiple_of(g * 8, 8)
            rows = pl.ds(pl.multiple_of(base + r, 8), 8)
            dg = d_ref[rows, :]
            mask = (dg <= hi_ref[rows, :1]) & (dg < jnp.inf)
            vals, src, keep = pack_front(dg, mask)
            vals = jnp.where(keep, vals, jnp.inf)
            ids = jnp.where(keep, j * block_n + src, -1)
            n = jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)
            cnt_ref[rows, :] += jnp.broadcast_to(n.astype(jnp.float32),
                                                 (8, 128))
            # row i's slots [off, off + n) start s lanes into chunk c:
            # each 128 lanes of its run, rolled by s, fill the rest of
            # one chunk and the head of the next.  A run that starts at
            # T_pad or later goes to chunks past T_pad, which are never
            # read: the count stays exact, so the caller sees the
            # overflow.  All loads come before all stores, so the rows'
            # writes need not wait on one another
            offs = [off_ref[r + i] for i in range(8)]
            for i in range(8):
                off_ref[r + i] = offs[i] + jnp.sum(n[i:i + 1])
            cs = [jnp.minimum(o // 128, spill) for o in offs]
            heads = [chunk >= o % 128 for o in offs]
            old = [[buf[r + i, pl.ds(cs[i], 1), :] for i in range(8)]
                   for buf in (vbuf, ibuf)]
            for buf, run, pad, first in ((vbuf, vals, jnp.inf, old[0]),
                                         (ibuf, ids, -1, old[1])):
                for i in range(8):
                    moved = [first[i]] + [
                        pltpu.roll(run[i:i + 1, q:q + 128], offs[i] % 128, 1)
                        for q in range(0, block_n, 128)]
                    moved.append(jnp.full_like(first[i], pad))
                    for q in range(block_n // 128 + 1):
                        buf[r + i, pl.ds(cs[i] + q, 1), :] = jnp.where(
                            heads[i], moved[q + 1], moved[q])
            return 0

        jax.lax.fori_loop(0, R // 8, group, 0)

        @pl.when(j == last)
        def _flush():
            copies = [pltpu.make_async_copy(buf, out.at[pl.ds(base, R)],
                                            sem.at[q])
                      for q, (buf, out) in enumerate(((vbuf, ov_hbm),
                                                      (ibuf, oi_hbm)))]
            for cp in copies:
                cp.start()
            for cp in copies:
                cp.wait()
            ocnt_ref[...] = cnt_ref[...]  # the last block's write holds all


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


# VMEM held by the join's value and id buffers: rows × slots × 8 bytes.
# A batch whose buffers exceed it is joined in row blocks that fit, one
# more pass over the tiles each (v5e: 128 MiB of VMEM a core)
_BUFFER_BYTES = 64 * 1024**2
_VMEM_SPARE = 16 * 1024**2  # the pipelined tiles and the one-hot


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
    return _radius_select(d, tau0, T, T_pad=T_pad, block_n=block_n,
                          ladder=ladder, iters=iters, c2=c2,
                          interpret=interpret, buffer_bytes=_BUFFER_BYTES)


def _radius_select(d, tau0, T, *, T_pad, block_n, ladder, iters, c2,
                   interpret, buffer_bytes):
    B, N = d.shape
    assert 1 <= T <= N, f"T={T} out of range for N={N}"
    assert T_pad >= T, f"T_pad={T_pad} < T={T}"
    L = min(ladder, 128)
    bN = min(block_n, _ceil_mult(N, 128))
    assert bN % 128 == 0, f"block_n={block_n} is not a multiple of 128"
    Np = _ceil_mult(N, bN)
    n_tiles = Np // bN
    # a row's buffer: its T_pad slots in 128-lane chunks, room for a
    # tile's run that starts in the last of them, and the spill chunks
    # where the runs past T_pad land; whole (8, 128) tiles, so the VMEM
    # buffer and its HBM copy have one size
    C = _ceil_mult(-(-T_pad // 128) + bN // 128 + 1, 8)
    row_bytes = C * 128 * 8
    R, blocks = _row_blocks(_ceil_mult(B, 8), buffer_bytes // row_bytes)
    Bh = R * blocks
    P = iters + 1 + blocks
    kern = functools.partial(
        radius_select_kernel, T=T, T_pad=T_pad, block_n=bN, L=L, L0=L // 2,
        c2=c2, iters=iters, n_tiles=n_tiles, Bh=Bh, R=R)
    with jax.named_scope("radius"):
        dp = jnp.full((Bh, Np), jnp.inf, jnp.float32).at[:B, :N].set(d)
        t0 = jnp.zeros((Bh, 128), jnp.float32).at[:B, :].set(
            jnp.broadcast_to(
                jnp.maximum(jnp.asarray(tau0, jnp.float32), 1e-30)[:, None],
                (B, 128)))
        rows = pl.BlockSpec((Bh, 128), lambda p, j: (0, 0))
        vals, idx, count = pl.pallas_call(
            kern,
            grid=(P, n_tiles),
            in_specs=[rows, pl.BlockSpec((Bh, bN), lambda p, j: (0, j))],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY), rows],
            out_shape=[
                jax.ShapeDtypeStruct((Bh, C, 128), jnp.float32),
                jax.ShapeDtypeStruct((Bh, C, 128), jnp.int32),
                jax.ShapeDtypeStruct((Bh, 128), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((Bh, 128), jnp.float32),  # pass / survivor count
                pltpu.VMEM((Bh, 128), jnp.float32),  # ladder counts (lane l)
                pltpu.VMEM((Bh, 128), jnp.float32),  # bracket lo
                pltpu.VMEM((Bh, 128), jnp.float32),  # bracket hi
                pltpu.VMEM((Bh, 128), jnp.float32),  # running data max
                pltpu.VMEM((R, C, 128), jnp.float32),  # joined values
                pltpu.VMEM((R, C, 128), jnp.int32),  # joined ids
                pltpu.SMEM((R,), jnp.int32),  # each row's running total
                pltpu.SemaphoreType.DMA((2,)),  # the buffers' copies out
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=R * row_bytes + _VMEM_SPARE),
            interpret=interpret,
            name="radius_select_pallas",
        )(t0, dp)
    vals = vals.reshape(Bh, C * 128)[:B, :T_pad]
    idx = idx.reshape(Bh, C * 128)[:B, :T_pad]
    return vals, idx, count[:B, 0].astype(jnp.int32)


def _row_blocks(rows: int, fit: int) -> tuple[int, int]:
    """Split ``rows`` (a multiple of 8) into the fewest blocks of at most
    ``fit`` rows: (rows a block, a multiple of 8; blocks)."""
    blocks = -(-rows // max(fit // 8 * 8, 8))
    return _ceil_mult(-(-rows // blocks), 8), blocks


def _ceil_mult(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m

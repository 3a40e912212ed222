"""Pallas TPU kernel: gather-free candidate verification.

The PM-LSH VERIFY step computes exact d-dimensional distances on the
T = βn + k selected candidates and keeps the k best.  The unfused
pipeline spells this ``data[cand]`` → a (B, T, d) tensor that XLA
materializes in HBM (one gather write + one read back) before the
distance reduction ever runs.  At T ≈ 0.1n that round-trip is ~3× the
verify stage's unavoidable traffic and dominates the query's HBM bytes.

This kernel never materializes the candidate tensor: the grid walks
(block of 8 query rows, candidate tile); each step DMAs the tile's
8·bT rows from the HBM-resident data array straight into a VMEM
scratch, computes exact squared distances against the resident query
rows (direct difference, as the oracle), and folds them into the
running (8, k) top-k via the selection network shared with ``topk.py``.
Gathered rows live only in VMEM.

The rows are copied out of an (n, 1, d) view of the data: the TPU
compiler copies only whole tiles, and the (n, d) array is tiled 8 rows
deep.  XLA lays the view out one row per tile row, unpadded, so every
call first copies the whole data array once (1 GB at n = 1M, d = 256;
``tests/test_tpu_compile.py`` pins its size).  Past that copy, HBM sees
one read of each candidate row.

Padding contract: candidate ids < 0 are placeholders — their distance
is +inf and they can only surface in the answer as (-1, inf) when a row
has fewer than k real candidates, matching the facade's padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .topk import smallest_k

__all__ = ["verify_topk_kernel", "verify_topk_pallas"]


def verify_topk_kernel(q_ref, cid_ref, cand_ref, data_ref, ov_ref, oi_ref,
                       rows_ref, sem, *, block_t: int, rows: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ov_ref[...] = jnp.full_like(ov_ref, jnp.inf)
        oi_ref[...] = jnp.full_like(oi_ref, -1)

    # gather the tile's candidate rows HBM → VMEM, one (1, d) row per
    # copy (padding ids < 0 copy nothing and are masked below); start
    # every copy of the step, then drain
    def each_copy(op):
        for r in range(rows):
            def body(i, _, r=r):
                c = cid_ref[r, i]

                @pl.when(c >= 0)
                def _():
                    op(pltpu.make_async_copy(
                        data_ref.at[c], rows_ref.at[r * block_t + i],
                        sem.at[r]))

                return 0

            jax.lax.fori_loop(0, block_t, body, 0)

    each_copy(lambda cp: cp.start())
    each_copy(lambda cp: cp.wait())

    d = q_ref.shape[1]
    x = rows_ref[...].reshape(rows, block_t, d)
    q = q_ref[...].astype(jnp.float32)  # (rows, d)
    # the direct difference form, as the oracle: no norm-trick
    # cancellation between near-duplicates
    d2 = jnp.sum((x - q[:, None, :]) ** 2, axis=-1)  # (rows, bT)
    cand = cand_ref[...]  # (rows, bT) int32 ids into data, -1 = padding
    d2 = jnp.where(cand < 0, jnp.inf, d2)
    accv = ov_ref[...]  # running top-k, ascending

    @pl.when(jnp.any(jnp.min(d2, axis=1, keepdims=True)
                     < jnp.max(accv, axis=1, keepdims=True)))
    def _merge():
        outv, (outi,) = smallest_k(
            [(accv, (oi_ref[...],), (1,)), (d2, (cand,), (1,))],
            ov_ref.shape[1], rows)
        ov_ref[...] = outv
        oi_ref[...] = outi


@functools.partial(jax.jit, static_argnames=("k", "block_t", "interpret"))
def verify_topk_pallas(
    data: jax.Array,
    q: jax.Array,
    cand: jax.Array,
    k: int,
    *,
    block_t: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Exact-verify candidates and answer: fused gather + distance + top-k.

    Args:
      data: (n, d) float32 points, resident in HBM; read through an
        (n, 1, d) relayout copy made on every call.
      q: (B, d) float32 queries.
      cand: (B, Tc) int32 candidate ids per query; -1 marks padding.
      k: answer size, ≤ min(128, Tc) (same selection-network regime as
        ``topk.py``; the big-T selection belongs to ``select.py``).

    Returns (d² (B, k) ascending float32, ids (B, k) int32); slots
    beyond a row's real candidates are (+inf, -1).  Ties resolve to the
    earliest candidate position, matching ``lax.top_k`` over the same
    candidate order.
    """
    n, d = data.shape
    B, Tc = cand.shape
    B2, d2_ = q.shape
    assert B == B2 and d == d2_, f"shape mismatch q{q.shape} cand{cand.shape}"
    if k > 128:
        raise ValueError(
            f"verify_topk_pallas: k={k} > 128; the in-VMEM selection "
            "network is O(k²) — route large-k selection through "
            "radius_select instead")
    # k > Tc is legal: short rows answer with (-1, inf) padding slots
    R = 8  # query rows per grid step: one full sublane tile
    bT = min(block_t, _ceil_mult(max(Tc, 1), 128))
    Tp = _ceil_mult(max(Tc, 1), bT)
    Bp = _ceil_mult(B, R)
    cp = jnp.full((Bp, Tp), -1, jnp.int32).at[:B, :Tc].set(
        jnp.asarray(cand, jnp.int32))
    qp = jnp.zeros((Bp, d), jnp.float32).at[:B].set(
        jnp.asarray(q, jnp.float32))
    # (n, 1, d): one point per leading index, so a single-row copy is a
    # whole tile of the HBM layout.  Not a bitcast: XLA relays the data
    # out from 8-row to 1-row tiles, a copy of the whole array per call
    rows3 = jnp.asarray(data, jnp.float32).reshape(n, 1, d)
    kern = functools.partial(verify_topk_kernel, block_t=bT, rows=R)
    vals, idx = pl.pallas_call(
        kern,
        grid=(Bp // R, Tp // bT),
        in_specs=[
            pl.BlockSpec((R, d), lambda b, j: (b, 0)),
            pl.BlockSpec((R, bT), lambda b, j: (b, j),
                         memory_space=pltpu.SMEM),  # ids for the copies
            pl.BlockSpec((R, bT), lambda b, j: (b, j)),  # ids as a vector
            pl.BlockSpec(memory_space=pl.ANY),  # data stays in HBM
        ],
        out_specs=[
            pl.BlockSpec((R, k), lambda b, j: (b, 0)),
            pl.BlockSpec((R, k), lambda b, j: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, k), jnp.float32),
            jax.ShapeDtypeStruct((Bp, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((R * bT, 1, d), jnp.float32),  # gathered rows
            pltpu.SemaphoreType.DMA((R,)),
        ],
        interpret=interpret,
    )(qp, cp, cp, rows3)
    vals, idx = vals[:B], idx[:B]
    return vals, jnp.where(jnp.isinf(vals), -1, idx)


def _ceil_mult(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m

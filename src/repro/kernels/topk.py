"""Pallas TPU kernel: streaming k-smallest selection over distance tiles.

PM-LSH's SELECT step takes the T = βn + k projected-nearest candidates.
XLA's native `lax.top_k` is fine when the full (B, N) distance row fits
HBM, but streaming selection fused after the distance tiles avoids a
second pass.  This kernel demonstrates the streaming pattern: the grid
walks N tiles; the resident (B, k) output block carries the running
best values + indices; each step merges the tile via k rounds of masked
first-minimum extraction (selection network — regular, branch-free,
TPU-friendly for k ≤ 128).  A tile none of whose values beats the
running k-th is skipped outright.

Complexity per merged tile: k·(k + bN) compares on the VPU.  For the
k ≤ 64, bN = 512 regime of PM-LSH queries this is ≈ 37K compare-ops per
tile — noise next to the MXU distance work it fuses behind.

``smallest_k`` is the selection network itself, shared with the verify
and pair-join kernels.  It reads every pick out of the pool with masked
reductions over the ``hit`` mask — the TPU compiler lowers no gather
inside a kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["smallest_k", "topk_kernel", "topk_smallest_pallas"]

_BIG = jnp.iinfo(jnp.int32).max  # order of an element already taken


def _row_major(shape, axes) -> jax.Array:
    """Position of each element within its pool: row-major over ``axes``."""
    pos = jnp.zeros(shape, jnp.int32)
    stride = 1
    for ax in reversed(axes):
        pos = pos + stride * jax.lax.broadcasted_iota(jnp.int32, shape, ax)
        stride *= shape[ax]
    return pos


def smallest_k(pieces, k: int, rows: int):
    """The k smallest of a pool, ascending, with their payloads.

    ``pieces`` is a sequence of ``(vals, payloads, axes)``; the pool of
    output row r is the concatenation, in piece order, of each piece's
    elements of row r taken row-major over ``axes``: ``(1,)`` for a
    (rows, C) piece, ``(0, 1)`` for one 2-D piece when rows == 1.
    ``payloads`` are int32 arrays shaped like ``vals`` (ids) that travel
    with the picks.  Ties go to the earlier pool element, as with
    ``lax.top_k`` over the concatenation, and each element is taken at
    most once.  Returns (vals (rows, k) float32, [payload (rows, k)]).
    """
    npay = len(pieces[0][1])
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)
    vals = [v for v, _, _ in pieces]
    orders = [_row_major(v.shape, axes) for v, _, axes in pieces]

    def round_(s, carry):
        vals, orders, outv, outp = carry
        gmin = functools.reduce(jnp.minimum, [
            jnp.min(v, axis=axes, keepdims=True)
            for v, (_, _, axes) in zip(vals, pieces)])  # (rows, 1)
        taken = jnp.zeros((rows, 1), jnp.bool_)
        picked = [jnp.zeros((rows, 1), jnp.int32) for _ in range(npay)]
        new_vals, new_orders = [], []
        for v, o, (_, pays, axes) in zip(vals, orders, pieces):
            first = jnp.min(jnp.where(v == gmin, o, _BIG), axis=axes,
                            keepdims=True)  # earliest live minimum
            mine = ~taken & (first < _BIG)  # this piece supplies the pick
            hit = mine & (o == first)
            picked = [acc + jnp.sum(jnp.where(hit, p, 0), axis=axes,
                                    keepdims=True)
                      for acc, p in zip(picked, pays)]
            taken = taken | mine
            new_vals.append(jnp.where(hit, jnp.inf, v))
            new_orders.append(jnp.where(hit, _BIG, o))
        at = lane == s
        outv = jnp.where(at, gmin, outv)
        outp = [jnp.where(at, p, out) for p, out in zip(picked, outp)]
        return new_vals, new_orders, outv, outp

    outv = jnp.zeros((rows, k), jnp.float32)
    outp = [jnp.zeros((rows, k), jnp.int32) for _ in range(npay)]
    _, _, outv, outp = jax.lax.fori_loop(0, k, round_,
                                         (vals, orders, outv, outp))
    return outv, outp


def topk_kernel(d_ref, ov_ref, oi_ref, *, k: int, block_n: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        ov_ref[...] = jnp.full_like(ov_ref, jnp.inf)
        oi_ref[...] = jnp.zeros_like(oi_ref)

    d = d_ref[...].astype(jnp.float32)  # (B, bN)
    B, bN = d.shape
    accv = ov_ref[...]  # running top-k, ascending: last column is the k-th

    @pl.when(jnp.any(jnp.min(d, axis=1, keepdims=True)
                     < jnp.max(accv, axis=1, keepdims=True)))
    def _merge():
        gidx = j * block_n + jax.lax.broadcasted_iota(jnp.int32, (B, bN), 1)
        outv, (outi,) = smallest_k(
            [(accv, (oi_ref[...],), (1,)), (d, (gidx,), (1,))], k, B)
        ov_ref[...] = outv
        oi_ref[...] = outi


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def topk_smallest_pallas(
    d: jax.Array, k: int, *, block_n: int = 512, interpret: bool = False
) -> tuple[jax.Array, jax.Array]:
    """Row-wise k smallest of d (B, N), ascending. Returns (values, idx).

    k is capped at 128: the merge is a masked-argmin selection network,
    O(k²) compares per tile, which stops being "noise next to the MXU"
    right around the VPU lane width.  Selection at candidate-budget
    scale (T = βn + k in the thousands) belongs to the radius-threshold
    kernel in ``select.py``; ``ops.topk_smallest`` routes k > 128 there
    automatically.
    """
    B, N = d.shape
    assert k <= N, f"k={k} > N={N}"
    if k > 128:
        raise ValueError(
            f"topk_smallest_pallas: k={k} > 128 — the O(k²) selection "
            "network does not scale past the VPU lane width; use "
            "ops.topk_smallest (auto-fallback) or ops.radius_select")
    bN = min(block_n, _ceil_mult(N, 128))
    Bh = _ceil_mult(B, 8)
    Np = _ceil_mult(N, bN)
    dp = jnp.full((Bh, Np), jnp.inf, jnp.float32).at[:B, :N].set(d)
    kern = functools.partial(topk_kernel, k=k, block_n=bN)
    vals, idx = pl.pallas_call(
        kern,
        grid=(Np // bN,),
        in_specs=[pl.BlockSpec((Bh, bN), lambda j: (0, j))],
        out_specs=[
            pl.BlockSpec((Bh, k), lambda j: (0, 0)),
            pl.BlockSpec((Bh, k), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bh, k), jnp.float32),
            jax.ShapeDtypeStruct((Bh, k), jnp.int32),
        ],
        interpret=interpret,
    )(dp)
    return vals[:B], idx[:B]


def _ceil_mult(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m

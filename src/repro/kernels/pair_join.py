"""Pallas TPU kernel: blockwise closest-pair self-join with tile pruning.

The PM-LSH CP engine (paper §6, Algorithms 3-5) bounds pair-verification
volume with a radius filter: once an upper bound ``ub`` on the k-th pair
distance is known, only pairs whose PROJECTED distance is below ``t·ub``
can matter (Lemma 1 turns the projected gap into a tunable-confidence
original-distance bound), and the tree walk exists solely to skip
regions that cannot contain such a pair.  On device the tree is the
wrong shape — but the filter itself is not: over points SORTED by a
1-D projection key, any (row-block i, row-block j) tile of the (n, n)
pair space has the closed-form projected Mindist

    mindist(i, j) = key_lo[j] - key_hi[i]          (j >= i, sorted keys)

a lower bound on every cross pair's 1-D key gap, hence on its m-dim
projected distance.  Algorithm 4's FindLCA-and-descend becomes pure
tile masking:

  grid (band, i)   walks the upper-triangular tile space band-by-band
                   (band b pairs block i with block j = i + b), so the
                   diagonal self-joins run first — the device analogue
                   of Algorithm 4's leaf self-joins seeding ``ub``;
  ub register      a running (1, k) ascending top-k of pair distances
                   lives in VMEM scratch; its last slot IS ub² and
                   tightens monotonically as tiles fold in;
  tile skip        a tile is skipped outright when
                   mindist² > thresh2 · ub² (thresh2 = (γ·t)², the
                   §6.3-calibrated radius filter); skipped tiles never
                   DMA their blocks — data stays in HBM.

Unskipped tiles DMA their two row blocks HBM→VMEM, compute exact
original-space distances (norm trick, MXU cross term), mask the lower
triangle / diagonal / padding, and fold all bN² candidates into the
running top-k via the selection network shared with ``topk.py`` (a
tile with no pair under the current ub² skips the fold).  Work counters
(pair distances computed, tiles pruned) stream through SMEM and are
emitted with the answer, so WorkStats can report ``pairs_verified`` /
``tiles_pruned`` per query.

Exactness: pruning is the ONLY approximation.  Every unskipped pair is
an exact float32 distance, and a pair is skipped only when its 1-D key
gap exceeds γ·t·ub — for the true k-th-closest pair that happens with
probability ≤ 2Φ(-γt) per pair (the key is one 2-stable coordinate, so
the gap is |N(0,1)|·r), ~6e-5 at the default t ≈ 4.  The jnp-free
oracle ``ref.pair_join`` replicates the traversal bit-for-bit
(including counters), so kernel-vs-ref parity is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .topk import smallest_k

__all__ = ["pair_join_kernel", "pair_join_pallas"]

_LIMB = 1 << 30  # pairs_verified limb base: per-tile add < 2³⁰ ⇒ one carry


def pair_join_kernel(key_lo_ref, key_hi_ref, data_ref,
                     ov_ref, oi_ref, oj_ref, os_ref,
                     xi_ref, xj_ref, accv_ref, acci_ref, accj_ref,
                     nver_lo_ref, nver_hi_ref, npru_ref, sem,
                     *, k: int, block_n: int, n: int, n_ti: int,
                     thresh2: float):
    b = pl.program_id(0)  # band: tile pairs (i, i + b)
    i = pl.program_id(1)
    j = i + b
    last = (b == pl.num_programs(0) - 1) & (i == pl.num_programs(1) - 1)

    @pl.when((b == 0) & (i == 0))
    def _init():
        accv_ref[...] = jnp.full_like(accv_ref, jnp.inf)
        acci_ref[...] = jnp.full_like(acci_ref, -1)
        accj_ref[...] = jnp.full_like(accj_ref, -1)
        nver_lo_ref[0] = 0
        nver_hi_ref[0] = 0
        npru_ref[0] = 0

    # -- radius filter as tile masking (Alg. 4's FindLCA, closed form) ----
    in_range = j < n_ti
    jc = jnp.minimum(j, n_ti - 1)  # clamp: out-of-triangle tiles are no-ops
    gap = key_lo_ref[jc] - key_hi_ref[i]  # 1-D projected Mindist of the tile
    ub2 = accv_ref[0, k - 1]  # k-th pair distance² so far (inf until full)
    pruned = in_range & (gap > 0.0) & (gap * gap > thresh2 * ub2)

    @pl.when(pruned)
    def _count_prune():
        npru_ref[0] = npru_ref[0] + 1

    @pl.when(in_range & ~pruned)
    def _join_tile():
        # DMA the two row blocks HBM → VMEM (skipped tiles never pay this)
        cp_i = pltpu.make_async_copy(
            data_ref.at[pl.ds(i * block_n, block_n)], xi_ref, sem.at[0])
        cp_j = pltpu.make_async_copy(
            data_ref.at[pl.ds(j * block_n, block_n)], xj_ref, sem.at[1])
        cp_i.start()
        cp_j.start()
        cp_i.wait()
        cp_j.wait()

        xi = xi_ref[...].astype(jnp.float32)  # (bN, d)
        xj = xj_ref[...].astype(jnp.float32)  # (bN, d)
        ni = jnp.sum(xi * xi, axis=1)  # (bN,)
        nj = jnp.sum(xj * xj, axis=1)  # (bN,)
        cross = jax.lax.dot_general(
            xi, xj, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)  # (bN, bN) on the MXU
        d2 = jnp.maximum(ni[:, None] + nj[None, :] - 2.0 * cross, 0.0)

        # unordered pairs once: global row ids, keep gj > gi and real rows
        gi = (i * block_n
              + jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 0))
        gj = (j * block_n
              + jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 1))
        valid = (gj > gi) & (gi < n) & (gj < n)
        d2 = jnp.where(valid, d2, jnp.inf)
        # pairs_verified accumulates as base-2³⁰ (lo, hi) int32 limbs:
        # a single int32 wraps at n ≈ 65k fully-joined pairs, and the
        # per-tile increment (≤ block² < 2³⁰) can carry at most once
        new_lo = nver_lo_ref[0] + jnp.sum(valid.astype(jnp.int32))
        carry = (new_lo >= _LIMB).astype(jnp.int32)
        nver_lo_ref[0] = new_lo - carry * _LIMB
        nver_hi_ref[0] = nver_hi_ref[0] + carry

        # fold the tile into the running top-k pair heap (ub register):
        # pool = acc ++ tile row-major; a tile with no pair under the
        # k-th leaves the heap as it is
        @pl.when(jnp.min(d2) < ub2)
        def _fold():
            outv, (outi, outj) = smallest_k(
                [(accv_ref[...], (acci_ref[...], accj_ref[...]), (1,)),
                 (d2, (jnp.where(valid, gi, -1), jnp.where(valid, gj, -1)),
                  (0, 1))], k, 1)
            accv_ref[...] = outv
            acci_ref[...] = outi
            accj_ref[...] = outj

    @pl.when(last)
    def _emit():
        ov_ref[...] = accv_ref[...]
        oi_ref[...] = acci_ref[...]
        oj_ref[...] = accj_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        os_ref[...] = jnp.where(
            lane == 0, nver_lo_ref[0],
            jnp.where(lane == 1, npru_ref[0],
                      jnp.where(lane == 2, nver_hi_ref[0], 0)))


def pair_join_pallas(
    x: jax.Array,
    key: jax.Array,
    k: int,
    *,
    thresh2: float,
    block_n: int = 128,
    interpret: bool = False,
):
    """Top-k closest pairs of x's rows by blockwise pruned self-join.

    Args:
      x: (n, d) float32 points, SORTED ascending by ``key`` (the caller
        — ``repro.core.cp_fused`` — sorts and owns the position→id map).
        Resident in HBM; only unpruned tiles are ever copied on chip.
      key: (n,) float32 sort key: one coordinate of the 2-stable
        projection, so |key_i − key_j| lower-bounds the m-dim projected
        distance of the pair (and N(0,1)·dist models it).
      k: pairs to keep, ≤ 128 (the selection-network regime; larger k
        routes through the host oracle — see ``ops.pair_join``).
      thresh2: squared radius-filter multiplier (γ·t)²; a tile whose
        squared key Mindist exceeds ``thresh2 · ub²`` is skipped.
        ``float('inf')`` disables pruning (exhaustive exact join).

    Returns (d² (k,) ascending float32, pi (k,) int32, pj (k,) int32,
    stats (2,) numpy int64 = [pairs_verified, tiles_pruned] — the
    in-kernel count runs as two int32 limbs and is recombined here, so
    the counter matches the ref oracle past the int32 wrap).  pi < pj
    are ROW POSITIONS in the sorted order; slots past the real pair
    count are (+inf, -1, -1).
    """
    import numpy as np

    vals, pi, pj, raw = _pair_join_jit(
        jnp.asarray(x, jnp.float32), jnp.asarray(key, jnp.float32), k,
        thresh2=float(thresh2), block_n=block_n, interpret=interpret)
    raw = np.asarray(raw, np.int64)
    stats = np.asarray([raw[0] + (raw[2] << 30), raw[1]], np.int64)
    return vals, pi, pj, stats


@functools.partial(
    jax.jit, static_argnames=("k", "thresh2", "block_n", "interpret"))
def _pair_join_jit(
    x: jax.Array,
    key: jax.Array,
    k: int,
    *,
    thresh2: float,
    block_n: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    n, d = x.shape
    assert key.shape == (n,), f"key {key.shape} != ({n},)"
    if k > 128:
        raise ValueError(
            f"pair_join_pallas: k={k} > 128; the in-VMEM selection "
            "network is O(k²) — route large-k CP through the host "
            "oracle (ops.pair_join does)")
    bN = max(min(block_n, _ceil_mult(n, 8)), 8)
    n_pad = _ceil_mult(max(n, 1), bN)
    n_ti = n_pad // bN
    xp = jnp.zeros((n_pad, d), jnp.float32).at[:n].set(
        jnp.asarray(x, jnp.float32))
    keyp = jnp.full((n_pad,), jnp.inf, jnp.float32).at[:n].set(
        jnp.asarray(key, jnp.float32))
    blocks = keyp.reshape(n_ti, bN)
    key_lo = jnp.min(blocks, axis=1)  # +inf padding never lowers a real lo
    key_hi = jnp.max(jnp.where(jnp.isfinite(blocks), blocks, -jnp.inf),
                     axis=1)
    kern = functools.partial(pair_join_kernel, k=k, block_n=bN, n=n,
                             n_ti=n_ti, thresh2=float(thresh2))
    vals, pi, pj, stats = pl.pallas_call(
        kern,
        grid=(n_ti, n_ti),  # (band, i); j = i + band, j >= n_ti skipped
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # key_lo (n_ti,)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # key_hi (n_ti,)
            pl.BlockSpec(memory_space=pl.ANY),  # x stays in HBM
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda b, i: (0, 0)),
            pl.BlockSpec((1, k), lambda b, i: (0, 0)),
            pl.BlockSpec((1, k), lambda b, i: (0, 0)),
            pl.BlockSpec((1, 128), lambda b, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
            jax.ShapeDtypeStruct((1, 128), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bN, d), jnp.float32),  # row block i
            pltpu.VMEM((bN, d), jnp.float32),  # row block j
            pltpu.VMEM((1, k), jnp.float32),   # ub register: top-k d²
            pltpu.VMEM((1, k), jnp.int32),     # top-k pair i side
            pltpu.VMEM((1, k), jnp.int32),     # top-k pair j side
            pltpu.SMEM((1,), jnp.int32),       # pairs_verified lo limb
            pltpu.SMEM((1,), jnp.int32),       # pairs_verified hi limb
            pltpu.SMEM((1,), jnp.int32),       # tiles_pruned
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(key_lo, key_hi, xp)
    return vals[0], pi[0], pj[0], stats[0, :3]


def _ceil_mult(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m

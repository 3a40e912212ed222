"""Pure-jnp oracles for every Pallas kernel (the correctness references).

Each function here defines the EXACT semantics the corresponding kernel
in this package must reproduce; tests sweep shapes/dtypes and
`assert_allclose(kernel, ref)`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["pairwise_sq_dist", "project_dist", "topk_smallest", "adc_dist",
           "radius_select", "verify_topk", "pair_join"]

#: float32 matmuls run as float32: on a TPU the default precision takes
#: one bf16 pass, which rounds distances and ids past 2⁸
_HIGHEST = jax.lax.Precision.HIGHEST


def pairwise_sq_dist(q: jax.Array, x: jax.Array) -> jax.Array:
    """Squared Euclidean distances between rows of q (B,d) and x (N,d).

    x may also be per-query candidate rows (B, N, d) — the VERIFY step's
    gathered form — giving out[b, i] = ||q[b] - x[b, i]||².
    Returns (B, N) float32, clamped at 0 (guards fp cancellation).
    """
    q = jnp.asarray(q, jnp.float32)
    x = jnp.asarray(x, jnp.float32)
    if x.ndim == 3:
        # gathered verify rows are already materialized per query, so
        # the direct difference form costs nothing extra and avoids the
        # norm trick's catastrophic cancellation on near-duplicates
        return jnp.sum((x - q[:, None, :]) ** 2, axis=-1)
    qn = jnp.sum(q * q, axis=-1, keepdims=True)  # (B, 1)
    xn = jnp.sum(x * x, axis=-1)  # (N,)
    d2 = qn + xn[None, :] - 2.0 * jnp.dot(q, x.T, precision=_HIGHEST)
    return jnp.maximum(d2, 0.0)


def project_dist(x: jax.Array, a: jax.Array, qp: jax.Array) -> jax.Array:
    """Fused LSH estimate: squared PROJECTED distances ||x@a - qp||².

    x: (N, d) points, a: (d, m) projection, qp: (B, m) projected queries.
    Returns (B, N) float32.  Semantically pairwise_sq_dist(qp, x @ a) —
    the kernel's value is that x@a never round-trips through HBM.
    """
    proj = jnp.dot(jnp.asarray(x, jnp.float32), jnp.asarray(a, jnp.float32),
                   precision=_HIGHEST)  # (N, m)
    return pairwise_sq_dist(qp, proj)


def adc_dist(codes: jax.Array, lut: jax.Array) -> jax.Array:
    """Asymmetric (query-float vs point-code) squared distances via LUTs.

    codes: (N, S) integer codes shared across the batch, or (B, N, S)
           per-query candidate codes; S code slots, values in [0, V).
    lut:   (B, S, V) float32 per-query tables; lut[b, s, v] is the
           squared-distance contribution of code value v in slot s.

    Returns (B, N) float32: out[b, n] = Σ_s lut[b, s, codes[..., n, s]].
    Both codecs in ``repro.quant`` reduce to this form — PQ with one
    slot per sub-codebook, SQ8 with one slot per dimension.
    """
    codes = jnp.asarray(codes, jnp.int32)
    lut = jnp.asarray(lut, jnp.float32)
    if codes.ndim == 2:
        codes = jnp.broadcast_to(codes[None], (lut.shape[0],) + codes.shape)
    # lut (B, 1, S, V) gathered at codes (B, N, S, 1) along V
    g = jnp.take_along_axis(lut[:, None, :, :], codes[..., None], axis=3)
    return jnp.sum(g[..., 0], axis=-1)


def topk_smallest(d: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """k smallest entries per row of d (B, N), ascending.

    Returns (values (B,k) float32, indices (B,k) int32).
    """
    neg, idx = jax.lax.top_k(-jnp.asarray(d, jnp.float32), k)
    return -neg, idx.astype(jnp.int32)


def _bisect_threshold(d: jax.Array, target, iters: int) -> jax.Array:
    """Per-row τ with count(d ≤ τ) ≥ target, shrunk toward the target-th
    smallest value by ``iters`` bisection steps on the [0, max] bracket."""
    lo = jnp.zeros((d.shape[0], 1), jnp.float32)
    hi = jnp.max(d, axis=1, keepdims=True)
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        ge = jnp.sum((d <= mid).astype(jnp.int32), axis=1,
                     keepdims=True) >= target
        hi = jnp.where(ge, mid, hi)
        lo = jnp.where(ge, lo, mid)
    return hi


def radius_select(d: jax.Array, T: int, *, T_pad: int | None = None,
                  sample_stride: int = 8, with_count: bool = False):
    """T smallest per row of d (B, N) by RADIUS, not rank — the jnp
    oracle of the ``select.py`` kernel and the fast non-TPU SELECT path.

    Same contract as :func:`topk_smallest` (ascending values, int32
    indices, lowest-index tie-break), reached without the O(N·T) sort:
    a bisection on a strided sample estimates the T-th smallest value,
    one full counting pass validates the threshold (falling back to
    full-row bisection when the sample misleads), survivors are
    compacted by cumsum + searchsorted GATHER into T_pad ≈ 1.1·T slots,
    and one small top_k over those columns finishes exactly.

    Exact for ANY input: a tie cluster wider than T_pad − T straddling
    the T-th smallest value cannot fit the compaction buffer, so that
    (pathological, never-on-continuous-distances) case is detected from
    the survivor count and rerouted to the plain sort.

    With ``with_count=True`` additionally returns the per-row survivor
    count (B,) int32 — the realized T under the final threshold, the
    ``WorkStats.candidates_selected`` calibration signal.  Paths that
    answer by exact sort (degenerate T_pad ≥ N budget, tie-cluster
    reroute) have no threshold and report the budget T itself.
    """
    d = jnp.asarray(d, jnp.float32)
    B, N = d.shape
    assert 1 <= T <= N, f"T={T} out of range for N={N}"
    if T_pad is None:
        T_pad = T + max(256, T // 8)
    T_pad = min(max(T_pad, T), N)
    if T_pad >= N:  # degenerate budget: nothing to skip, sort it all
        vals, idx = topk_smallest(d, T)
        if with_count:
            return vals, idx, jnp.full((B,), T, jnp.int32)
        return vals, idx

    samp = d[:, ::sample_stride]
    s = samp.shape[1]
    # aim the sample quantile a few σ above T/N so the full-row count
    # lands in [T, T_pad] with overwhelming probability
    margin = 4.0 * float(np.sqrt(T * max(1.0 - T / N, 1e-9))) / N
    t_s = min(int(np.ceil((T / N + margin) * s)) + 2, s)
    hi = _bisect_threshold(samp, t_s, iters=18)
    cnt = jnp.sum((d <= hi).astype(jnp.int32), axis=1, keepdims=True)
    ok = jnp.all((cnt >= T) & (cnt <= T_pad))
    hi = jax.lax.cond(ok, lambda: hi, lambda: _bisect_threshold(d, T, 22))

    def _compact():
        mask = d <= hi
        cs = jnp.cumsum(mask.astype(jnp.int32), axis=1)  # survivor ranks
        ranks = jnp.arange(1, T_pad + 1, dtype=jnp.int32)
        g = jax.vmap(lambda c: jnp.searchsorted(c, ranks, side="left"))(cs)
        valid = g < N
        gc = jnp.minimum(g, N - 1)
        vals = jnp.where(valid, jnp.take_along_axis(d, gc, axis=1), jnp.inf)
        idxs = jnp.where(valid, gc, -1).astype(jnp.int32)
        neg, pos = jax.lax.top_k(-vals, T)
        return -neg, jnp.take_along_axis(idxs, pos, axis=1)

    # even the full-row bisection cannot squeeze a tie cluster at the
    # threshold below T_pad survivors; dropping any of them would lose
    # true top-T members, so that case takes the exact sort instead
    cnt_hi = jnp.sum((d <= hi).astype(jnp.int32), axis=1)
    vals, idx, cnt = jax.lax.cond(
        jnp.any(cnt_hi > T_pad),
        lambda: topk_smallest(d, T) + (jnp.full((B,), T, jnp.int32),),
        lambda: _compact() + (cnt_hi.astype(jnp.int32),))
    if with_count:
        return vals, idx, cnt
    return vals, idx


def pair_join(x, key, k: int, *, thresh2: float, block_n: int = 128
              ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Blockwise pruned closest-pair self-join — oracle of ``pair_join.py``.

    Unlike the other oracles this one is host-side numpy, not jnp: the
    tile-skip decision depends on the RUNNING k-th pair distance (the
    kernel's ub register), i.e. on sequential data-dependent control
    flow, so the reference replicates the kernel's exact band-major
    traversal — same tile order, same norm-trick float32 distances,
    same counters — with a Python tile loop.

    Args / returns: see ``pair_join_pallas``.  x (n, d) sorted by
    ``key`` (n,) ascending; returns (d² (k,) ascending, pi (k,),
    pj (k,), stats (2,) = [pairs_verified, tiles_pruned]) with
    (+inf, -1, -1) padding past the real pair count.  Ties resolve to
    the earliest pair in traversal order, matching the kernel's
    masked-argmin fold.
    """
    x = np.asarray(x, np.float32)
    key = np.asarray(key, np.float32)
    n = x.shape[0]
    bN = max(min(block_n, n + (-n) % 8 if n else 8), 8)
    n_ti = max(-(-n // bN), 1)
    norms = np.sum(x * x, axis=1)
    thresh2 = float(thresh2)

    vals = np.empty((0,), np.float32)  # survivors in traversal order
    pis = np.empty((0,), np.int64)
    pjs = np.empty((0,), np.int64)
    ub2 = np.inf
    pairs_verified = 0
    tiles_pruned = 0
    for b in range(n_ti):
        for i in range(n_ti - b):
            j = i + b
            si, sj = i * bN, j * bN
            ei, ej = min(si + bN, n), min(sj + bN, n)
            gap = float(key[sj] - key[ei - 1])  # sorted: block-j lo − block-i hi
            if gap > 0.0 and gap * gap > thresh2 * ub2:
                tiles_pruned += 1
                continue
            xi, xj = x[si:ei], x[sj:ej]
            d2 = np.maximum(
                norms[si:ei, None] + norms[None, sj:ej]
                - 2.0 * (xi @ xj.T).astype(np.float32), 0.0)
            gi = si + np.arange(ei - si)[:, None]
            gj = sj + np.arange(ej - sj)[None, :]
            valid = gj > gi
            pairs_verified += int(valid.sum())
            sel = valid.ravel()  # row-major == the kernel's flatten order
            vals = np.concatenate([vals, d2.ravel()[sel]])
            pis = np.concatenate([pis, np.broadcast_to(gi, d2.shape).ravel()[sel]])
            pjs = np.concatenate([pjs, np.broadcast_to(gj, d2.shape).ravel()[sel]])
            if vals.size > 4096 + k:  # keep the running pool bounded
                keep = np.argsort(vals, kind="stable")[: 2 * k]
                keep.sort()  # preserve traversal order among the kept
                vals, pis, pjs = vals[keep], pis[keep], pjs[keep]
            if vals.size >= k:
                ub2 = float(np.partition(vals, k - 1)[k - 1])
    order = np.argsort(vals, kind="stable")[:k]
    out_v = np.full((k,), np.inf, np.float32)
    out_i = np.full((k,), -1, np.int32)
    out_j = np.full((k,), -1, np.int32)
    out_v[: order.size] = vals[order]
    out_i[: order.size] = pis[order]
    out_j[: order.size] = pjs[order]
    stats = np.asarray([pairs_verified, tiles_pruned], np.int64)
    return out_v, out_i, out_j, stats


def verify_topk(data: jax.Array, q: jax.Array, cand: jax.Array, k: int
                ) -> tuple[jax.Array, jax.Array]:
    """Exact-verify candidates and answer — oracle of ``verify.py``.

    data (n, d) × q (B, d) × cand (B, Tc) int32 ids (-1 = padding) →
    (d² (B, k) ascending, ids (B, k)); slots beyond a row's real
    candidates are (+inf, -1).  The oracle materializes the gathered
    (B, Tc, d) candidate tensor the kernel exists to avoid.
    """
    cand = jnp.asarray(cand, jnp.int32)
    cpts = jnp.asarray(data, jnp.float32)[jnp.maximum(cand, 0)]  # (B, Tc, d)
    d2 = pairwise_sq_dist(q, cpts)  # (B, Tc)
    d2 = jnp.where(cand < 0, jnp.inf, d2)
    if k > cand.shape[1]:  # short candidate rows: keep the (B, k) contract
        pad = k - cand.shape[1]
        d2 = jnp.pad(d2, ((0, 0), (0, pad)), constant_values=jnp.inf)
        cand = jnp.pad(cand, ((0, 0), (0, pad)), constant_values=-1)
    neg, sel = jax.lax.top_k(-d2, k)
    idx = jnp.take_along_axis(cand, sel, axis=1)
    return -neg, jnp.where(jnp.isinf(-neg), -1, idx).astype(jnp.int32)

"""The quantized three-tier query pipeline (DESIGN.md §8).

Extends the flat pipeline's estimate → select → verify with an ADC
rerank tier between select and verify:

    1. estimate:  projected distances ||x@A - q'||²       (m-dim, χ²(m))
    2. select:    top-(βn+k) projected-nearest             candidates C
    3. rerank:    ADC distances on codes over C → top-R    (d-dim, quantized)
    4. verify:    exact distances on the R float vectors   (or skip when
                  the raw vectors were dropped: answer straight from ADC)

Tier 3 reads S bytes/point instead of 4d, so the candidate budget T
stays cheap to examine and only R ≪ T rows ever touch full-precision
storage.  With ``store_raw=False`` tier 4 disappears entirely and the
index holds no float vectors at all — returned distances are then the
(slightly biased) ADC estimates.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.flat_index import FlatIndex

from .codec import Codec

__all__ = ["quant_ann_query", "quant_cp_search"]


@partial(jax.jit,
         static_argnames=("k", "T", "R", "store_raw", "force", "fused",
                          "with_count"))
def quant_ann_query(
    index: FlatIndex,
    codec: Codec,
    codes: jax.Array,
    q: jax.Array,
    *,
    k: int,
    T: int,
    R: int,
    store_raw: bool = True,
    force: str | None = None,
    fused: bool = False,
    with_count: bool = False,
):
    """(c,k)-ANN over quantized storage.

    Args:
      index: the flat index (projection family + projected points; its
        ``data`` may be empty when ``store_raw=False``).
      codec: the trained codec (pytree — traces through jit).
      codes: (n, S) uint8 codes for every indexed point.
      q: (B, d) query batch.
      k / T / R: answer size, candidate budget (βn + k), rerank budget.
      store_raw: verify the final R candidates against float vectors
        (exact distances) vs. answer straight from ADC estimates.
      fused: use the fused pipeline (DESIGN.md §9): radius-threshold
        SELECT for both the T-budget and the R-rerank cut, and the
        gather-free VERIFY kernel for the exact tier — the ADC rerank
        slots in unchanged as the verify stage on codes.  Identical
        answers on ties-free data.
      with_count: also return the T-select's per-query survivor counts
        (B,) int32 (realized T → ``WorkStats.candidates_selected``);
        the unfused rank cut has no radius and reports the budget T.

    Returns (indices (B, k) int32, distances (B, k) float32) plus the
    counts when ``with_count``.
    """
    from repro.kernels import ops as kops

    assert k <= R <= T, f"need k <= R <= T, got k={k} R={R} T={T}"
    q = jnp.asarray(q, jnp.float32)
    if q.ndim == 1:
        q = q[None]
    qp = index.family.project(q)  # (B, m)

    # 1-2. estimate + select (identical to the float pipeline)
    d2p = kops.pairwise_sq_dist(qp, index.projected, force=force)  # (B, n)
    if fused:
        from repro.core.fused import select_seed

        m = index.params.m if index.params is not None else index.m
        tau0 = select_seed(d2p, T, m)
        _, cand, cnt = kops.radius_select(d2p, T, tau0=tau0, force=force,
                                          with_count=True)
    else:
        _, cand = jax.lax.top_k(-d2p, T)  # (B, T)
        cnt = jnp.full((q.shape[0],), T, jnp.int32)

    # 3. rerank: ADC on the candidates' codes, keep the R best.
    # gather BEFORE widening: only B·T code rows are ever touched at
    # int32 (adc_dist casts internally); the n-row store stays uint8
    ccodes = jnp.asarray(codes)[cand]  # (B, T, S)
    direct = getattr(codec, "adc_direct", None)
    if direct is not None:  # affine codecs skip the LUT contraction
        d2a = direct(q, ccodes)  # (B, T)
    else:
        lut = codec.lookup_tables(q)  # (B, S, V)
        d2a = kops.adc_dist(ccodes, lut, force=force)  # (B, T)
    if fused and R > 128:
        adcR, selR = kops.radius_select(d2a, R, force=force)
        negR = -adcR
    else:
        negR, selR = jax.lax.top_k(-d2a, R)
    rcand = jnp.take_along_axis(cand, selR, axis=1)  # (B, R)

    if not store_raw:
        # codes-only: the R-selection is already ascending in ADC distance
        idx = rcand[:, :k]
        dd = jnp.sqrt(jnp.maximum(-negR[:, :k], 0.0))
        out = idx.astype(jnp.int32), dd
    elif fused:
        # 4. verify: exact distances on the R survivors, through the
        # kernel dispatch policy (force= now reaches the verify tier too)
        d2, idx = kops.verify_topk(index.data, q, rcand, k, force=force)
        out = idx.astype(jnp.int32), jnp.sqrt(jnp.maximum(d2, 0.0))
    else:
        cpts = index.data[rcand]  # (B, R, d)
        d2 = kops.pairwise_sq_dist(q, cpts, force=force)  # (B, R)
        negk, sel = jax.lax.top_k(-d2, k)
        idx = jnp.take_along_axis(rcand, sel, axis=1)
        out = idx.astype(jnp.int32), jnp.sqrt(jnp.maximum(-negk, 0.0))
    return out + (cnt,) if with_count else out


def quant_cp_search(
    codec: Codec,
    codes,
    key,
    k: int,
    *,
    raw=None,
    R: int | None = None,
    c: float = 4.0,
    m: int = 15,
    gamma: float = 1.0,
    force: str | None = None,
    recon=None,
):
    """(c,k)-ACP over quantized storage (DESIGN.md §10).

    The candidate join runs on code-estimated distances: points are
    reconstructed from their codes (the decode that ADC sums per slot,
    taken whole) and the fused pair-join engine generates the top-R
    estimated pairs under the same γ·t·ub radius filter as the float
    path.  With ``raw`` available the R survivors are then exact-
    verified — one pair-distance pass over 2R rows — so returned
    distances are exact; codes-only indexes answer straight from the
    estimates.

    Args:
      codec / codes: the trained codec and the (n, S) point codes.
      key: (n,) 1-D projection sort key (the flat index's first
        projected coordinate, so CP shares the build-time family).
      k: pairs to return.
      raw: optional (n, d) float32 rows for the exact verify tier
        (None when ``store_raw=False`` dropped them).
      R: estimated-pair rerank budget, default max(4k, n/4, 64) capped
        at 1024 — like the quant ANN rerank tier it must scale with
        the pool (code-estimation noise on pair ORDER grows with n),
        so a fixed budget would starve recall at scale; survivors are
        exact-verified, so over-budgeting only costs 2R row reads.
        Note R > 128 puts the estimated join past the pair-join
        kernel's answer-network cap, so ``ops.pair_join`` serves it
        from the (equally pruned) host band-major oracle regardless of
        ``force`` — capping R per dispatch mode instead would fork
        recall across modes.
      recon: optional precomputed ``codec.decode(codes)`` — callers
        with immutable codes (the flat backend) memoize it across
        queries instead of re-decoding per call.

    Returns (pairs (k', 2) int32 ascending by distance, distances (k',)
    float32, pairs_estimated int, pairs_verified int, tiles_pruned int).
    """
    import numpy as np

    from repro.core.cp_fused import cp_fused_search

    if recon is None:
        recon = codec.decode(codes)
    recon = np.asarray(recon, dtype=np.float32)
    n = recon.shape[0]
    R = min(max(4 * k, n // 4, 64), 1024) if R is None else int(R)
    R = min(max(R, k), max(n * (n - 1) // 2, 1))
    est = cp_fused_search(recon, R, m=m, c=c, gamma=gamma, force=force,
                          key=key)
    if raw is None or est.pairs.shape[0] == 0:
        kk = min(k, est.pairs.shape[0])
        return (est.pairs[:kk], est.distances[:kk], est.pairs_verified,
                0, est.tiles_pruned)
    raw = np.asarray(raw, dtype=np.float32)
    a, b = est.pairs[:, 0], est.pairs[:, 1]
    d = np.linalg.norm(raw[a] - raw[b], axis=-1).astype(np.float32)
    order = np.argsort(d, kind="stable")[:k]
    return (est.pairs[order], d[order], est.pairs_verified,
            int(est.pairs.shape[0]), est.tiles_pruned)

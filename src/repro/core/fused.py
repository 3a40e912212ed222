"""The fused estimate→select→verify query pipeline (DESIGN.md §9).

One entry point, ``fused_ann_query``, that every device backend routes
through — flat float32, flat-pq (ADC rerank slots in as a verify tier
on codes), and the streaming index's sealed segments (which are flat
backends under the hood).  Against the unfused pipeline in
``flat_index.ann_query`` it changes two stages:

  SELECT   `lax.top_k` over (B, n) with T = βn + k — O(n·T) sort work —
           becomes radius-threshold selection (`kernels/select.py`):
           the Eq. 9 confidence interval turns rank T into a radius,
           found by a few O(n) branch-free counting passes seeded from
           the Lemma-2 distance estimate, with the paper's r·c^i
           doubling schedule as the refinement ladder.
  VERIFY   the (B, T, d) candidate gather — an HBM write + read-back
           that dominates query traffic at T ≈ 0.1n — becomes the
           gather-free kernel (`kernels/verify.py`): candidate rows are
           DMA'd HBM→VMEM tile-by-tile and reduced in place, so HBM
           sees exactly one read per candidate row.

Both stages keep exact parity with the unfused path on ties-free data
(see the kernel docstrings for the tie-cluster caveat), so backends can
flip between pipelines via a config option with identical answers.

The threshold seed: Lemma 2 says the projected squared distance of a
point at distance r concentrates at m·r²; Eq. 9's interval bounds it by
χ² quantiles.  We therefore seed τ₀ = χ²_ppf(T/n; m) · (mean d'²/m) —
the T/n quantile of the χ²(m) model at the row's Lemma-2 scale — and
let the r·c^i ladder absorb the model error.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .estimator import chi2_ppf
from .flat_index import FlatIndex

__all__ = ["fused_ann_query", "select_seed"]


def select_seed(d2p: jax.Array, T: int, m: int | None) -> jax.Array:
    """Per-row Eq. 9 / Lemma 2 seed for the radius-select ladder.

    d2p: (B, n) projected squared distances; T: candidate budget;
    m: projected dimensionality (None → plain sample-mean seed).
    Returns (B,) float32 seeds in squared projected units.
    """
    from repro.kernels import ops as kops

    n = d2p.shape[1]
    if m is None or m < 1:
        return kops.default_select_seed(d2p, T)
    samp = d2p[:, :: max(n // 4096, 1)]
    scale = jnp.mean(samp, axis=1) / float(m)  # Lemma-2 r̄² estimate
    q = min(max(T / n, 1e-6), 1.0 - 1e-9)
    return scale * float(chi2_ppf(q, m))


@partial(jax.jit, static_argnames=("k", "T", "force", "with_count"))
def fused_ann_query(
    index: FlatIndex,
    q: jax.Array,
    *,
    k: int,
    T: int,
    force: str | None = None,
    with_count: bool = False,
):
    """(c,k)-ANN through the fused pipeline.

    Same contract as ``flat_index.ann_query`` — (indices (B, k) int32,
    distances (B, k) float32) — and identical output on ties-free data.

    Args:
      q: (B, d) query batch.
      k: results per query (≤ 128; the answer-size regime).
      T: candidate budget (βn + k) from ``candidate_budget``.
      force: kernel dispatch override ("pallas"|"interpret"|"ref"|None).
      with_count: also return the select stage's per-query survivor
        counts (B,) int32 — realized T, the signal behind
        ``WorkStats.candidates_selected``.  A static arg (the pipeline
        is jit'd, so the extra output must be part of the return).
    """
    from repro.kernels import ops as kops

    q = jnp.asarray(q, jnp.float32)
    if q.ndim == 1:
        q = q[None]

    # 1. estimate: projected squared distances (Lemma 2)
    with jax.named_scope("estimate"):
        qp = index.family.project(q)  # (B, m)
        d2p = kops.pairwise_sq_dist(qp, index.projected, force=force)

    # 2. select: radius-threshold selection seeded from Eq. 9
    with jax.named_scope("select"):
        with jax.named_scope("seed"):
            m = index.params.m if index.params is not None else index.m
            tau0 = select_seed(d2p, T, m)
        _, cand, cnt = kops.radius_select(d2p, T, tau0=tau0, force=force,
                                          with_count=True)  # (B, T), (B,)

    # 3-4. verify + answer: gather-free exact distances, streaming top-k
    with jax.named_scope("verify"):
        d2, idx = kops.verify_topk(index.data, q, cand, k, force=force)
        out = idx.astype(jnp.int32), jnp.sqrt(jnp.maximum(d2, 0.0))
    return out + (cnt,) if with_count else out

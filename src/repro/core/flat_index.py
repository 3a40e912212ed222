"""TPU-native "flat" PM-LSH backend (DESIGN.md §3).

The paper's Algorithm 2 terminates once the range query has collected
``βn + k`` candidates ordered by projected distance — i.e. its candidate
set equals the ``βn + k`` projected-nearest points (up to radius-step
boundary effects).  On TPU, computing ALL n projected distances is a
single fused MXU pass (n·m MACs), so the tree's probing-order machinery
is replaced by a dense estimate → top-T select → verify pipeline:

    1. estimate:  d'_i = ||x_i @ A - q'||        (fused Pallas kernel)
    2. select:    top-(βn+k) smallest d'_i        (the candidate set C)
    3. verify:    exact ||x_i - q|| on C          (Pallas pairwise kernel)
    4. answer:    top-k smallest exact distances

Accuracy-wise this is the same estimator + candidate budget as the
paper (Lemmas 1-4 untouched); only the probing mechanism differs.  The
host PM-tree path (``ann.py``) remains the faithful reproduction.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .hashing import ProjectionFamily
from .estimator import PMLSHParams, solve_parameters

__all__ = ["FlatIndex", "build_flat_index", "ann_search", "candidate_budget"]


@dataclasses.dataclass(frozen=True)
class FlatIndex:
    """Device-resident flat PM-LSH index.

    data:      (n, d) original points.
    projected: (n, m) = data @ family.a  (precomputed).
    family:    the projection family (holds A).
    params:    Eq. 10 solution cached at build time so queries never
               re-run the χ² quantile solver (static pytree metadata).
    """

    data: jax.Array
    projected: jax.Array
    family: ProjectionFamily
    params: PMLSHParams | None = None

    @property
    def n(self) -> int:
        return self.data.shape[0]

    @property
    def d(self) -> int:
        return self.data.shape[1]

    @property
    def m(self) -> int:
        return self.projected.shape[1]


jax.tree_util.register_dataclass(
    FlatIndex, data_fields=["data", "projected", "family"],
    meta_fields=["params"],
)
jax.tree_util.register_dataclass(ProjectionFamily, data_fields=["a"], meta_fields=[])


def build_flat_index(
    data: np.ndarray | jax.Array, m: int = 15, seed: int = 0, c: float = 1.5
) -> FlatIndex:
    data = jnp.asarray(data, jnp.float32)
    family = ProjectionFamily.create(data.shape[1], m, seed=seed)
    return FlatIndex(data=data, projected=family.project(data), family=family,
                     params=solve_parameters(c, m=m))


def candidate_budget(params: PMLSHParams, n: int, k: int) -> int:
    """T = βn + k, clamped to [k, n]."""
    return int(min(max(int(np.ceil(params.beta * n)) + k, k), n))


@jax.jit
def answer_distances(data: jax.Array, ids: jax.Array,
                     q: jax.Array) -> jax.Array:
    """Canonical answer distances: ||q_b − data[ids[b, j]]||, +inf where
    id < 0.

    Backends that promise bit-identical answers to each other (flat and
    the sharded-flat family, DESIGN.md §15) route their final distances
    through this ONE function after id selection.  The verify d² that
    RANKS candidates is computed inside each pipeline's own jit program,
    and XLA is free to reassociate a fused reduce differently per
    program — 1-ulp drift that would break cross-backend distance
    equality even when the ids agree.  Recomputing the k answers here,
    in a single standalone-compiled program both backends share, pins
    the returned floats to one reduction order at O(B·k·d) cost — noise
    next to the O(B·T·d) verify.
    """
    with jax.named_scope("answer"):
        rows = data[jnp.maximum(ids, 0)]  # (B, k, d)
        d2 = jnp.sum((rows - q[:, None, :]) ** 2, axis=-1)
        d2 = jnp.where(ids < 0, jnp.inf, d2)
        return jnp.sqrt(jnp.maximum(d2, 0.0)).astype(jnp.float32)


@partial(jax.jit, static_argnames=("k", "T", "use_kernels", "fused", "force",
                                   "with_count"))
def ann_query(
    index: FlatIndex,
    q: jax.Array,
    *,
    k: int,
    T: int,
    use_kernels: bool = True,
    fused: bool = False,
    force: str | None = None,
    with_count: bool = False,
):
    """(c,k)-ANN for a batch of queries.

    Args:
      q: (B, d) query batch.
      k: results per query.
      T: candidate budget (βn + k) from `candidate_budget`.
      use_kernels: route distance work through the kernel dispatch
        policy (``repro.kernels.ops``) vs. forcing the jnp oracles.
      fused: use the fused estimate→select→verify pipeline
        (``repro.core.fused``): radius-threshold selection instead of
        the O(n·T) top_k, gather-free verification instead of the
        (B, T, d) candidate materialization.  Identical answers on
        ties-free data.
      force: explicit kernel dispatch mode ("pallas" | "interpret" |
        "ref"); None derives it from ``use_kernels``.
      with_count: also return the select stage's per-query survivor
        counts (B,) int32 — realized T on the fused radius path; the
        rank cut here selects exactly T, so the unfused path reports
        the budget.

    Returns:
      (indices (B, k) int32 into index.data, distances (B, k) float32),
      plus the counts when ``with_count``.
    """
    from repro.core.fused import fused_ann_query
    from repro.kernels import ops as kops

    if force is None:
        force = None if use_kernels else "ref"
    if fused:
        return fused_ann_query(index, q, k=k, T=T, force=force,
                               with_count=with_count)

    q = jnp.asarray(q, jnp.float32)
    if q.ndim == 1:
        q = q[None]

    # 1-2. estimate + select: projected distances, top-T smallest
    with jax.named_scope("estimate"):
        qp = index.family.project(q)  # (B, m)
        d2p = kops.pairwise_sq_dist(qp, index.projected, force=force)
    with jax.named_scope("select"):
        _, cand = jax.lax.top_k(-d2p, T)  # (B, T) candidate ids

    # 3. verify: exact distances on the candidate set, through the same
    # kernel dispatch policy as the estimate (vmapped per-query rows),
    # then 4. the answer's top-k
    with jax.named_scope("verify"):
        cpts = index.data[cand]  # (B, T, d)
        d2 = kops.pairwise_sq_dist(q, cpts, force=force)  # (B, T)
        negk, sel = jax.lax.top_k(-d2, k)
        idx = jnp.take_along_axis(cand, sel, axis=1)
        out = idx.astype(jnp.int32), jnp.sqrt(jnp.maximum(-negk, 0.0))
    if with_count:
        return out + (jnp.full((q.shape[0],), T, jnp.int32),)
    return out


def ann_search(
    index: FlatIndex,
    q: jax.Array,
    k: int,
    c: float = 1.5,
    params: PMLSHParams | None = None,
    use_kernels: bool = True,
    fused: bool = False,
):
    """Convenience wrapper: pick T from the build-time parameter cache
    (re-solving Eq. 10 only when queried at a different ratio c)."""
    if params is None:
        if index.params is not None and index.params.c == c:
            params = index.params
        else:
            params = solve_parameters(c, m=index.m)
    T = candidate_budget(params, index.n, k)
    return ann_query(index, q, k=k, T=T, use_kernels=use_kernels, fused=fused)

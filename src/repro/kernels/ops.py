"""Public jit'd wrappers over the Pallas kernels.

Dispatch policy: Pallas kernels are TPU programs; on the CPU backend of
this container they execute through `interpret=True` (kernel body run
op-by-op — bit-accurate, slow).  Each wrapper therefore routes:

    TPU backend          → compiled Pallas kernel
    elsewhere, validate  → interpret-mode Pallas (tests force this)
    elsewhere, fast path → the jnp oracle from ref.py (identical math)

`force` overrides: "pallas" | "interpret" | "ref" | None (auto).

Observability: the main-path kernels carry stable names
(``pallas_call(name=...)``), which the profiler shows as their op
names; the stages around them are ``jax.named_scope``s of the calling
program (DESIGN.md §12).  Nothing here times or syncs anything.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .adc import adc_dist_pallas
from .pair_join import pair_join_pallas
from .pairwise_dist import pairwise_sq_dist_pallas
from .project_dist import project_dist_pallas
from .select import radius_select_pallas
from .topk import topk_smallest_pallas
from .verify import verify_topk_pallas

__all__ = ["pairwise_sq_dist", "project_dist", "topk_smallest", "adc_dist",
           "radius_select", "verify_topk", "pair_join"]


def _mode(force: str | None) -> str:
    if force is not None:
        return force
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def pairwise_sq_dist(q: jax.Array, x: jax.Array, *, force: str | None = None,
                     **block_kw) -> jax.Array:
    """(B,d) × (N,d) → (B,N) squared Euclidean distances (f32).

    x may be per-query candidate rows (B, N, d) — the gathered VERIFY
    form — in which case the kernel is vmapped over the batch.
    """
    mode = _mode(force)
    if mode == "ref":
        return ref.pairwise_sq_dist(q, x)
    interpret = mode == "interpret"
    if x.ndim == 3:
        return jax.vmap(
            lambda qb, xb: pairwise_sq_dist_pallas(
                qb[None], xb, interpret=interpret, **block_kw)[0]
        )(q, x)
    return pairwise_sq_dist_pallas(q, x, interpret=interpret, **block_kw)


def project_dist(x: jax.Array, a: jax.Array, qp: jax.Array, *,
                 force: str | None = None, **block_kw) -> jax.Array:
    """Fused (x@a) projected distances to qp: (N,d),(d,m),(B,m) → (B,N)."""
    mode = _mode(force)
    if mode == "ref":
        return ref.project_dist(x, a, qp)
    return project_dist_pallas(x, a, qp, interpret=(mode == "interpret"), **block_kw)


def adc_dist(codes: jax.Array, lut: jax.Array, *, force: str | None = None,
             **block_kw) -> jax.Array:
    """Asymmetric distances: codes (N,S) or (B,N,S) × LUTs (B,S,V) → (B,N).

    Per-query candidate codes (B, N, S) vmap the shared-codes kernel
    over the batch; the ref oracle handles both shapes directly.
    """
    mode = _mode(force)
    if mode == "ref":
        return ref.adc_dist(codes, lut)
    interpret = mode == "interpret"
    if codes.ndim == 3:
        return jax.vmap(
            lambda c, l: adc_dist_pallas(c, l[None], interpret=interpret,
                                         **block_kw)[0]
        )(codes, lut)
    return adc_dist_pallas(codes, lut, interpret=interpret, **block_kw)


def topk_smallest(d: jax.Array, k: int, *, force: str | None = None,
                  **block_kw) -> tuple[jax.Array, jax.Array]:
    """Row-wise k smallest (values, indices), ascending.

    The streaming selection-network kernel is O(k²) and capped at
    k ≤ 128; larger k transparently routes through the radius-threshold
    selection path (``radius_select``), which has no such cap.
    """
    mode = _mode(force)
    if mode == "ref":
        return ref.topk_smallest(d, k)
    if k > 128:
        return radius_select(d, k, force=force, **block_kw)
    return topk_smallest_pallas(d, k, interpret=(mode == "interpret"), **block_kw)


def default_select_seed(d: jax.Array, T: int, *, stride: int = 8) -> jax.Array:
    """Per-row seed for radius selection from a strided sample of d:
    the sample mean scaled by the target fraction T/N — within the
    rung ladder's reach of the T-th smallest for any unimodal row."""
    samp = jax.lax.slice(d, (0, 0), d.shape, (1, stride))  # not a gather
    N = d.shape[1]
    return jnp.mean(samp, axis=1) * jnp.float32(max(T / N, 1e-3))


def radius_select(d: jax.Array, T: int, *, tau0: jax.Array | None = None,
                  T_pad: int | None = None, force: str | None = None,
                  with_count: bool = False, **block_kw):
    """Row-wise T smallest (values, indices) by radius thresholding.

    Same contract as :func:`topk_smallest` (ascending, lowest-index
    tie-break) for any T, but O(n) threshold passes + one O(T_pad·T)
    finishing sort instead of an O(n·T) selection — the SELECT step for
    candidate budgets in the thousands.  The kernel hands back each
    row's survivors already joined into a dense (B, T_pad) buffer in
    index order, so the finish is one ``top_k`` and one gather of its
    ids (scope ``finish``).  ``tau0`` (B,) optionally seeds
    the threshold ladder (e.g. the Eq. 9 estimate from
    ``repro.core.fused``); default is a sample-mean seed.

    Exactness matches top_k unconditionally: a tie cluster wider than
    the survivor buffer (see select.py) is detected from the kernel's
    per-row survivor counts and rerouted to the exact sort, so the
    radius path can only ever be a perf win, never a recall loss.
    Degenerate budgets (T_pad ≥ N) fall back to the sort directly.

    ``with_count=True`` appends the per-row survivor count (B,) int32 —
    the realized T under the final threshold, surfaced to callers as
    ``WorkStats.candidates_selected``.  Sort paths (degenerate budget,
    tie-cluster reroute) have no threshold and report the budget T.
    """
    mode = _mode(force)
    B, N = d.shape
    if T_pad is None:
        T_pad = T + max(256, T // 8)
    T_pad = min(max(T_pad, T), N)
    if mode == "ref":
        return ref.radius_select(d, T, T_pad=T_pad, with_count=with_count)
    if T_pad >= N:  # nothing to skip — the plain sort is cheaper
        vals, idx = ref.topk_smallest(d, T)
        if with_count:
            return vals, idx, jnp.full((B,), T, jnp.int32)
        return vals, idx
    if tau0 is None:
        tau0 = default_select_seed(d, T)
    vals_p, idx_p, cnt = radius_select_pallas(
        d, tau0, T, T_pad=T_pad, interpret=(mode == "interpret"), **block_kw)

    def _trim():
        neg, pos = jax.lax.top_k(-vals_p, T)
        return (-neg, jnp.take_along_axis(idx_p, pos, axis=1),
                cnt.astype(jnp.int32))

    # buffer overflow (pathological tie cluster at the threshold) drops
    # survivors in index order — arbitrarily wrong ones — so reroute to
    # the exact sort rather than return a degraded candidate set
    with jax.named_scope("finish"):
        vals, idx, cnt_out = jax.lax.cond(
            jnp.any(cnt > T_pad),
            lambda: ref.topk_smallest(d, T) + (jnp.full((B,), T, jnp.int32),),
            _trim)
    if with_count:
        return vals, idx, cnt_out
    return vals, idx


def pair_join(x, key, k: int, *, thresh2: float, force: str | None = None,
              block_n: int = 128):
    """Top-k closest pairs of x's rows by pruned blockwise self-join.

    x (n, d) sorted ascending by key (n,) → (d² (k,) ascending, pi (k,),
    pj (k,), stats (2,) = [pairs_verified, tiles_pruned]); pi < pj are
    row POSITIONS in the sorted order, (-1, +inf) past the real pair
    count.  ``thresh2`` = (γ·t)² is Algorithm 4's radius filter as tile
    masking; ``float('inf')`` disables pruning (exhaustive exact join).

    k > 128 is outside the in-VMEM selection network's regime and
    routes through the host oracle on every dispatch mode.
    """
    if not pair_join_on_device(k, force):
        return ref.pair_join(x, key, k, thresh2=thresh2, block_n=block_n)
    return pair_join_pallas(x, key, k, thresh2=float(thresh2),
                            block_n=block_n,
                            interpret=(_mode(force) == "interpret"))


def pair_join_on_device(k: int, force: str | None = None) -> bool:
    """Whether :func:`pair_join` runs the join kernel, which reads its
    rows from the device (the host oracle takes k > 128 and "ref")."""
    return _mode(force) != "ref" and k <= 128


def verify_topk(data: jax.Array, q: jax.Array, cand: jax.Array, k: int, *,
                force: str | None = None, **block_kw
                ) -> tuple[jax.Array, jax.Array]:
    """Fused VERIFY: exact distances on candidate ids + top-k answer.

    data (n,d) × q (B,d) × cand (B,Tc) → (d² (B,k) ascending, ids (B,k)).
    The kernel gathers candidate rows HBM→VMEM tile-by-tile and never
    materializes the (B,Tc,d) tensor; the ref oracle (and the k > 128
    regime, where the in-VMEM selection network does not apply) does.
    """
    mode = _mode(force)
    if mode == "ref" or k > 128:
        return ref.verify_topk(data, q, cand, k)
    return verify_topk_pallas(data, q, cand, k,
                              interpret=(mode == "interpret"), **block_kw)

"""Pallas TPU kernel: fused LSH projection + projected-space distance.

The PM-LSH ESTIMATE step (Lemma 2) needs ||x_i@A − q'||² for every point
x_i.  Done naively this materializes the (N, m) projection in HBM and
reads it back.  The fusion keeps each X tile's projection in a VMEM
scratch accumulator across the d-contraction and emits the (B, N)
projected distances directly — the projection never touches HBM.

Arithmetic-intensity note: for d = 4096, m = 16, the naive two-pass
moves N·(d + 2m + 1) floats; the fused kernel moves N·(d + 1).  On an
819 GB/s part that is the whole ball game for the estimate step, which
is memory-bound (2·d·m MACs per point ≪ the MXU's appetite).

Grid = (N/bN, d/bD), d innermost; scratch acc (bN, m̂) persists across
the d loop (m̂ = m padded to a 128 lane).  On the last d step the tile's
projection meets the (B, m̂) projected queries in a tiny MXU matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["project_dist_kernel", "project_dist_pallas"]


def project_dist_kernel(x_ref, a_ref, qp_ref, o_ref, acc_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)  # (bN, bD)
    a = a_ref[...].astype(jnp.float32)  # (bD, m̂)
    acc_ref[...] += jax.lax.dot_general(
        x, a, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _emit():
        proj = acc_ref[...]  # (bN, m̂)
        qp = qp_ref[...].astype(jnp.float32)  # (B̂, m̂)
        pn = jnp.sum(proj * proj, axis=1)  # (bN,)
        qn = jnp.sum(qp * qp, axis=1, keepdims=True)  # (B̂, 1)
        cross = jax.lax.dot_general(
            qp, proj, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)  # (B̂, bN)
        o_ref[...] = jnp.maximum(qn + pn[None, :] - 2.0 * cross, 0.0)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_d", "interpret")
)
def project_dist_pallas(
    x: jax.Array,
    a: jax.Array,
    qp: jax.Array,
    *,
    block_n: int = 512,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """x (N,d), a (d,m), qp (B,m) → (B, N) squared projected distances.

    m is padded to 128 lanes; qp rows padded to a sublane multiple. Both
    pads are zeros, which leave the distances exact (extra coordinates
    contribute 0 to both projections and norms).
    """
    N, d = x.shape
    d2, m = a.shape
    B, m2 = qp.shape
    assert d == d2 and m == m2
    bN = min(block_n, _ceil_mult(N, 128))
    bD = min(block_d, _ceil_mult(d, 128))
    mh = _ceil_mult(m, 128)
    Bh = _ceil_mult(B, 8)
    Np, Dp = _ceil_mult(N, bN), _ceil_mult(d, bD)
    xp = jnp.zeros((Np, Dp), x.dtype).at[:N, :d].set(x)
    ap = jnp.zeros((Dp, mh), a.dtype).at[:d, :m].set(a)
    qpp = jnp.zeros((Bh, mh), qp.dtype).at[:B, :m].set(qp)
    grid = (Np // bN, Dp // bD)
    out = pl.pallas_call(
        project_dist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bN, bD), lambda j, k: (j, k)),
            pl.BlockSpec((bD, mh), lambda j, k: (k, 0)),
            pl.BlockSpec((Bh, mh), lambda j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((Bh, bN), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((Bh, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bN, mh), jnp.float32)],
        interpret=interpret,
    )(xp, ap, qpp)
    return out[:B, :N]


def _ceil_mult(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m

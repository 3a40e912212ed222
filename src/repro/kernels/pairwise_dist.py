"""Pallas TPU kernel: tiled pairwise squared Euclidean distance.

This is the candidate-VERIFICATION hot spot of PM-LSH (Algorithm 1/2
line "verify the real distances"): exact d-dimensional distances between
a query batch Q (B, d) and candidate points X (N, d).

TPU mapping (DESIGN.md §3):
  * grid = (B/bB, N/bN, d/bD); the contraction dim d is innermost so the
    (bB, bN) output tile stays resident in VMEM across the k-loop.
  * each step computes   qn + xn - 2·Q_tile @ X_tileᵀ   — the matmul
    lands on the MXU (preferred_element_type=f32 keeps bf16 inputs
    accumulating in f32), the rank-1 norm updates ride the VPU.
  * block shapes default to (128, 128, 512): MXU-aligned (multiples of
    128 lanes / 8 sublanes) and 128·512·4B ≈ 256 KiB per operand tile —
    three tiles + out fit comfortably in 16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["pairwise_sq_dist_kernel", "pairwise_sq_dist_pallas"]


def pairwise_sq_dist_kernel(q_ref, x_ref, o_ref):
    """One (i, j, k) grid step: accumulate the k-th d-slab's contribution."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...].astype(jnp.float32)  # (bB, bD)
    x = x_ref[...].astype(jnp.float32)  # (bN, bD)
    qn = jnp.sum(q * q, axis=1, keepdims=True)  # (bB, 1)
    xn = jnp.sum(x * x, axis=1)  # (bN,)
    cross = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)  # (bB, bN) on the MXU
    o_ref[...] += qn + xn[None, :] - 2.0 * cross

    @pl.when(k == pl.num_programs(2) - 1)
    def _clamp():
        o_ref[...] = jnp.maximum(o_ref[...], 0.0)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n", "block_d", "interpret")
)
def pairwise_sq_dist_pallas(
    q: jax.Array,
    x: jax.Array,
    *,
    block_b: int = 128,
    block_n: int = 128,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """(B, d) × (N, d) → (B, N) squared distances via the Pallas kernel.

    Inputs are zero-padded to block multiples (exact for the distance
    math in d; padded N columns are sliced away).
    """
    B, d = q.shape
    N, d2 = x.shape
    assert d == d2, f"dim mismatch {d} vs {d2}"
    bB = min(block_b, _ceil_mult(B, 8))
    bN = min(block_n, _ceil_mult(N, 128))
    bD = min(block_d, _ceil_mult(d, 128))
    Bp, Np, Dp = _ceil_mult(B, bB), _ceil_mult(N, bN), _ceil_mult(d, bD)
    qp = jnp.zeros((Bp, Dp), q.dtype).at[:B, :d].set(q)
    xp = jnp.zeros((Np, Dp), x.dtype).at[:N, :d].set(x)
    grid = (Bp // bB, Np // bN, Dp // bD)
    out = pl.pallas_call(
        pairwise_sq_dist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bB, bD), lambda i, j, k: (i, k)),
            pl.BlockSpec((bN, bD), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bB, bN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.float32),
        interpret=interpret,
        name="pairwise_sq_dist_pallas",
    )(qp, xp)
    return out[:B, :N]


def _ceil_mult(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m

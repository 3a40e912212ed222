"""Pallas TPU kernel: asymmetric-distance computation (ADC) over codes.

The RERANK tier of the quantized pipeline (DESIGN.md §8): given int
codes (N, S) — S code slots per point, values in [0, V) — and per-query
lookup tables (B, S, V) of squared-distance contributions, compute

    out[b, n] = Σ_s lut[b, s, codes[n, s]]

i.e. the exact distance between a FLOAT query and a QUANTIZED point,
without ever dequantizing the point.  PQ (slot = sub-codebook) and SQ8
(slot = dimension) both reduce to this form, so one kernel serves every
codec in ``repro.quant``.

TPU mapping: gathers are poison on the VPU, so the per-slot table
lookup is rewritten as a one-hot contraction that lands on the MXU —
for each slot s the (bN, V) one-hot of the codes tile multiplies the
(bB, V) table slice, a regular 2D dot_general accumulated over the slot
grid axis.  The grid is (B/bB, N/bN, S/bS) with the slot axis innermost
so the (bB, bN) output tile stays resident in VMEM across the s-loop
(same accumulation pattern as pairwise_dist).  V is padded to the
128-lane boundary; codes never reach the padded values, so the padded
one-hot columns are all-zero and the padded LUT columns never
contribute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["adc_dist_kernel", "adc_dist_pallas"]


def adc_dist_kernel(codes_ref, lut_ref, o_ref, *, block_s: int):
    """One (i, j, s) grid step: accumulate block_s slots' contributions."""
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    codes = codes_ref[...]  # (bS, bN) int32, slot-major
    lut = lut_ref[...]  # (bB, bS, V) float32
    bS, bN = codes.shape
    V = lut.shape[-1]
    acc = jnp.zeros_like(o_ref)
    for t in range(block_s):  # static unroll: one MXU matmul per slot
        onehot = (
            codes[t, :][:, None]
            == jax.lax.broadcasted_iota(jnp.int32, (bN, V), 1)
        ).astype(jnp.float32)  # (bN, V)
        acc += jax.lax.dot_general(
            lut[:, t, :], onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (bB, bN)
    o_ref[...] += acc


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n", "block_s", "interpret")
)
def adc_dist_pallas(
    codes: jax.Array,
    lut: jax.Array,
    *,
    block_b: int = 8,
    block_n: int = 256,
    block_s: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """(N, S) codes × (B, S, V) LUTs → (B, N) ADC squared distances.

    Codes are cast to int32 (int8 VMEM tiling is stricter and the
    values index a table anyway); padded slots carry code 0 against an
    all-zero LUT column, so padding contributes exactly 0.
    """
    N, S = codes.shape
    B, S2, V = lut.shape
    assert S == S2, f"slot mismatch {S} vs {S2}"
    bB = min(block_b, _ceil_mult(B, 8))
    bN = min(block_n, _ceil_mult(N, 128))
    bS = min(block_s, S)
    Bp, Np, Sp = _ceil_mult(B, bB), _ceil_mult(N, bN), _ceil_mult(S, bS)
    Vp = _ceil_mult(V, 128)
    # slot-major codes: (Sp, Np) so the lane axis is the point axis
    cp = jnp.zeros((Sp, Np), jnp.int32).at[:S, :N].set(
        jnp.asarray(codes, jnp.int32).T)
    lp = jnp.zeros((Bp, Sp, Vp), jnp.float32).at[:B, :S, :V].set(
        jnp.asarray(lut, jnp.float32))
    grid = (Bp // bB, Np // bN, Sp // bS)
    kern = functools.partial(adc_dist_kernel, block_s=bS)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bS, bN), lambda i, j, s: (s, j)),
            pl.BlockSpec((bB, bS, Vp), lambda i, j, s: (i, s, 0)),
        ],
        out_specs=pl.BlockSpec((bB, bN), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.float32),
        interpret=interpret,
    )(cp, lp)
    return out[:B, :N]


def _ceil_mult(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m

"""Roofline models: modeled bytes + FLOPs per kernel call.

Extends ``benchmarks/cost_model.query_traffic_model``'s per-stage HBM
byte accounting down to the individual kernel: the bytes an op must
move and the FLOPs it must execute for its argument shapes, so a
measured time (``benchmarks/kernel_micro.py``, ``sharded_scale.py``, or
a span carrying ``bytes``/``flops`` attrs in ``obs.export``) answers
*memory-bound or compute-bound, and at what fraction of peak*
(DESIGN.md §12).

Conventions:

  * bytes are the minimal one-pass traffic of the op at float32 (code
    arrays at their stored width) — reads of every input once, writes
    of every output once.  Kernels that re-read (the radius-select
    ladder) model their pass count explicitly.
  * FLOPs count multiply and add separately (one MAC = 2 FLOPs),
    compares/selects count 1 — the usual roofline convention.
  * arithmetic intensity AI = flops / bytes.  Against a device's
    (peak_flops, peak_bw) the ridge point is peak_flops / peak_bw;
    AI below the ridge → the op is memory-bound, its attainable
    ceiling is AI · peak_bw; above → compute-bound at peak_flops.

Peaks come from one table, :data:`PEAKS`, keyed by the device kind JAX
reports (``jax.devices()[0].device_kind``) and citing its source; a
device missing from it is an error, not a default.  :func:`set_peaks`
pins measured peaks for a process.
"""
from __future__ import annotations

import dataclasses

__all__ = ["KernelCost", "DevicePeaks", "PEAKS", "device_kind", "get_peaks",
           "set_peaks", "pairwise_sq_dist_cost", "project_dist_cost",
           "topk_cost", "radius_select_cost", "verify_topk_cost",
           "shard_exchange_cost", "shard_merge_cost", "shard_ring_cost",
           "achieved"]

F32 = 4


@dataclasses.dataclass(frozen=True)
class KernelCost:
    """Modeled single-execution cost of one kernel dispatch."""

    bytes: int
    flops: int

    @property
    def intensity(self) -> float:
        """Arithmetic intensity (FLOPs per byte moved)."""
        return self.flops / max(self.bytes, 1)

    def attrs(self) -> dict:
        """The span-attribute form ``obs.export`` places on the roofline."""
        return {"bytes": int(self.bytes), "flops": int(self.flops),
                "intensity": round(self.intensity, 4)}


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Nominal (peak FLOP/s, peak bytes/s) for classification."""

    kind: str
    peak_flops: float
    peak_bw: float

    @property
    def ridge(self) -> float:
        """AI at which the roofline transitions memory→compute bound."""
        return self.peak_flops / self.peak_bw


#: per-chip peaks keyed by ``jax.devices()[0].device_kind``
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 and
    # 819 GB/s of HBM bandwidth per chip
    "TPU v5 lite": DevicePeaks("TPU v5 lite", 1.97e14, 8.19e11),
    # nominal 8-core AVX2 host (8 cores · 2.5 GHz · 16 f32 FLOP/cycle,
    # DDR4): the row CPU-only runs and the tests classify against
    "cpu": DevicePeaks("cpu", 3.2e11, 4.0e10),
}
_PEAKS_OVERRIDE: DevicePeaks | None = None


def device_kind() -> str:
    """The device kind JAX reports for the first device, e.g.
    "TPU v5 lite" or "cpu" — the key of :data:`PEAKS`."""
    import jax

    return str(jax.devices()[0].device_kind)


def get_peaks(kind: str | None = None) -> DevicePeaks:
    """Peaks of ``kind`` (default: this process's device).  Raises
    ``KeyError`` for a device missing from :data:`PEAKS`."""
    if _PEAKS_OVERRIDE is not None:
        return _PEAKS_OVERRIDE
    kind = kind or device_kind()
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; add a row with "
                       f"its source to repro.obs.roofline.PEAKS")
    return PEAKS[kind]


def set_peaks(peaks: DevicePeaks | None) -> None:
    """Pin measured peaks for this process (None restores defaults)."""
    global _PEAKS_OVERRIDE
    _PEAKS_OVERRIDE = peaks


# ---------------------------------------------------------------------------
# per-kernel models (shapes as the ops-layer sees them)
# ---------------------------------------------------------------------------


def pairwise_sq_dist_cost(B: int, N: int, d: int) -> KernelCost:
    """ESTIMATE: (B,d)×(N,d)→(B,N).  One read of each input, one write
    of the output; 2·B·N·d MACs-worth of FLOPs (norm trick or direct
    difference cost the same to leading order)."""
    return KernelCost(bytes=(B * d + N * d + B * N) * F32,
                      flops=2 * B * N * d + 2 * B * N)


def project_dist_cost(N: int, d: int, m: int, B: int) -> KernelCost:
    """Fused project+distance: x (N,d) @ a (d,m), then (B,m)×(N,m)."""
    proj = KernelCost(bytes=(N * d + d * m) * F32, flops=2 * N * d * m)
    dist = pairwise_sq_dist_cost(B, N, m)
    return KernelCost(bytes=proj.bytes + dist.bytes,
                      flops=proj.flops + dist.flops)


def topk_cost(B: int, N: int, k: int) -> KernelCost:
    """Selection-network top-k: one read of (B,N); ~N·k compares/row."""
    return KernelCost(bytes=(B * N + 2 * B * k) * F32, flops=B * N * k)


def radius_select_cost(B: int, N: int, T_pad: int,
                       passes: int = 16) -> KernelCost:
    """SELECT: the threshold ladder re-reads the (B,N) row once per
    counting pass (ladder + bisection + compaction ≈ ``passes`` —
    the same constant ``cost_model.query_traffic_model`` uses), then
    writes the compacted (B, T_pad) values + indices."""
    return KernelCost(bytes=passes * B * N * F32 + 2 * B * T_pad * F32,
                      flops=passes * B * N)


def verify_topk_cost(B: int, Tc: int, d: int, k: int) -> KernelCost:
    """Gather-free VERIFY: each candidate row DMA'd HBM→VMEM exactly
    once (B·Tc·d reads), queries once, (B,k)·2 answer writes; exact
    distances are 2·B·Tc·d FLOPs plus the streaming top-k compares."""
    return KernelCost(bytes=(B * Tc * d + B * d + 4 * B * k) * F32,
                      flops=2 * B * Tc * d + B * Tc * k)


def shard_exchange_cost(P: int, B: int, k_l: int,
                        rounds: int = 32) -> KernelCost:
    """Sharded-ANN THRESHOLD EXCHANGE: the counts-only bisection.  Each
    of the ``rounds`` rungs psums one (B,) int32 survivor count per
    shard — ``rounds·P·B`` int32 on the wire, zero candidate payload.
    (The k_l argument is carried so callers can log the companion merge
    volume next to it; it does not enter this cost.)  FLOPs are the
    per-rung compare+reduce over nothing the model sees — counted as
    the P·B adds of the reduction tree."""
    del k_l
    return KernelCost(bytes=rounds * P * B * 4,
                      flops=rounds * P * B)


def shard_merge_cost(P: int, B: int, k_l: int) -> KernelCost:
    """All-gather-of-k MERGE: each shard contributes (B, k_l) float32
    distances + int32 ids; the replicated pool is P·B·k_l·8 bytes, the
    final selection P·B·k_l·k_l-ish compares (modeled linear — top_k
    over an L-pool is O(L log k), noise either way)."""
    return KernelCost(bytes=P * B * k_l * (F32 + 4),
                      flops=P * B * k_l)


def shard_ring_cost(P: int, nl: int, d: int, k: int) -> KernelCost:
    """One CP ring hop: every shard ppermutes its (nl, d) row block,
    (nl,) norms + keys, (nl,) ids to its neighbor, and the round's ub
    register refresh all-gathers each shard's (k,) running best."""
    return KernelCost(
        bytes=P * (nl * d * F32 + 3 * nl * F32 + k * F32),
        flops=P * nl * d)


# ---------------------------------------------------------------------------
# achieved performance: model + measured time → roofline placement
# ---------------------------------------------------------------------------


def achieved(cost: KernelCost, seconds: float,
             peaks: DevicePeaks | None = None) -> dict:
    """Place one measured execution on the roofline.

    Returns the span-attribute dict the exporter merges into kernel
    spans: achieved GFLOP/s and GB/s, the model's arithmetic
    intensity, the bound classification against ``peaks`` (memory if
    AI < ridge else compute) and the fraction of the *attainable*
    ceiling (min(peak_flops, AI·peak_bw)) the execution reached."""
    peaks = peaks or get_peaks()
    t = max(float(seconds), 1e-12)
    gflops = cost.flops / t / 1e9
    gbps = cost.bytes / t / 1e9
    ai = cost.intensity
    ceiling = min(peaks.peak_flops, ai * peaks.peak_bw)
    return {
        "achieved_gflops": round(gflops, 3),
        "achieved_gbps": round(gbps, 3),
        "intensity": round(ai, 4),
        "ridge": round(peaks.ridge, 4),
        "bound": "memory" if ai < peaks.ridge else "compute",
        "fraction_of_peak": round(cost.flops / t / max(ceiling, 1.0), 6),
        "device_kind": peaks.kind,
    }

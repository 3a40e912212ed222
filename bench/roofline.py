"""Roofline share of a layer: the least time the chip could take for the
work the layer must do, over the device time it took.

The work is counted from shapes (or from a program counter) by each
metric's reader, as what the algorithm needs, not what an
implementation happens to do, so a faster implementation can never read
above 100%.  The least time is the larger of operations over peak
FLOP/s and bytes over peak bandwidth, from ``peaks.json``.  The kernels
run float32 at Precision.HIGHEST, which the MXU does in six bf16
passes, and are still held to the bf16 peak: such shares read low.
"""
from __future__ import annotations


def share(flops: float, nbytes: float, seconds: float, peaks: dict):
    """Percent of the roofline, or None where no time was measured."""
    if seconds <= 0:
        return None
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
    return 100.0 * least / seconds


def kernel_share(ctx, counter: str, seconds, work):
    """share() of the device time ``seconds(trace)`` for the work
    ``work(counters) -> (flops, bytes)`` over the whole window."""
    c = ctx.counters.get(counter)
    if not c or ctx.trace is None:
        return None
    flops, nbytes = work(c)
    return share(flops, nbytes, seconds(ctx.trace), ctx.peaks)

"""select (``kernels/select.py``, ``_concat_tiles`` and the finishing
top-k in ``kernels/ops.py``): the T nearest estimates of each query.
Work per call, whatever the implementation: one read of the (B, n)
estimates, B·T ids and values written, B·n compares.  Time: in each run
of the search program, the device time from the end of the estimate
kernel to the first operation of verify (the relayout of the rows to
(n, 1, d), or the verify kernel)."""
from roofline import kernel_share

MODULE = r"^jit_ann_query\b"
ESTIMATE = r"^%pairwise_sq_dist_pallas"
VERIFY_FIRST = r"^%reshape[\w.-]* = f32\[\d+,1,\d+\]|^%verify_topk_pallas"


def seconds(trace):
    return trace.phase_s(MODULE, (ESTIMATE, "end"), (VERIFY_FIRST, "start"))


def work(c):
    B, n, T, calls = c["B"], c["n"], c["T"], c["calls"]
    return calls * B * n, calls * (4 * B * n + 8 * B * T)


def read(ctx):
    c = ctx.counters.get("ann")
    if not c or not c.get("T"):
        return None
    return kernel_share(ctx, "ann", seconds, work)

"""verify (``kernels/verify.py``): exact distances of each query's T
candidates and its k nearest among them.  Work per call: B·T·d + B·d
floats read, 2·B·k written; 2·B·T·d operations.  Time: in each run of
the search program, the device time from verify's first operation (the
relayout of the rows to (n, 1, d)) to the end of the verify kernel."""
from roofline import kernel_share

MODULE = r"^jit_ann_query\b"
FIRST = r"^%reshape[\w.-]* = f32\[\d+,1,\d+\]|^%verify_topk_pallas"
KERNEL = r"^%verify_topk_pallas"


def seconds(trace):
    return trace.phase_s(MODULE, (FIRST, "start"), (KERNEL, "end"))


def work(c):
    B, T, d, k, calls = c["B"], c["T"], c["d"], c["k"], c["calls"]
    return calls * 2 * B * T * d, calls * (4 * (B * T * d + B * d) + 8 * B * k)


def read(ctx):
    c = ctx.counters.get("ann")
    if not c or not c.get("T"):
        return None
    return kernel_share(ctx, "ann", seconds, work)

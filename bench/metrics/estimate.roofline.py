"""estimate (``kernels/pairwise_dist.py``): projected squared distances
of a batch to every row.  Work per call: read the (n, m) projection and
the (B, m) projected queries, write the (B, n) estimates; 2·B·n·m
operations.  Time: in each run of the search program, the device time
from its start to the end of the estimate kernel (the projection's
relayout and padding to 128 lanes, the query projection, the kernel)."""
from roofline import kernel_share

MODULE = r"^jit_ann_query\b"
KERNEL = r"^%pairwise_sq_dist_pallas"


def seconds(trace):
    return trace.phase_s(MODULE, None, (KERNEL, "end"))


def work(c):
    B, n, m, calls = c["B"], c["n"], c["m"], c["calls"]
    return calls * 2 * B * n * m, calls * 4 * (n * m + B * m + B * n)


def read(ctx):
    return kernel_share(ctx, "ann", seconds, work)

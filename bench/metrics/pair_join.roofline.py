"""CP join (``kernels/pair_join.py``): the pruned blockwise self-join.
Work per job: 2·d operations for each pair the join verified (the
program's ``pairs_verified`` counter), and one read of the (n, d) rows.
Time: the device time of the join kernel."""
from roofline import kernel_share

KERNEL = r"^%_pair_join_jit\b"  # the join kernel, named by its jit


def seconds(trace):
    return trace.op_s([KERNEL])


def work(c):
    return (2 * c["d"] * c["pairs_verified"],
            c["jobs"] * 4 * c["n"] * c["d"])


def read(ctx):
    return kernel_share(ctx, "cp", seconds, work)

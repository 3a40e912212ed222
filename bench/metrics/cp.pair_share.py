"""CP join pruning: the pairs the join verified (``pairs_verified``, a
program counter) over all n(n-1)/2 pairs, per job, in percent."""


def read(ctx):
    c = ctx.counters.get("cp")
    if not c or not c["jobs"]:
        return None
    n = c["n"]
    return 100.0 * c["pairs_verified"] / (c["jobs"] * n * (n - 1) / 2)

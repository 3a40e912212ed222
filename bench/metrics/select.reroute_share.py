"""select (``kernels/ops.py`` ``radius_select``): the batches whose
radius selection overflowed its survivor buffer and were rerouted to
the exact sort of all n estimates, in percent of the window's batches.
A program counter: a rerouted batch reports the budget T as every
row's survivor count."""


def read(ctx):
    c = ctx.counters.get("ann")
    if not c or not c["calls"]:
        return None
    return 100.0 * c["rerouted"] / c["calls"]

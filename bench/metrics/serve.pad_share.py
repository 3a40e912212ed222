"""scheduler (``serve/scheduler.py``): padded slots over all slots the
window's flushes executed, in percent, from the scheduler's own
per-bucket counters (``real_slots``, ``padded_slots``)."""


def read(ctx):
    c = ctx.counters.get("serve")
    if not c or not c["padded_slots"]:
        return None
    return 100.0 * (1.0 - c["real_slots"] / c["padded_slots"])

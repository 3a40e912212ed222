"""scheduler: the 95th percentile (nearest rank) of the requests' queue
wait, from submit to the start of the flush that serves them, in ms.
Program spans (``serve.queue_wait``, one per request, from
``repro.obs.trace``), recorded while the traced window's profiler
session is on; None where the program records none."""
import math


def read(ctx):
    from repro.obs.trace import get_tracer

    waits = sorted(s.duration_s for s in get_tracer().spans
                   if s.name == "serve.queue_wait")
    if not waits:
        return None
    return 1e3 * waits[max(math.ceil(0.95 * len(waits)) - 1, 0)]

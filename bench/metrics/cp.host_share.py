"""CP join, its host round trip: the seconds of each job's host steps
(``cp.d2h`` the copies of the rows and keys to the host, ``cp.sort`` the
argsort, ``cp.gather`` the sorted copy, ``cp.h2d`` the upload, and
``cp.reverify`` the exact re-rank) over the seconds of the jobs
(``index.cp_search``), in percent.  Program spans (``repro.obs.trace``),
recorded while the traced window's profiler session is on; None where
the program records none."""
HOST = ("cp.d2h", "cp.sort", "cp.gather", "cp.h2d", "cp.reverify")


def read(ctx):
    from repro.obs.trace import get_tracer

    spans = get_tracer().spans
    jobs = sum(s.duration_s for s in spans if s.name == "index.cp_search")
    if jobs <= 0:
        return None
    host = sum(s.duration_s for s in spans if s.name in HOST)
    return 100.0 * host / jobs

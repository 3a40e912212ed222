"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the benchmark's numbers.

The trace holds, per TPU, a line of XLA operations with their device
start and duration, and on the host the benchmark's own
``TraceAnnotation`` spans (names starting ``bench.``) around each call
into a layer.  From these:

- busy time: the union of the intervals in which an operation ran on a
  device, inside the ``bench.window`` span, averaged over devices;
- per-name sums: the device time of the operations whose HLO name or
  JAX op name matches a pattern (a layer's kernels);
- idle gaps: the stretches inside the window with no device operation,
  each put to the innermost ``bench.`` span that covers most of it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass(frozen=True)
class Op:
    name: str  # HLO instruction name, e.g. "radius_select_pallas.1"
    op_name: str  # JAX name stack, e.g. "jit(f)/jit(g)/pallas_call"
    start: float  # ns
    end: float  # ns


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class Trace:
    """Device operations per device, and host spans, on one clock."""

    def __init__(self, devices: dict[str, list[Op]],
                 spans: list[tuple[str, float, float]],
                 modules: dict[str, list[Op]] | None = None):
        self.devices = devices
        self.spans = spans
        self.modules = modules or {}
        win = [(s, e) for name, s, e in spans if name == WINDOW]
        if not win:
            raise ValueError(f"the trace has no {WINDOW!r} span")
        self.start = min(s for s, _ in win)
        self.end = max(e for _, e in win)

    @classmethod
    def from_profile(cls, data) -> "Trace":
        devices, modules, spans = {}, {}, []
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                ops, mods = [], []
                for line in plane.lines:
                    if line.name in OP_LINES:
                        out = ops
                    elif line.name in MODULE_LINES:
                        out = mods
                    else:
                        continue
                    for ev in line.events:
                        stats = dict(ev.stats)
                        out.append(Op(ev.name, str(stats.get("tf_op", "")),
                                      ev.start_ns, ev.end_ns))
                devices[plane.name] = ops
                modules[plane.name] = mods
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [(ev.name, ev.start_ns, ev.end_ns)
                              for ev in line.events
                              if ev.name.startswith("bench.")]
        return cls(devices, spans, modules)

    @classmethod
    def from_dir(cls, log_dir: str) -> "Trace":
        from jax.profiler import ProfileData

        files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls.from_profile(ProfileData.from_file(max(files)))

    @property
    def window_s(self) -> float:
        return (self.end - self.start) / 1e9

    def _ops(self):
        for ops in self.devices.values():
            for op in ops:
                if op.end > self.start and op.start < self.end:
                    yield op

    def busy_intervals(self, ops) -> list:
        return _merge(_clip([(o.start, o.end) for o in ops],
                            self.start, self.end))

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over devices."""
        if not self.devices:
            return 0.0
        total = sum(e - s for ops in self.devices.values()
                    for s, e in self.busy_intervals(ops))
        return total / len(self.devices) / 1e9

    def op_s(self, patterns) -> float:
        """Device seconds (summed over devices) of the operations whose
        HLO name or JAX op name matches any of the regexes."""
        rx = [re.compile(p) for p in patterns]
        return sum(min(o.end, self.end) - max(o.start, self.start)
                   for o in self._ops()
                   if any(r.search(o.name) or r.search(o.op_name)
                          for r in rx)) / 1e9

    def phase_s(self, module: str, begin=None, end=None) -> float:
        """Busy device seconds between two boundaries inside each run of
        the XLA modules whose name matches ``module``, summed over runs
        and devices.

        A boundary is ``(pattern, edge)``: the ``"start"`` or ``"end"``
        of the first top-level operation in the run, after the begin
        boundary, whose HLO text matches ``pattern``.  ``None`` is the run's own
        start (as begin) or end (as end).  A run in which a boundary
        is not found adds nothing.  This is how a layer of one fused
        program is timed while its kernels carry no names of their own:
        by the order of the operations around its kernels.
        """
        mod_rx = re.compile(module)
        total = 0.0
        for dev, runs in self.modules.items():
            ops = sorted((o for o in self.devices.get(dev, ())
                          if o.end > self.start and o.start < self.end),
                         key=lambda o: o.start)
            for run in runs:
                if not mod_rx.search(run.name):
                    continue
                inside = [o for o in ops
                          if o.start >= run.start and o.start < run.end]
                top = _top_level(inside)
                lo = _boundary(top, begin, run.start, run.start)
                hi = None if lo is None else _boundary(top, end, run.end, lo)
                if lo is None or hi is None or hi <= lo:
                    continue
                busy = _merge(_clip([(o.start, o.end) for o in inside],
                                    max(lo, self.start), min(hi, self.end)))
                total += sum(e - s for s, e in busy)
        return total / 1e9

    def top_ops(self, count: int = 10) -> list:
        """[[HLO name, seconds], ...] of the operations that took most
        device time in the window."""
        by = {}
        for o in self._ops():
            by[o.name] = by.get(o.name, 0.0) + (
                min(o.end, self.end) - max(o.start, self.start)) / 1e9
        return [[k, v] for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])[:count]]

    def idle_gaps(self, count: int = 10) -> list:
        """[[host span, seconds], ...]: device idle time in the window,
        summed by the innermost ``bench.`` span over each gap."""
        inner = [(n, s, e) for n, s, e in self.spans if n != WINDOW]
        by = {}
        for ops in self.devices.values():
            busy = self.busy_intervals(ops)
            edges = [self.start] + [x for iv in busy for x in iv] + [self.end]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e <= s:
                    continue
                # most overlap wins; of equal overlaps the shorter span,
                # which is the inner one
                cands = [(min(e, he) - max(s, hs), -(he - hs), name)
                         for name, hs, he in inner
                         if min(e, he) > max(s, hs)]
                best = max(cands)[2] if cands else "outside bench spans"
                by[best] = by.get(best, 0.0) + (e - s) / 1e9
        n = max(len(self.devices), 1)
        return [[k, v / n] for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])[:count]]


def _top_level(ops):
    """The operations (sorted by start) that no other one contains: the
    steps of the program, not the steps of a loop or branch inside it."""
    top = []
    for o in ops:
        if top and o.end <= top[-1].end:
            continue
        top.append(o)
    return top


def _boundary(ops, rule, default, after):
    """The time a boundary rule names among ``ops`` (sorted by start),
    looking only at operations that start at or after ``after``."""
    if rule is None:
        return default
    pattern, edge = rule
    rx = re.compile(pattern)
    for o in ops:
        if o.start >= after and rx.search(o.name):
            return o.start if edge == "start" else o.end
    return None


def idle_percent(ctx):
    """100·(1 − busy/window) of the traced window, or None untraced."""
    tr = ctx.trace
    if tr is None or tr.window_s <= 0 or not tr.devices:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)


def describe(log_dir: str, ops: int = 8) -> None:
    """Print what a trace holds: planes, lines, and a few events with
    their stats, for reading one trace by hand."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(max(files))
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs))
            for ev in evs[:ops]:
                print("    ", ev.name, ev.start_ns, ev.duration_ns,
                      {k: str(v)[:160] for k, v in ev.stats})
    tr = Trace.from_profile(data)
    for name, ops_ in tr.devices.items():
        if ops_:
            print(name, "first op", min(o.start for o in ops_) - tr.start,
                  "last op end", max(o.end for o in ops_) - tr.end,
                  "(ns from the window's edges)")


if __name__ == "__main__":
    import sys

    describe(sys.argv[1])

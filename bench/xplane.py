"""Read what ``jax.profiler.ProfileData`` leaves out of a trace.

``ProfileData`` gives each event its own stats only.  On a TPU the
device ops keep their JAX name stack (``tf_op``, where each
``jax.named_scope`` of the program shows) among the stats of the op's
event *metadata*, which it does not expose.  This reads the
``.xplane.pb`` file itself with a minimal schema of ``XSpace`` (the
fields below, numbered as in ``tsl/profiler/protobuf/xplane.proto``)
and returns each device op with its name stack, and the benchmark's host
spans, on the file's own clock (ns).
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
WINDOW = "bench.window"


@functools.lru_cache(maxsize=1)
def _classes():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="bench_xplane_min.proto",
                                             package="bench_xplane_min",
                                             syntax="proto3")

    def message(name, fields):
        m = fdp.message_type.add(name=name)
        for fname, number, ftype, label, type_name in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if type_name:
                f.type_name = ".bench_xplane_min." + type_name

    opt, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    message("XStat", [("metadata_id", 1, F.TYPE_INT64, opt, None),
                      ("double_value", 2, F.TYPE_DOUBLE, opt, None),
                      ("uint64_value", 3, F.TYPE_UINT64, opt, None),
                      ("int64_value", 4, F.TYPE_INT64, opt, None),
                      ("str_value", 5, F.TYPE_STRING, opt, None),
                      ("ref_value", 7, F.TYPE_UINT64, opt, None)])
    message("XEvent", [("metadata_id", 1, F.TYPE_INT64, opt, None),
                       ("offset_ps", 2, F.TYPE_INT64, opt, None),
                       ("duration_ps", 3, F.TYPE_INT64, opt, None)])
    message("XLine", [("name", 2, F.TYPE_STRING, opt, None),
                      ("timestamp_ns", 3, F.TYPE_INT64, opt, None),
                      ("events", 4, F.TYPE_MESSAGE, rep, "XEvent")])
    message("XEventMetadata", [("id", 1, F.TYPE_INT64, opt, None),
                               ("name", 2, F.TYPE_STRING, opt, None),
                               ("stats", 5, F.TYPE_MESSAGE, rep, "XStat")])
    message("XStatMetadata", [("id", 1, F.TYPE_INT64, opt, None),
                              ("name", 2, F.TYPE_STRING, opt, None)])
    # the two maps, read as the repeated key/value entries they are on
    # the wire
    message("EventMetadataEntry", [
        ("key", 1, F.TYPE_INT64, opt, None),
        ("value", 2, F.TYPE_MESSAGE, opt, "XEventMetadata")])
    message("StatMetadataEntry", [
        ("key", 1, F.TYPE_INT64, opt, None),
        ("value", 2, F.TYPE_MESSAGE, opt, "XStatMetadata")])
    message("XPlane", [
        ("name", 2, F.TYPE_STRING, opt, None),
        ("lines", 3, F.TYPE_MESSAGE, rep, "XLine"),
        ("event_metadata", 4, F.TYPE_MESSAGE, rep, "EventMetadataEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, rep, "StatMetadataEntry")])
    message("XSpace", [("planes", 1, F.TYPE_MESSAGE, rep, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane_min.XSpace"))


@dataclasses.dataclass(frozen=True)
class DeviceOp:
    name: str  # the op's event name (its HLO text on a TPU)
    tf_op: str  # JAX name stack, named scopes included
    start: float  # ns
    end: float  # ns


def read(data: bytes):
    """(device ops {plane name: [DeviceOp]}, host spans [(name, start,
    end)]) of one serialized ``XSpace``."""
    space = _classes().FromString(data)
    devices, spans = {}, []
    for plane in space.planes:
        is_device = plane.name.startswith(DEVICE_PREFIX)
        if not (is_device or plane.name.startswith("/host:")):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op_id = next((k for k, v in stat_names.items() if v == "tf_op"),
                        None)
        meta = {}
        for e in plane.event_metadata:
            tf_op = next((s.str_value or stat_names.get(s.ref_value, "")
                          for s in e.value.stats
                          if s.metadata_id == tf_op_id), "")
            meta[e.key] = (e.value.name, tf_op)
        ops = []
        for line in plane.lines:
            if is_device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                name, tf_op = meta.get(ev.metadata_id, ("", ""))
                start = line.timestamp_ns + ev.offset_ps / 1e3
                end = start + ev.duration_ps / 1e3
                if is_device:
                    ops.append(DeviceOp(name, tf_op, start, end))
                elif name.startswith("bench."):
                    spans.append((name, start, end))
        if is_device:
            devices[plane.name] = ops
    return devices, spans


def window(spans):
    """(start, end) of the ``bench.window`` span, or None."""
    win = [(s, e) for n, s, e in spans if n == WINDOW]
    if not win:
        return None
    return min(s for s, _ in win), max(e for _, e in win)


def scope_seconds(devices, win, rx) -> float:
    """Device seconds in the window in which an op whose name stack
    matches ``rx`` ran (ops nest: a loop and its body count once),
    summed over devices."""
    lo, hi = win
    total = 0.0
    for ops in devices.values():
        ivs = sorted((max(o.start, lo), min(o.end, hi)) for o in ops
                     if rx.search(o.tf_op) and o.end > lo and o.start < hi)
        end = None
        for s, e in ivs:
            if end is None or s > end:
                total += e - s
                end = e
            elif e > end:
                total += e - end
                end = e
    return total / 1e9


def this_runs_profile(trace, root: str):
    """(device ops, window) of the profile under ``<root>/.bench_trace``
    that ``trace`` (a ``devtrace.Trace``) was read from: the newest one
    whose window lasts as long as the trace's, to the nanosecond.  None
    where there is none (``run.py`` keeps the run's profile there until
    its readers are done)."""
    files = sorted(glob.glob(os.path.join(root, ".bench_trace", "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime, reverse=True)
    want = trace.end - trace.start
    for path in files:
        with open(path, "rb") as f:
            devices, spans = read(f.read())
        win = window(spans)
        if win is not None and abs((win[1] - win[0]) - want) < 2.0:
            return devices, win
    return None

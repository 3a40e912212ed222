#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this machine holds.

    python3 bench/run.py --workload deep1m.ann-batch --seed 7 --seconds 40 --trace 0

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration,
``bench/configs/<config>.json``, and a traffic mix,
``bench/traffic/<mix>.json``; its per-layer metrics are the readers
``bench/metrics/<metric>.py`` and its limits ``bench/limits/<cell>.json``.
Nothing here names a cell: a new one is new files and new entries.

A run makes the data and queries on the device from ``--seed``, builds
the index through ``repro.index.build_index``, warms the cell's own
shapes (set-up, ``setup_s``), drives the mix for ``--seconds``, frees
the program's state, checks every answer of the window against the
plain reference, and prints one JSON line last: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a ``jax.profiler`` trace of the window.  The numbers compared
for ``correct`` are printed beside their limits, last on standard error
and last in the line.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, and where the program's sources are not
in the checkout.

``--control 1`` puts the reference at ``Precision.HIGH`` in the
program's place (the check must then fail); ``--sweep R1,R2,...`` runs
an open-loop mix once per rate after one set-up and prints a line per
rate (the knee sweep).  Neither is part of a measured run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    cfg: dict
    traffic: dict
    chips: int
    seed: int
    options: dict = dataclasses.field(default_factory=dict)


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(spec: dict, workload: str, seed: int,
              root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
    return Cell(workload, cfg, traffic, int(w["chips"]), int(seed))


def cell_metrics(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: ``setup_s`` and the end-to-end
    metrics listing it, or with a trace the per-layer ones."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def accelerator(chips: int):
    """The TPU devices, or NoDevice: this benchmark measures no CPU."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoDevice(f"JAX finds no accelerator ({e})") from e
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX sees {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, {len(devs)} seen")
    return devs[:chips]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def per_layer(spec, cell, win, trace, peaks, root: Path = ROOT) -> dict:
    from types import SimpleNamespace

    ctx = SimpleNamespace(trace=trace, counters=win.counters, peaks=peaks,
                          window_s=win.seconds)
    out = {}
    for m in cell_metrics(spec, cell.name, True):
        value = load_reader(m["name"], root).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def enable_cache():
    import jax

    from repro.compile_cache import enable_compile_cache

    # every program of the cell, however quick to compile, is cached:
    # a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable_compile_cache()


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(args, *, devices=None, cfg_override: dict | None = None,
        options: dict | None = None, patch=None,
        compile_cache: bool = True, root: Path = ROOT) -> dict:
    """One run of one cell; returns the result line's object.

    ``devices`` stands in for the accelerator check, ``cfg_override``
    for configuration keys and ``options`` for index options (tests run
    a cell's whole path on the CPU at a small size with them, and leave
    the process's compile cache alone with ``compile_cache=False``);
    ``patch(driver)`` may replace part of the timed path after set-up;
    ``root`` is the directory that holds ``BENCHMARK.json`` and the
    cells' files (tests point it at a copy with cells of their own).
    """
    import jax

    import drivers
    from check import judge, load_limits

    spec = load_spec(root)
    cell = load_cell(spec, args.workload, args.seed, root)
    if devices is None:
        devices = accelerator(cell.chips)
    cell.cfg.update(cfg_override or {})
    cell.options = dict(options or {})
    cache = enable_cache() if compile_cache else None
    info = device_info(devices)
    peaks = peaks_for(info["kind"], root) if args.trace else None
    log(f"cell={cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={info['kind']} x{info['count']} "
        f"jax={jax.__version__} cache={cache}")

    drv = drivers.KINDS[cell.traffic["kind"]](cell)
    drv.setup()
    setup_s = time.perf_counter() - T_START
    log(f"setup_s={setup_s:.3f}")
    if patch is not None:
        patch(drv)

    if args.sweep:
        return sweep(drv, args)

    trace_dir = None
    if args.trace:
        trace_dir = Path(args.trace_dir or root / ".bench_trace" / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        win = drv.window(args.seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    info = device_info(devices)
    log(f"window_s={win.seconds:.3f} attempted={win.attempted} "
        f"failed={win.failed} {win.e2e} {win.extra}")

    result = {}
    if args.trace:
        import devtrace

        tr = devtrace.Trace.from_dir(str(trace_dir))
        busy = tr.busy_s()
        info.update(busy_s=busy, window_s=tr.window_s)
        metrics = per_layer(spec, cell, win, tr, peaks, root)
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
        if not args.trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    drv.release()
    t0 = time.perf_counter()
    numbers, measured = drv.check(control=bool(args.control))
    log(f"reference_s={time.perf_counter() - t0:.3f}")
    correct, compared = judge(numbers, load_limits(root, cell.name))
    for name in sorted(set(numbers) - set(compared)):
        log(f"read {name}={numbers[name]!r} (not compared)")

    if not args.trace:
        values = {"setup_s": setup_s, **win.e2e, **measured}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(spec, cell.name, False)}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": info,
              **result, "compared": compared}
    for name, c in compared.items():
        log(f"compared {name}={c['value']!r} limit={c['limit']!r}")
    return result


def sweep(drv, args) -> dict:
    """The knee sweep: one open-loop window per rate, one line each."""
    rows = []
    for i, rate in enumerate(float(r) for r in args.sweep.split(",")):
        win = drv.window(args.seconds, rate=rate, stream=2 + i)
        row = {"rate_per_s": rate, **win.e2e, **win.extra,
               "window_s": win.seconds, "failed": win.failed}
        print(json.dumps({"sweep": row}), flush=True)
        rows.append(row)
    return {"sweep": rows}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--trace-dir", default="")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program's sources are not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        result = run(args)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

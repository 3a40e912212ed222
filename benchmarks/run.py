"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints ``name,us_per_call,derived`` CSV rows (framework contract), one
per measurement, grouped per paper artifact, and writes one
machine-readable ``BENCH_<name>.json`` per module (parsed rows + any
summary blocks the module published via ``common.publish_summary``) so
the perf trajectory — recall, p50/p99 latency, bytes/point — is
diffable across PRs.

Algorithm sweeps (table4_nn, cp_queries, fig8_param_study) go through
the canonical entry point ``repro.index.build_index(data,
IndexConfig(backend=...))`` and iterate the backend registry, so a
newly registered backend shows up in the tables automatically.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from .common import provenance, take_summaries

MODULES = [
    ("fig3_estimator", "benchmarks.estimator_quality"),
    ("table2_cost_model", "benchmarks.cost_model"),
    ("fig8_param_study", "benchmarks.param_study"),
    ("table4_nn", "benchmarks.nn_queries"),
    ("figs9_13_curves", "benchmarks.nn_curves"),
    ("cp_queries", "benchmarks.cp_queries"),
    ("figs7_14_16_gamma", "benchmarks.gamma_study"),
    ("kernel_micro", "benchmarks.kernel_micro"),
    ("query_pipeline", "benchmarks.query_pipeline"),
    ("stream_queries", "benchmarks.stream_queries"),
    ("quant_tradeoff", "benchmarks.quant_tradeoff"),
    ("serve_load", "benchmarks.serve_load"),
    ("resilience", "benchmarks.resilience_cost"),
    ("sharded_scale", "benchmarks.sharded_scale"),
]


def _parse_derived(derived: str) -> dict:
    """'recall=0.98;live=1200' → {'recall': 0.98, 'live': 1200.0};
    non-numeric values stay strings."""
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        key, _, val = part.partition("=")
        try:
            out[key.strip()] = float(val)
        except ValueError:
            out[key.strip()] = val.strip()
    return out


def _parse_rows(rows: list[str]) -> list[dict]:
    parsed = []
    for r in rows:
        name, _, rest = str(r).partition(",")
        us, _, derived = rest.partition(",")
        try:
            entry = {"name": name, "us_per_call": float(us)}
        except ValueError:
            continue
        entry.update(_parse_derived(derived))
        parsed.append(entry)
    return parsed


def write_bench_json(key: str, rows: list[str], summaries: dict,
                     elapsed_s: float, json_dir: str) -> str:
    """Write BENCH_<key>.json; returns the path."""
    os.makedirs(json_dir, exist_ok=True)
    path = os.path.join(json_dir, f"BENCH_{key}.json")
    payload = {
        "module": key,
        "elapsed_s": round(elapsed_s, 3),
        "provenance": provenance(),
        "rows": _parse_rows(rows),
        "summary": summaries,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser(
        description="PM-LSH paper-artifact benchmarks.  Algorithm tables "
        "sweep every backend registered in repro.index — add an index "
        "via build_index(data, IndexConfig(backend=...)) and it appears "
        "in the tables.  Each module also writes BENCH_<name>.json.",
    )
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--only", default="",
                    help="comma-separated module keys to run")
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_<name>.json (default: cwd)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.compile_cache import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}", flush=True)

    print("name,us_per_call,derived")
    failed = []
    for key, modname in MODULES:
        if only and key not in only:
            continue
        t0 = time.time()
        take_summaries()  # drop anything stale from a failed module
        try:
            mod = __import__(modname, fromlist=["run"])
            rows = mod.run(quick=not args.full)
            for r in rows:
                print(r, flush=True)
            elapsed = time.time() - t0
            path = write_bench_json(key, list(rows), take_summaries(),
                                    elapsed, args.json_dir)
            print(f"# {key}: ok in {elapsed:.1f}s → {path}", flush=True)
        except Exception:
            failed.append(key)
            print(f"# {key}: FAILED\n# {traceback.format_exc()}",
                  file=sys.stderr, flush=True)
    if failed:
        print(f"# FAILED modules: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

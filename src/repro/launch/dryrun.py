"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b \
        --shape train_4k --mesh single --out results/dryrun.jsonl

Per cell it records:
  * compiled.memory_analysis()  — bytes per device (proves HBM fit)
  * compiled.cost_analysis()    — HLO FLOPs + bytes accessed
  * collective bytes parsed from the optimized HLO (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute)
  * the three roofline terms for TPU v5e (peaks from
    ``repro.obs.roofline.PEAKS``, ~50 GB/s/link ICI) and
    MODEL_FLOPS/HLO_FLOPs utilization.
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

import argparse
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.obs.roofline import PEAKS

# hardware constants (TPU v5e)
PEAK_FLOPS = PEAKS["TPU v5 lite"].peak_flops  # bf16 per chip
HBM_BW = PEAKS["TPU v5 lite"].peak_bw  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


# header example: `%wide.region_5.7_spmd.clone (wide.param.21: (s32[], ...)) -> ... {`
# param lists nest parentheses (tuple types) — only extract the name, and
# require the line to end with '{' to qualify as a computation header.
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w\.\-]+)\s*\(")
_TUPLE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_WHILE_RE = re.compile(r'body=%([\w\.\-]+).*?"known_trip_count":\{"n":"(\d+)"\}')
_CALL_RE = re.compile(r"\b(?:call|fusion)\(.*?to_apply=%([\w\.\-]+)")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _TUPLE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for dstr in dims.split(","):
            if dstr:
                n *= int(dstr)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str) -> dict[str, float]:
    """TRIP-COUNT-AWARE collective accounting from the optimized HLO.

    Scan-over-layers lowers to `while` loops whose bodies appear once in
    the module text; XLA records `known_trip_count` in backend_config.
    We index every computation's own collective bytes, then expand the
    call graph from ENTRY, multiplying while-body contributions by their
    trip counts (nested scans — attention chunks inside the layer scan —
    multiply through).

    Ring-algorithm wire factors ((P-1)/P, 2(P-1)/P for all-reduce) are
    applied later in `roofline_terms`.
    """
    # ---- split into computations
    comps: dict[str, list[str]] = {}
    cur: str | None = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if not line.startswith(" "):  # computation header or module line
            m = _COMP_RE.match(stripped) if stripped.endswith("{") else None
            if m:
                cur = m.group(1)
                comps[cur] = []
                continue
            cur = None
        elif cur is not None:
            comps[cur].append(stripped)

    entry = None
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY") and line.rstrip().endswith("{"):
            m = _COMP_RE.match(line.strip())
            if m:
                entry = m.group(1)
    if entry is None and comps:
        entry = list(comps)[-1]

    # ---- per-computation: own collective bytes + sub-calls
    own: dict[str, dict[str, float]] = {}
    calls: dict[str, list[tuple[str, int]]] = {}
    counts = {k: 0 for k in _COLLECTIVES}
    for name, lines in comps.items():
        acc = {k: 0.0 for k in _COLLECTIVES}
        sub: list[tuple[str, int]] = []
        for ln in lines:
            if " = " not in ln:
                continue
            _, rhs = ln.split(" = ", 1)
            opm = re.search(r"\)?\s([a-z\-]+)\(", rhs)
            if opm:
                op = opm.group(1)
                if op.endswith("-done"):
                    continue  # the paired -start already carries the bytes
                if op.endswith("-start"):
                    op = op[: -len("-start")]
                if op == "while":
                    wm = _WHILE_RE.search(rhs)
                    if wm:
                        sub.append((wm.group(1), int(wm.group(2))))
                    continue
                if op in _COLLECTIVES:
                    b = _shape_bytes(rhs[: opm.start()])
                    acc[op] += b
                    counts[op] += 1
                    continue
            cm = _CALL_RE.search(rhs)
            if cm:
                sub.append((cm.group(1), 1))
        own[name] = acc
        calls[name] = sub

    # ---- expand from entry (memoized; cycles impossible in HLO)
    memo: dict[str, dict[str, float]] = {}

    def expand(name: str) -> dict[str, float]:
        if name in memo:
            return memo[name]
        total = dict(own.get(name, {k: 0.0 for k in _COLLECTIVES}))
        for child, trips in calls.get(name, []):
            sub = expand(child)
            for k in _COLLECTIVES:
                total[k] = total.get(k, 0.0) + trips * sub.get(k, 0.0)
        memo[name] = total
        return total

    out = expand(entry) if entry else {k: 0.0 for k in _COLLECTIVES}
    # 'done' ops double-count their 'start': halve paired async collectives
    out["counts"] = counts  # type: ignore[assignment]
    return out


def roofline_terms(flops: float, bytes_hbm: float, coll: dict, n_chips: int,
                   model_flops: float) -> dict:
    """All terms are PER-CHIP seconds (cost_analysis reports per-program =
    per-chip numbers under SPMD)."""
    ring = lambda b: b * (n_chips - 1) / max(n_chips, 1)
    wire = (
        ring(coll.get("all-gather", 0.0))
        + 2.0 * ring(coll.get("all-reduce", 0.0))
        + ring(coll.get("reduce-scatter", 0.0))
        + coll.get("all-to-all", 0.0)
        + coll.get("collective-permute", 0.0)
    )
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_hbm / HBM_BW
    t_coll = wire / ICI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    bound = max(t_compute, t_memory, t_coll)
    return {
        **terms,
        "dominant": dom,
        "step_lower_bound_s": bound,
        "model_flops_per_chip": model_flops / max(n_chips, 1),
        "useful_flops_ratio": (model_flops / max(n_chips, 1)) / max(flops, 1.0),
        "roofline_fraction": (t_compute / bound) if bound > 0 else 0.0,
    }


def model_flops_for_cell(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (forward-only), N = active params."""
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch  # decode: one token per sequence
    return 2.0 * n_active * tokens


def analytic_cell_costs(cfg, shape, n_chips: int, model_axis: int = 16) -> dict:
    """Analytic FLOPs + HBM bytes per chip for this cell.

    Needed because XLA's cost_analysis on the CPU backend counts
    while-loop (scan-over-layers) bodies ONCE and reports fusion-naive
    bytes; the analytic model provides trip-count-correct numbers.
    Both are recorded; §Roofline uses the analytic terms as primary and
    the HLO terms for structure (collective schedule, op mix).

    Model (documented in EXPERIMENTS.md):
      train FLOPs  = 8·N·D (fwd 2 + bwd 4 + full-remat fwd 2)
                     + 4·B·S²·heads·hd·L_attn (causal attn fwd+bwd+remat)
      prefill      = 2·N·D + B·S²·heads·hd·L_attn
      decode       = 2·N·B + attention-over-cache (or LSH estimate+verify)
      bytes: params traffic (3 reads bf16 + grad/opt f32 rw for train;
      1 read for serve) + activation residual traffic + KV-cache traffic.
    """
    N = cfg.param_count(active_only=True)
    B, S = shape.global_batch, shape.seq_len
    L_attn = cfg.n_layers
    if cfg.family == "hybrid":
        L_attn = cfg.n_layers // 3  # only the local-attn third
    if cfg.family == "ssm":
        L_attn = 0
    H, hd, d = cfg.n_heads, cfg.head_dim, cfg.d_model
    win = cfg.window or S

    pbytes_chip = 2.0 * N / model_axis  # bf16 params per chip (TP-sharded)

    if shape.kind == "train":
        tokens = B * S
        eff_s = min(S, win)
        attn = 4.0 * B * S * eff_s * H * hd * L_attn
        flops = 8.0 * N * tokens + attn
        act = 16.0 * (tokens / max(n_chips // model_axis, 1)) * d * cfg.n_layers * 2
        bytes_chip = pbytes_chip * 3 + (4.0 * N / model_axis) * 7 + act / model_axis
    elif shape.kind == "prefill":
        tokens = B * S
        eff_s = min(S, win)
        attn = 1.0 * B * S * eff_s * H * hd * L_attn * 2
        flops = 2.0 * N * tokens + attn
        act = 8.0 * (tokens / max(n_chips // model_axis, 1)) * d * cfg.n_layers * 2
        bytes_chip = pbytes_chip + act / model_axis
        # KV cache write traffic
        bytes_chip += 2.0 * tokens * cfg.n_kv_heads * hd * 2 * L_attn / n_chips
    else:  # decode
        flops = 2.0 * N * B
        kvbytes = 2.0 * B * S * cfg.n_kv_heads * hd * 2 * L_attn  # full K+V read
        if cfg.lsh_attention:
            # the paper's path: read m-dim projected keys + T verified
            est = 2.0 * B * S * cfg.n_kv_heads * cfg.lsh_m * L_attn
            ver = 2.0 * B * cfg.lsh_topk * cfg.n_kv_heads * hd * 2 * L_attn
            kvbytes = est + ver
            flops += (
                2.0 * B * S * cfg.n_kv_heads * cfg.lsh_m * L_attn  # estimate
                + 4.0 * B * cfg.lsh_topk * H * hd * L_attn  # verify attn
            )
        elif cfg.family == "hybrid":
            kvbytes = 2.0 * B * min(S, win) * cfg.n_kv_heads * hd * 2 * L_attn
            flops += 4.0 * B * min(S, win) * H * hd * L_attn
        elif L_attn:
            flops += 4.0 * B * S * H * hd * L_attn
        flops = flops
        bytes_chip = pbytes_chip + kvbytes / n_chips
    return {"flops_per_chip": flops / n_chips, "bytes_per_chip": bytes_chip}


def lower_cell(cfg, shape, mesh):
    """Build + lower the right step function for this (arch, shape)."""
    from repro.configs.base import input_specs
    from repro.serve.serve_step import make_decode_step, make_prefill
    from repro.train.train_step import make_train_step
    from repro.models import model_module
    from repro.train.optimizer import abstract_opt_state

    mod = model_module(cfg)
    specs = input_specs(cfg, shape)
    if shape.kind == "train":
        # ZeRO-3/FSDP kicks in when TP-16-sharded params exceed half of a
        # v5e's HBM — the deterministic large-model rule (§Perf iter. 2)
        params_per_chip = cfg.param_count() * 2 / 16
        fsdp = params_per_chip > 8e9
        remat = os.environ.get("REPRO_REMAT", "unit")
        step, info = make_train_step(cfg, mesh, batch_specs=specs,
                                     donate=False, fsdp=fsdp, remat=remat)
        aop = info["abstract_opt"]
        return step.lower(info["abstract_params"], aop, specs)
    if shape.kind == "prefill":
        step, info = make_prefill(
            cfg, mesh, batch=shape.global_batch, seq_len=shape.seq_len
        )
        return step.lower(info["abstract_params"], specs)
    step, info = make_decode_step(
        cfg, mesh, batch=shape.global_batch, max_seq=shape.seq_len
    )
    return step.lower(info["abstract_params"], info["cache_specs"], specs)


def run_cell(arch: str, shape_name: str, mesh_kind: str) -> dict:
    from repro.configs import SHAPES, get_config
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                 "family": cfg.family}

    if shape_name == "long_500k" and not cfg.sub_quadratic:
        rec["status"] = "skipped"
        rec["reason"] = "full attention at 500k context (no LSH path)"
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.size
    t0 = time.time()
    with mesh:
        lowered = lower_cell(cfg, shape, mesh)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        try:
            mem = compiled.memory_analysis()
            rec["memory"] = {
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, k)
            }
        except Exception as e:  # CPU backend may lack it
            rec["memory"] = {"error": str(e)}
        try:
            cost = compiled.cost_analysis()
            flops = float(cost.get("flops", 0.0))
            bytes_hbm = float(cost.get("bytes accessed", 0.0))
        except Exception as e:
            flops, bytes_hbm = 0.0, 0.0
            rec["cost_error"] = str(e)

        hlo = compiled.as_text()
        coll = parse_collective_bytes(hlo)
        rec["collective_counts"] = coll.pop("counts")
        rec["collective_bytes"] = coll
        rec["hlo_flops"] = flops
        rec["hlo_bytes"] = bytes_hbm
        mflops = model_flops_for_cell(cfg, shape)
        rec["roofline_hlo"] = roofline_terms(flops, bytes_hbm, coll, n_chips,
                                             mflops)
        ana = analytic_cell_costs(cfg, shape, n_chips)
        rec["analytic"] = ana
        rec["roofline"] = roofline_terms(
            ana["flops_per_chip"] * n_chips / n_chips, ana["bytes_per_chip"],
            coll, n_chips, mflops,
        )
    rec["params_total"] = cfg.param_count()
    rec["params_active"] = cfg.param_count(active_only=True)
    rec["lower_s"] = round(t_lower, 1)
    rec["compile_s"] = round(t_compile, 1)
    rec["status"] = "ok"
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(
        __import__("repro.configs", fromlist=["SHAPES"]).SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    try:
        rec = run_cell(args.arch, args.shape, args.mesh)
    except Exception as e:  # record failures as data, not crashes
        import traceback

        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
    line = json.dumps(rec)
    print(line)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    sys.exit(0 if rec.get("status") in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()

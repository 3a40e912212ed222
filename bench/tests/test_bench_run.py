"""A whole run of each cell on the CPU at a small size, the chip check
stood in for: sound runs come out correct; the control and each fault
that a cell of its traffic kind can have, planted in the timed path,
come out not correct; and
with no TPU, or no program beside it, ``run.py`` prints nothing and
exits non-zero.  A cell on more than one chip runs in a child process
on as many forced host devices, with its own configuration; a copy of
the benchmark that adds a four-chip cell as new files and entries only
shows that path before such a cell exists."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import run  # noqa: E402

# rows of a CPU run by traffic kind, at the published width: small
# enough for the CPU
ROWS = {"batch": 8192, "open": 8192, "cp": 4096}
SPEC = run.load_spec(ROOT)
SERVE = "deep1m.serve-open"


def _alter_ann(index, rows):
    search = index.search

    def altered(q, k=None):
        res = search(q, k=k)
        ids = res.indices.copy()
        if rows == "one":  # one answer changed where it is produced
            ids[0, 0] = (ids[0, 0] + 1) % index.n
        else:  # half of the batch left out
            ids[ids.shape[0] // 2:] = -1
        return type(res)(ids, res.distances, stats=res.stats)

    index.search = altered


def _alter_pair(drv):
    cp_search = drv.index.cp_search

    def altered(k):
        res = cp_search(k)
        pairs = res.pairs.copy()
        pairs[-1] = [0, 1] if tuple(pairs[-1]) != (0, 1) else [0, 2]
        return type(res)(pairs, res.distances, stats=res.stats)

    drv.index.cp_search = altered


def _shard_local(index):
    """The exchange between chips left out: every query answered by the
    first shard alone, from its own threshold and its own top-k, as its
    chip would answer with no psum and no all-gather merge."""
    from repro.core.sharded import ShardedFlatIndex

    impl = index.impl
    alone = ShardedFlatIndex(np.asarray(index.data)[:impl.nl], shards=1,
                             m=impl.m, seed=impl.seed, force=impl.force)
    impl.query = alone.query


# faults planted in the timed path after set-up, by name, so that a
# child process can be given one; the open driver's index sits in its
# retrieval step
FAULTS = {
    "one": lambda drv: _alter_ann(getattr(drv, "step", drv).index, "one"),
    "half": lambda drv: _alter_ann(getattr(drv, "step", drv).index, "half"),
    "local": lambda drv: _shard_local(getattr(drv, "step", drv).index),
    "pair": _alter_pair,
}
# the faults a cell of each traffic kind can have; on more than one chip
# an ANN cell can also leave out the exchange between chips
KIND_FAULTS = {"batch": ("one", "half"), "open": ("one", "half"),
               "cp": ("pair",)}


def kind_of(workload):
    return run.load_cell(SPEC, workload, 0).traffic["kind"]


def faults_of(w):
    kind = kind_of(w["name"])
    multi = ("local",) if w["chips"] > 1 and kind != "cp" else ()
    return KIND_FAULTS[kind] + multi


def fault_cases(kind):
    """(cell, fault) for every cell of this traffic kind."""
    return [(w["name"], f) for w in SPEC["workloads"]
            if kind_of(w["name"]) == kind for f in faults_of(w)]


def runs_here(workload, calls, root=ROOT):
    """The result line of each call (``seconds``, ``control``, ``fault``,
    ``seed``), run in this process on as many of its devices as the
    cell asks for chips."""
    import jax

    cell = run.load_cell(run.load_spec(root), workload, 0, root)
    devices = jax.devices()[:cell.chips]
    assert len(devices) == cell.chips, (workload, jax.devices())
    out = []
    for c in calls:
        args = run.parse(["--workload", workload,
                          "--seed", str(c.get("seed", 2**31 + 7)),
                          "--seconds", str(c.get("seconds", 0.5)),
                          "--control", str(c.get("control", 0))])
        fault = c.get("fault")
        out.append(run.run(
            args, devices=devices,
            cfg_override={"n": ROWS[cell.traffic["kind"]]},
            patch=FAULTS[fault] if fault else None, compile_cache=False,
            root=root))
    return out


def runs(workload, calls, root=ROOT):
    """``runs_here``, in a child process on ``chips`` forced host
    devices where the cell takes more than one chip (this process keeps
    its one device for the other tests)."""
    chips = run.load_cell(run.load_spec(root), workload, 0, root).chips
    if chips > 1:
        return _child_runs(workload, calls, root, chips)
    return runs_here(workload, calls, root)


def _child_runs(workload, calls, root, chips):
    code = ("import json, sys\n"
            f"sys.path.insert(0, {str(BENCH / 'tests')!r})\n"
            "import test_bench_run as t\n"
            f"res = t.runs_here({workload!r}, {calls!r},\n"
            f"                  t.Path({str(root)!r}))\n"
            "print(json.dumps(res))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.splitlines()[-1])


def one_run(workload, **call):
    return runs(workload, [call])[0]


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_sound_run_is_correct(workload):
    res = one_run(workload)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in run.cell_metrics(SPEC, workload, False)}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(res["device"])


def test_sound_served_run_is_correct():
    """Every request of the window is answered and judged; the tail is
    timed from the due time, so it is never under the service time."""
    res = one_run(SERVE, seconds=1.0)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"recall_at_10", "serve_p95_ms", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["recall_at_10"]["value"] > 0.9


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_control_is_not_correct(workload):
    res = one_run(workload, control=1)
    assert res["correct"] is False
    assert res["compared"]["dist_gap"]["value"] > \
        res["compared"]["dist_gap"]["limit"]


@pytest.mark.parametrize("workload,rows", fault_cases("batch"))
def test_batch_fault_is_not_correct(workload, rows):
    assert one_run(workload, fault=rows)["correct"] is False


@pytest.mark.parametrize("workload,rows", fault_cases("open"))
def test_served_fault_is_not_correct(workload, rows):
    assert one_run(workload, fault=rows)["correct"] is False


@pytest.mark.parametrize("workload,fault", fault_cases("cp"))
def test_cp_pair_altered_is_not_correct(workload, fault):
    assert one_run(workload, fault=fault)["correct"] is False


FOUR = "wide4.ann-batch"


@pytest.fixture(scope="module")
def four_chip_root(tmp_path_factory):
    """A copy of the benchmark with one cell more, on four chips, added
    only as new files and new entries: ``sharded-flat`` over four
    shards at d = 128, the ``ann-batch`` mix, ``deep1m.ann-batch``'s
    limits; every metric of that cell reported by this one too."""
    root = tmp_path_factory.mktemp("four_chips")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((BENCH / "configs" / "deep1m.json").read_text())
    index = dict(cfg["index"], backend="sharded-flat",
                 options={"shards": 4})
    cfg.update(name="wide4", d=128, index=index, reduced=["d"])
    (root / "bench" / "configs" / "wide4.json").write_text(json.dumps(cfg))
    shutil.copy(BENCH / "limits" / "deep1m.ann-batch.json",
                root / "bench" / "limits" / f"{FOUR}.json")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "wide4", "source": cfg["source"],
        "file": "bench/configs/wide4.json", "reduced": ["d"],
        "why": "a test cell: the Deep twin at d = 128 over four shards"})
    spec["workloads"].append({
        "name": FOUR, "config": "wide4", "traffic": "ann-batch", "chips": 4,
        "why": "a test cell: ann-batch through sharded-flat on four chips"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "deep1m.ann-batch" in m.get("workloads", ()):
            m["workloads"].append(FOUR)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root


@pytest.fixture(scope="module")
def four_chip_runs(four_chip_root):
    """A sound run, the control and each fault of the four-chip cell
    (one altered answer, half of the batch left out, the exchange
    between chips left out), in one child process on four forced host
    devices."""
    return runs(FOUR, [{}, {"control": 1}, {"fault": "one"},
                       {"fault": "half"}, {"fault": "local"}],
                four_chip_root)


def test_four_chip_copy_passes_the_spec_checks(four_chip_root):
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         str(four_chip_root / "bench" / "tests" / "test_bench_spec.py")],
        cwd=four_chip_root, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-3000:]


def test_four_chip_copy_gets_its_control_and_faults(four_chip_root):
    """The copy's own tests take the new cell by its traffic kind and
    chips: a sound run, the control, and each fault, the exchange
    between chips left out among them."""
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "--collect-only",
         "-p", "no:cacheprovider",
         str(four_chip_root / "bench" / "tests" / "test_bench_run.py")],
        cwd=four_chip_root, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-3000:]
    ids = set(re.findall(r"::(\w+\[[^\]]*\])", out.stdout))
    assert {f"test_sound_run_is_correct[{FOUR}]",
            f"test_control_is_not_correct[{FOUR}]",
            f"test_batch_fault_is_not_correct[{FOUR}-one]",
            f"test_batch_fault_is_not_correct[{FOUR}-half]",
            f"test_batch_fault_is_not_correct[{FOUR}-local]"} <= ids


def test_four_chip_sound_run_is_correct(four_chip_runs):
    res = four_chip_runs[0]
    assert res["correct"] is True, res["compared"]
    assert res["device"]["count"] == 4
    assert res["attempted"] > 0 and res["failed"] == 0


def test_four_chip_control_is_not_correct(four_chip_runs):
    res = four_chip_runs[1]
    assert res["correct"] is False
    assert res["compared"]["dist_gap"]["value"] > \
        res["compared"]["dist_gap"]["limit"]


def test_four_chip_altered_answer_is_not_correct(four_chip_runs):
    assert four_chip_runs[2]["correct"] is False


def test_four_chip_half_batch_is_not_correct(four_chip_runs):
    assert four_chip_runs[3]["correct"] is False


def test_four_chip_exchange_left_out_is_not_correct(four_chip_runs):
    res = four_chip_runs[4]
    assert res["correct"] is False
    assert res["compared"]["recall_miss"]["value"] > \
        res["compared"]["recall_miss"]["limit"]


def _bench_cmd(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "deep1m.ann-batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _bench_cmd(ROOT, env)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_exits_non_zero_beside_no_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = _bench_cmd(tmp_path, env)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_control_reads_far_above_the_program():
    """The kNN reference at Precision.HIGH in the program's place, on
    the same rows: its distances stray by orders of magnitude more."""
    import jax.numpy as jnp

    import reference
    from check import knn_numbers

    rng = np.random.default_rng(0)
    x = (rng.normal(size=(4096, 256)) * 6).astype(np.float32)
    q = (x[:64] + rng.normal(size=(64, 256)) * 0.1).astype(np.float32)
    xd = jnp.asarray(x)
    exact, _ = reference.knn(xd, x, q, 10)
    ids, dist = reference.knn(xd, x, q, 10, "high")
    ctl = knn_numbers(ids, dist, x, q, exact)
    diff = x[exact].astype(np.float64) - q[:, None, :]
    exact_d = np.sqrt((diff ** 2).sum(-1)).astype(np.float32)
    own = knn_numbers(exact, exact_d, x, q, exact)
    assert own["dist_gap"] < 1e-6 and own["recall_miss"] == 0.0
    assert ctl["dist_gap"] > 100 * max(own["dist_gap"], 1e-8)

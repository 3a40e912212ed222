"""A whole run of each cell on the CPU at a small size, the chip check
stood in for: sound runs come out correct; the control and each fault
a cell can have, planted in the timed path, come out not correct; and
with no TPU, or no program beside it, ``run.py`` prints nothing and
exits non-zero."""
import os
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

# rows, at the published width: small enough for a CPU run
SIZES = {"deep1m.ann-batch": 8192, "deep1m.serve-open": 8192,
         "deep1m.cp": 4096}
SPEC = run.load_spec(ROOT)
SERVE = "deep1m.serve-open"


def one_run(workload, *, seconds=0.5, control=0, patch=None, seed=2**31 + 7):
    import jax

    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--control", str(control)])
    return run.run(args, devices=jax.devices()[:1],
                   cfg_override={"n": SIZES[workload]}, patch=patch,
                   compile_cache=False)


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


@pytest.mark.parametrize("workload", ["deep1m.ann-batch", "deep1m.cp",
                                      SERVE])
def test_control_is_not_correct(workload):
    res = one_run(workload, control=1)
    assert res["correct"] is False
    assert res["compared"]["dist_gap"]["value"] > \
        res["compared"]["dist_gap"]["limit"]


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


@pytest.mark.parametrize("rows", ["one", "half"])
def test_batch_fault_is_not_correct(rows):
    res = one_run("deep1m.ann-batch",
                  patch=lambda drv: _alter_ann(drv.index, rows))
    assert res["correct"] is False


@pytest.mark.parametrize("rows", ["one", "half"])
def test_served_fault_is_not_correct(rows):
    res = one_run(SERVE,
                  patch=lambda drv: _alter_ann(drv.step.index, rows))
    assert res["correct"] is False


def test_cp_pair_altered_is_not_correct():
    def patch(drv):
        cp_search = drv.index.cp_search

        def altered(k):
            res = cp_search(k)
            pairs = res.pairs.copy()
            pairs[-1] = [0, 1] if tuple(pairs[-1]) != (0, 1) else [0, 2]
            return type(res)(pairs, res.distances, stats=res.stats)

        drv.index.cp_search = altered

    assert one_run("deep1m.cp", patch=patch)["correct"] is False


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

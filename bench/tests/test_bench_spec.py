"""BENCHMARK.json against the benchmark's contract: keys, names and
units in their charsets, every cell's files present, and each per-layer
metric's ``moves`` reported by every cell it lists."""
import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\n\t]{1,200}$")


def chips_allowed(workloads) -> bool:
    """Every cell takes 1 or 4 chips, and at most half of the cells,
    rounded down, take 4 (one always may)."""
    chips = [w["chips"] for w in workloads]
    four = sum(c == 4 for c in chips)
    return set(chips) <= {1, 4} and four <= max(len(chips) // 2, 1)


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_entries_have_only_their_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def _cells(*chips):
    return [{"name": f"c{i}", "chips": c} for i, c in enumerate(chips)]


@pytest.mark.parametrize("workloads,allowed", [
    (SPEC["workloads"], True),
    (_cells(4), True),  # one cell may always take four chips
    (_cells(4, 1, 4, 1), True),  # half of the cells
    (_cells(4, 4, 1), False),  # more than half, rounded down
    (_cells(1, 2), False),  # 1 or 4 chips, nothing else
], ids=["spec", "1-of-1", "2-of-4", "2-of-3", "2-chips"])
def test_chips_rule(workloads, allowed):
    assert chips_allowed(workloads) is allowed


def test_names_units_and_text_in_their_charsets():
    groups = ("configs", "workloads", "end_to_end", "per_layer")
    names = [e["name"] for g in groups for e in SPEC[g]]
    assert len(names) == len(set(names))
    for e in (e for g in groups for e in SPEC[g]):
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                              "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert TEXT.match(e[key]), (e["name"], key)
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in SPEC["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in SPEC["configs"]}
    used = set()
    for w in SPEC["workloads"]:
        assert (ROOT / configs[w["config"]]["file"]).is_file()
        used.add(w["config"])
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        limits = json.loads((BENCH / "limits" / f"{w['name']}.json")
                            .read_text())["limits"]
        assert limits and all(v >= 0 for v in limits.values())
        cell = run.load_cell(SPEC, w["name"], 1, ROOT)
        assert cell.traffic["kind"] in ("batch", "open", "cp")
    assert used == set(configs)
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["reduced"] == c["reduced"] and body["name"] == c["name"]
    for m in SPEC["per_layer"]:
        assert callable(run.load_reader(m["name"], ROOT).read)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_moves_is_reported_by_every_listed_cell(metric):
    m = {x["name"]: x for x in SPEC["per_layer"]}[metric]
    cells = {w["name"] for w in SPEC["workloads"]}
    assert m["workloads"] and set(m["workloads"]) <= cells
    e2e = {x["name"]: x for x in SPEC["end_to_end"]}
    assert m["moves"] in e2e and m["moves"] != "setup_s"
    for cell in m["workloads"]:
        reported = {x["name"] for x in run.cell_metrics(SPEC, cell, False)}
        assert m["moves"] in reported


def test_every_cell_reports_setup_another_e2e_and_a_layer():
    for w in SPEC["workloads"]:
        e2e = {x["name"] for x in run.cell_metrics(SPEC, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.cell_metrics(SPEC, w["name"], True)


def test_a_check_fits_its_time_at_24_cells():
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_peaks_name_their_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    assert run.peaks_for("TPU v5 lite", ROOT)["bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        run.peaks_for("cpu", ROOT)

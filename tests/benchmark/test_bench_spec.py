"""BENCHMARK.json against the benchmark's contract, and the files it names.

The harness finds every configuration, run mode, limit file and per-layer
reader by name, so a later change adds a cell, mode or metric with new
files and a new entry, and edits nothing that exists: the last test adds a
metric that way in a copy of the benchmark.
"""
import json
import math
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import harness

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


def test_top_level_keys_and_paths():
    assert set(SPEC) == TOP
    assert SPEC["command"] == ["python3", "benchmarks/chip/run.py"]
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_run_seconds_fit_the_check_budget():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200


def _entries():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[kind]:
            yield kind, e


@pytest.mark.parametrize("kind, entry", list(_entries()), ids=lambda v: v if isinstance(v, str) else v["name"])
def test_entry_shape(kind, entry):
    keys = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
    }[kind]
    extra = set(entry) - keys
    assert extra <= ({"workloads"} if kind in ("end_to_end", "per_layer") else set())
    assert keys <= set(entry)
    assert NAME.match(entry["name"])
    for k in ("why", "layer", "source"):
        if k in entry and kind != "end_to_end" and kind != "per_layer":
            assert 1 <= len(entry[k]) <= 200 and "\n" not in entry[k] and "\t" not in entry[k]
    if kind in ("end_to_end", "per_layer"):
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
    if kind == "end_to_end":
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.25
    if kind == "per_layer":
        assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert entry["moves"] in {m["name"] for m in SPEC["end_to_end"]}
        assert 1 <= len(entry["layer"]) <= 200 and "\n" not in entry["layer"]
        if entry["name"].endswith("_roofline"):
            assert entry["unit"] == "%"
    if kind == "workloads":
        assert entry["chips"] in (1, 4) and NAME.match(entry["traffic"])
        assert len(entry["why"]) <= 200
    if kind == "configs":
        assert entry["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
        assert (ROOT / entry["file"]).is_file() and len(entry["reduced"]) <= 16
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert set(entry["reduced"]) == set(cfg["reduced"])


def test_names_unique_and_cells_complete():
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in SPEC[kind]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    setup = next(m for m in SPEC["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.25
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads_with_its_files(name):
    cell = harness.load_cell(SPEC, name)
    assert cell.mode["chips"] == cell.chips
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"]))
    assert set(cell.limits) == {"field_gap", "moment_gap", "census", "counters", "dropped"}
    assert cell.limits["dropped"] == cell.limits["census"] == cell.limits["counters"] == 0


def test_unknown_cell_is_refused():
    with pytest.raises(harness.CellError, match="unknown workload"):
        harness.load_cell(SPEC, "no_such_cell")


def _result(cell, trace):
    metrics = cell.per_layer if trace else cell.end_to_end
    out = {
        "correct": True, "attempted": 10, "failed": 0,
        "metrics": {m["name"]: {"value": 1.5, "unit": m["unit"]} for m in metrics},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": cell.chips,
                   "memory_peak_bytes": 1 << 30},
    }
    if trace:
        out["device"].update(busy_s=1.0, window_s=2.0)
        out["breakdown"] = {"device_ops": [["fusion", 1.0]], "idle_gaps": [["host idle", 0.1]]}
    out["checks"] = {"field_gap": [1e-7, 1e-6]}
    return out


@pytest.mark.parametrize("trace", [False, True])
def test_last_line_schema(trace):
    cell = harness.load_cell(SPEC, SPEC["workloads"][0]["name"])
    out = _result(cell, trace)
    assert harness.result_problems(out, cell, trace) == []
    broken = dict(out)
    broken.pop("device")
    assert "missing 'device'" in harness.result_problems(broken, cell, trace)
    reordered = {"checks": out["checks"], **{k: v for k, v in out.items() if k != "checks"}}
    assert "'checks' is not the last key" in harness.result_problems(reordered, cell, trace)


def test_roofline_over_105_is_malformed():
    cell = harness.load_cell(SPEC, SPEC["workloads"][0]["name"])
    out = _result(cell, True)
    out["metrics"]["kernel_roofline"]["value"] = 120.0
    assert any("105" in p for p in harness.result_problems(out, cell, True))


def test_a_metric_is_added_with_files_alone(tmp_path):
    """A new per-layer metric: its reader file and a BENCHMARK.json entry,
    and no edit to any existing file of the harness."""
    shutil.copytree(ROOT / "benchmarks" / "chip", tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "dummy_ms", "unit": "ms", "better": "lower", "source": "host_clock",
        "layer": "host driver + LB turn", "moves": "step_s",
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "benchmarks" / "chip" / "metrics" / "dummy_ms.py").write_text(
        "def read(run):\n    return 1e3 * run.window_s / run.steps\n"
    )
    code = (
        "import sys; sys.path.insert(0, 'benchmarks/chip'); import harness\n"
        "cell = harness.load_cell(harness.load_spec(), sys.argv[1])\n"
        "assert 'dummy_ms' in [m['name'] for m in cell.per_layer]\n"
        "class R: window_s, steps = 2.0, 40\n"
        "print(harness.load_reader('dummy_ms')(R()))\n"
    )
    p = subprocess.run([sys.executable, "-c", code, spec["workloads"][0]["name"]],
                       cwd=tmp_path, capture_output=True, text=True, timeout=120,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode == 0, p.stderr
    assert math.isclose(float(p.stdout.strip().splitlines()[-1]), 50.0)

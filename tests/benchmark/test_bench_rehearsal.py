"""Rehearsal of the command on CPU devices at a tiny size.

``rehearse_cell.py`` drives ``harness.run_cell`` (the command's own path)
with the TPU look skipped, the Pallas kernels in interpret mode, and as
many virtual CPU devices as the cell asks for.  The command itself refuses
any backend but a TPU.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import harness
from rehearse_cell import rehearse

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
ENV = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_rehearses_correct(cell):
    out = rehearse(cell)
    c = harness.load_cell(SPEC, cell)
    assert harness.result_problems(out, c, False) == []
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == c.chips
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["step_s"]["value"] > 0


def test_cell_rehearses_correct_across_chips():
    """The harness's multi-chip path (mesh, strip and emigrant exchange,
    adoptions) on four virtual devices, the domain grown with them."""
    out = rehearse(SPEC["workloads"][0]["name"], "--chips", "4")
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4


def _run_py(cwd: Path, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", SPEC["workloads"][0]["name"],
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300, env=env,
    )


def test_command_refuses_a_cpu_backend():
    p = _run_py(ROOT, dict(ENV, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    """A directory holding only BENCHMARK.json and the files under paths
    has no program to run: the command exits non-zero with no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, dict(ENV, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())

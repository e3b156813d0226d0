"""A broken timed path makes ``correct`` false, once for each fault a
cell can have: on one chip, and with the same mode over four virtual
devices, where the exchange left out is the one between chips."""
import json
from pathlib import Path

import pytest

from rehearse_cell import rehearse

SPEC = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
FAULTS = ["unchanged", "half", "no_exchange", "altered"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_fault_is_not_correct(cell, fault):
    out = rehearse(cell, "--fault", fault)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct_across_chips(fault):
    out = rehearse(SPEC["workloads"][0]["name"], "--chips", "4", "--fault", fault)
    assert out["correct"] is False, out["checks"]

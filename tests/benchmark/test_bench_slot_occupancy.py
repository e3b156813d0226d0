"""``ShardedRuntime.slot_occupancy()`` against the harness's own count.

The harness counts alive particles and lanes per device from the
runtime's private buffers (``harness._occupancy``); the runtime's public
accessor reads the same shards per slot.  Their per-device sums must
agree on a rehearsal-size run of the first cell's configuration and mode,
on four virtual devices, after an adoption has moved boxes between them.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

_RUN = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
root = sys.argv[2]
sys.path[:0] = [root + "/benchmarks/chip", root + "/src", root + "/tests/benchmark"]
import jax
import numpy as np
import harness, problems
from rehearse_cell import TINY
from repro.dist.sharded_runtime import ShardedRuntime

spec = harness.load_spec()
cell = harness.load_cell(spec, spec["workloads"][0]["name"])
config = dict(cell.config, **TINY)
devices = jax.devices()[:4]
dom, laser, species = problems.make_state(config, 4, 2**31 + 7)
rt = ShardedRuntime(
    harness._problem(dom, laser, species, config), n_devices=4, devices=devices,
    shape_order=int(config["shape_order"]), sponge_width=int(config["sponge"]["width_cells"]),
    **cell.mode["runtime"],
)
rt.run(2)
m = np.asarray(rt.balancer.mapping).copy()
a, b = np.where(m == 0)[0][0], np.where(m == 3)[0][-1]
m[a], m[b] = m[b], m[a]
rt.apply_mapping(m)
rt.run(2)
occ = rt.slot_occupancy()
n_boxes = (dom.nz // dom.box) * (dom.nx // dom.box)
alive, lanes, by_box = harness._occupancy(rt, devices, n_boxes)
out = {k: v.tolist() for k, v in occ.items()}
out.update(
    ids=[d.id for d in devices], h_alive=alive, h_lanes=lanes,
    h_by_box=[x.tolist() for x in by_box], moved=[int(a), int(b)],
    caps=list(rt._caps), mapping=m.tolist(), bpd=rt._bpd,
)
with open(sys.argv[1], "w") as f:
    json.dump(out, f)
"""


@pytest.fixture(scope="module")
def occ(tmp_path_factory):
    path = tmp_path_factory.mktemp("occupancy") / "occ.json"
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, "-c", _RUN, str(path), str(ROOT)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(path.read_text())
    return {k: np.asarray(v) if isinstance(v, list) else v for k, v in out.items()}


def test_per_device_sums_match_the_harness(occ):
    for i, dev in enumerate(occ["ids"]):
        mine = occ["device"] == dev
        assert occ["alive"][:, mine].sum() == occ["h_alive"][i]
        assert occ["lanes"][:, mine].sum() == occ["h_lanes"][i]
    assert occ["h_alive"].sum() > 0


def test_per_box_alive_matches_the_harness(occ):
    for s in range(len(occ["alive"])):
        by_box = np.zeros_like(occ["h_by_box"][s])
        by_box[occ["box"]] = occ["alive"][s]
        np.testing.assert_array_equal(by_box, occ["h_by_box"][s])


def test_slots_follow_the_adopted_mapping(occ):
    """Each slot's device is its box's device under the adopted mapping,
    the moved boxes included; every slot holds its species' capacity."""
    ids = occ["ids"]
    assert [ids[occ["mapping"][b]] for b in occ["box"]] == occ["device"].tolist()
    a, b = occ["moved"]
    assert occ["mapping"][a] == 3 and occ["mapping"][b] == 0
    assert sorted(occ["box"].tolist()) == list(range(len(occ["box"])))
    for s, cap in enumerate(occ["caps"]):
        assert (occ["lanes"][s] == cap).all()

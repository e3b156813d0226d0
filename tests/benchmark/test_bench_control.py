"""The control comes out not correct: the plain reference, put in the
program's place and computed one precision below the configuration's
(bfloat16 operands in every particle<->grid product), read against each
cell's limits at a tiny size."""
import json
from pathlib import Path

import numpy as np
import pytest

import check
import harness
import problems
from rehearse_cell import TINY

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell_name", [w["name"] for w in SPEC["workloads"]])
@pytest.mark.parametrize("seed", [2**31 + 11, 2**32 + 12])
def test_control_is_not_correct(cell_name, seed):
    cell = harness.load_cell(SPEC, cell_name)
    config = dict(cell.config, **TINY)
    interval = int(cell.mode["runtime"]["lb_interval"])
    n_steps = int(cell.mode["check_intervals"]) * interval
    guard = int(cell.mode["runtime"]["halo"])
    dom, laser, species = problems.make_state(config, cell.chips, seed)
    initial = [(q, m, {k: np.asarray(a[k]) for k in problems.PARTICLE_KEYS}) for q, m, a in species]
    ref, counts = check.reference_state(config, dom, laser, initial, n_steps, "highest", guard)
    low, low_counts = check.reference_state(config, dom, laser, initial, n_steps, "bfloat16", guard)
    fed = [check.counter_formula(low_counts[r * interval], dom.box**2) for r in range(n_steps // interval)]
    numbers = check.gaps(low, ref, dom, fed, counts, interval)
    over = {k: v for k, v in numbers.items() if v > cell.limits[k]}
    assert over, f"the control passed every limit: {numbers}"


def test_reference_is_deterministic():
    """The reference run twice on one seed reads every number as 0."""
    cell = harness.load_cell(SPEC, SPEC["workloads"][0]["name"])
    config = dict(cell.config, **TINY)
    dom, laser, species = problems.make_state(config, 1, 2**31 + 1)
    initial = [(q, m, {k: np.asarray(a[k]) for k in problems.PARTICLE_KEYS}) for q, m, a in species]
    a, ca = check.reference_state(config, dom, laser, initial, 10, "highest", 4)
    b, cb = check.reference_state(config, dom, laser, initial, 10, "highest", 4)
    fed = [check.counter_formula(cb[0], dom.box**2)]
    numbers = check.gaps(a, b, dom, fed, ca, 10)
    assert numbers == {"field_gap": 0.0, "moment_gap": 0.0, "census": 0, "counters": 0.0}


def test_seed_gives_same_sizes_and_inputs():
    cell = harness.load_cell(SPEC, SPEC["workloads"][0]["name"])
    config = dict(cell.config, **TINY)
    _, _, a = problems.make_state(config, 1, 2**33 + 7)
    _, _, b = problems.make_state(config, 1, 2**33 + 7)
    _, _, c = problems.make_state(config, 1, 2**33 + 8)
    for (_, _, x), (_, _, y), (_, _, z) in zip(a, b, c):
        assert x["z"].shape == z["z"].shape
        np.testing.assert_array_equal(np.asarray(x["z"]), np.asarray(y["z"]))
        assert not np.array_equal(np.asarray(x["z"]), np.asarray(z["z"]))


def _kernel_precisions():
    """Contract precisions in the Mosaic bodies of both particle kernels,
    lowered for a described TPU v5e."""
    import base64
    import os
    import re

    import jax
    import jax.numpy as jnp
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels.constants import DEPOSIT_TILE
    from repro.kernels.deposition import deposit_local_tiles
    from repro.kernels.gather_push import gather_push_move
    from repro.pic.grid import Grid2D

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    grid = Grid2D(nz=64, nx=64, dz=0.274, dx=0.274, box_nz=32, box_nx=32)

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    counts = spec((4,), jnp.int32)
    parts = [spec((4, 4 * DEPOSIT_TILE)) for _ in range(5)]
    tiles = [spec((4, 40, 40)) for _ in range(6)]
    texts = [
        jax.jit(lambda c, *p: deposit_local_tiles(c, *p, grid=grid, interpret=False, tile_shape=None))
        .lower(counts, *parts).as_text(),
        jax.jit(lambda c, q, *a: gather_push_move(
            c, *a[:5], tuple(a[5:]), grid=grid, qm=q, dt=0.1, interpret=False, tile_shape=(40, 40)))
        .lower(counts, spec(()), *parts, *tiles).as_text(),
    ]
    found = []
    for text in texts:
        bodies = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)
        assert len(bodies) == 1
        with ir.Context() as ctx:
            tpu.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True
            body = str(ir.Module.parse(base64.b64decode(bodies[0])))
        assert "tpu.matmul" in body
        found.append(set(re.findall(r"contract_precision<(\w+)>", body)))
    return found


def test_program_precision_reaches_the_kernels():
    """``control.kernel_precision`` changes the precision the kernels are
    lowered with: fp32 as the program states it, Mosaic's default under
    ``DEFAULT``.  Setting ``repro.kernels.common.DOT_PRECISION`` alone
    leaves both kernels as they were."""
    import jax

    import control
    from repro.kernels import common

    assert _kernel_precisions() == [{"fp32"}, {"fp32"}]
    saved = common.DOT_PRECISION
    common.DOT_PRECISION = jax.lax.Precision.DEFAULT
    jax.clear_caches()
    try:
        assert _kernel_precisions() == [{"fp32"}, {"fp32"}]
    finally:
        common.DOT_PRECISION = saved
        jax.clear_caches()
    with control.kernel_precision("DEFAULT"):
        assert _kernel_precisions() == [set(), set()]
    assert _kernel_precisions() == [{"fp32"}, {"fp32"}]

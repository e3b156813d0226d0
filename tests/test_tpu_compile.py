"""Compile both Pallas kernels for a described TPU v5e chip, with no chip.

The TPU compiler ships with libtpu and compiles for a topology that is
described rather than attached, so the lowering refusals an interpreter
cannot see — block shapes off the (8, 128) tiling, fast memory over its
limit — fail here, at no chip time.  Nothing runs, so nothing here says
anything about results or speed.

The exchange's merge compiles here too, at the laser-ion cell's slot shapes.

Shapes: the 256-lane counter tile at the kernel-default halo, the sharded
runtime's padded slot tile, and the per-slot capacity of one chip's share
of the paper's laser-ion run at its published density (900 ppc).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.common import HALO
from repro.kernels.constants import DEPOSIT_TILE
from repro.kernels.deposition import deposit_local_tiles
from repro.kernels.gather_push import gather_push_move
from repro.pic.grid import Grid2D

#: one chip's share of the Fig. 6b run at its published density
_PAPER = dict(nz=192, nx=192, box_cells=32, ppc=900, mass_ratio=1836.0)
#: ShardedRuntime's default guard depth and capacity margin
_RUNTIME_HALO, _MARGIN = 4, 2.0


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such compiles can be written to a persistent cache but never read back
    # without the chip; keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _paper_cap() -> int:
    """Per-slot capacity at ``_PAPER``: the densest box's markers per species
    (linear in ppc: ``ppc`` markers per occupied cell) times the runtime's
    capacity margin, rounded up to whole kernel blocks as the runtime's
    Pallas backend does."""
    from repro.kernels.common import particle_blocking
    from repro.pic import laser_ion_problem

    probe = laser_ion_problem(**dict(_PAPER, ppc=1))
    peak = max(
        int(np.bincount(np.asarray(probe.grid.box_of_position(p.z, p.x))).max())
        for p in probe.species
    )
    need = int(peak * _PAPER["ppc"] * _MARGIN)
    return particle_blocking(need, DEPOSIT_TILE)[1]


_GRID = Grid2D(nz=192, nx=192, dz=0.274, dx=0.274, box_nz=32, box_nx=32)


CASES = [
    pytest.param(16, 4 * DEPOSIT_TILE, None, id="tile256-kernel-halo"),
    pytest.param(
        36, 8 * DEPOSIT_TILE, (32 + 2 * _RUNTIME_HALO,) * 2, id="runtime-padded-tile"
    ),
    pytest.param(36, "paper", (32 + 2 * _RUNTIME_HALO,) * 2, id="paper-density-cap"),
]


def _shapes(one_chip, n_boxes, cap, tile_shape):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cap = _paper_cap() if cap == "paper" else cap
    bz, bx = tile_shape or (_GRID.box_nz + 2 * HALO, _GRID.box_nx + 2 * HALO)
    counts = spec((n_boxes,), jnp.int32)
    particles = [spec((n_boxes, cap)) for _ in range(5)]
    tiles = [spec((n_boxes, bz, bx)) for _ in range(6)]
    return counts, particles, tiles


@pytest.mark.parametrize("n_boxes,cap,tile_shape", CASES)
def test_deposition_compiles_for_v5e(one_chip, n_boxes, cap, tile_shape):
    counts, particles, _ = _shapes(one_chip, n_boxes, cap, tile_shape)
    fn = jax.jit(
        lambda c, *p: deposit_local_tiles(
            c, *p, grid=_GRID, interpret=False, tile_shape=tile_shape
        )
    )
    compiled = fn.lower(counts, *particles).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_boxes,cap,tile_shape", CASES)
def test_gather_push_compiles_for_v5e(one_chip, n_boxes, cap, tile_shape):
    counts, particles, tiles = _shapes(one_chip, n_boxes, cap, tile_shape)
    qm = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    fn = jax.jit(
        lambda c, q, *a: gather_push_move(
            c, *a[:5], tuple(a[5:]), grid=_GRID, qm=q, dt=0.1,
            interpret=False, tile_shape=tile_shape,
        )
    )
    compiled = fn.lower(counts, qm, *particles, *tiles).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_merge_compiles_for_v5e_as_one_sort_per_slot(one_chip):
    """The exchange's merge at the laser-ion cell's slot shapes (9 slots of
    475,136 lanes, 59,392 arrivals): one sort carries the payload, with no
    gather, and its temporaries stay under one slot's sort operands."""
    from repro.dist.sharded_runtime import _PKEYS, merge_slots

    slots, cap, width = 9, 475_136, 59_392

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (
        spec((slots, cap), jnp.bool_),
        {k: spec((slots, cap)) for k in _PKEYS},
        spec((slots,), jnp.int32),
        spec((slots, 2)),
        spec((width,), jnp.int32),
        {k: spec((width,)) for k in _PKEYS},
    )
    compiled = jax.jit(merge_slots, static_argnums=6).lower(*args, cap).compile()
    text = compiled.as_text()
    assert " gather(" not in text
    assert text.count(" sort(") == 1
    one_slot = (1 + len(_PKEYS)) * (cap + width) * 4
    assert compiled.memory_analysis().temp_size_in_bytes < one_slot

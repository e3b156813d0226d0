"""Single-program sharded stepping: the whole LB interval as one XLA program.

``BoxRuntime`` validates the paper's loop but drives it from the host — a
``device_put`` per halo strip and a jit dispatch per box per step, O(boxes)
host operations in the hot path.  ``ShardedRuntime`` is the production
counterpart: the same physics (the composable ``particle_phase`` /
``field_phase`` from ``repro.pic.engine``), the same halo geometry
(derived from the slice plans of ``repro.pic.boxes``), but executed
*inside* ``shard_map`` over the 1-D box mesh
(``repro.launch.mesh.make_box_mesh``) with the whole LB interval fused into
one ``lax.scan`` — so the host dispatches exactly one program per interval
and syncs exactly once, to fetch the interval's device-side work-counter
history for the balancer.

State layout — *slot-major*: every per-box array is stacked along a leading
axis of ``n_boxes`` slots, block-sharded over the mesh
(``repro.dist.sharding.state_shardings``), and device ``d`` owns the
contiguous slots ``[d*bpd, (d+1)*bpd)``.  Which *box* lives in which slot
is the distribution mapping: ``slot_box[s]`` names it, and because the
equal-count knapsack (``max_boxes_per_device=1.0``, cap honoured through
refinement) keeps every device at exactly ``bpd`` boxes, any adopted
mapping is realizable as a pure slot permutation.

Two collective modes drive the cross-box data motion (``comm=``):

``"neighbor"`` (default) — **strip-only neighbour collectives**.  Boxes
are laid out along a locality-preserving slot curve
(``repro.pic.boxes.box_slot_layout``), so grid-adjacent boxes live on
ring-adjacent devices, and every cross-box transfer becomes a directional
payload on a small set of ring offsets (one ``jax.lax.ppermute`` per
offset — ``repro.dist.collectives.neighbor_exchange``):

  1. *Halo paste* — each device sends, per (slot, direction) pair crossing
     a device boundary, only the guard strip the neighbouring box needs
     (``halo_strip_tables``); arrivals scatter straight into the padded
     tiles.  Nothing global is ever materialized.
  2. *Particle phase* — ``particle_phase_stacked``: all owned slots
     advance in one vmapped call, emitting per-slot deposits, alive
     counts and the in-situ executed-work counters.
  3. *Current fold* — the overlapping deposit strips travel the same
     directional hops and scatter-**add** into each slot's padded frame
     (the strip form of ``halo_fold_plan``).
  4. *Field phase* — ``field_phase_stacked`` advances every padded tile
     (sponge + per-box laser profile) and keeps interiors.
  5. *Emigration* — leavers are binned by the ring offset of their
     destination box's owner into fixed-capacity *destination-aware
     packs*, one pack per offset per species; each pack rides its single
     directional hop and every slot merges the arrivals addressed to its
     box (overflow is counted, never silently lost).  Pack capacities are
     sized adaptively from the observed per-interval migration demand
     (grow under pressure, shrink with hysteresis — see
     :meth:`ShardedRuntime.migration_stats`).

Per-step traffic is O(strip): flat in the number of boxes for a fixed
device count, where the ring path below is O(n_boxes · tile)
(``benchmarks/bench_collectives.py`` measures both).

``"ring"`` — the reference path: interiors, padded deposits and emigrant
packs all travel the full ``ppermute`` ring (``ring_all_gather``) and each
device assembles the global frame through the dense index tables
(``interior_cell_map`` / ``padded_cell_map``).  Structurally simple and
mapping-agnostic; kept as the executable specification the neighbour path
is validated against (both match the global solver to f32 rounding).

``overlap=True`` restructures each scanned step into **split-phase
stepping** (either ``comm`` mode): the particle phase advances every
particle but deposits only the *frontier* — particles whose post-move cell
can reach a strip-sent cell (``repro.pic.boxes.frontier_cell_mask``).  The
current-fold strip sends are issued right after that frontier pass
(``repro.dist.collectives.neighbor_exchange_start``), the *interior*
deposit — the complement, geometrically unable to touch any sent strip —
runs inside the resulting dataflow window, and the arrivals are folded in
only afterwards (``neighbor_exchange_done``).  Physics is identical to the
monolithic step to f32 rounding (strip-sent cells are bitwise equal; only
the per-cell sum order changes), the collectives gain a data-independent
compute window the width of the interior deposit for XLA's latency-hiding
scheduler (``repro.launch.xla.GPU_PERF_FLAGS``), and the price is a second
masked deposit sweep.  ``overlap=False`` (default) keeps the monolithic
step as the executable non-overlapped reference;
``benchmarks/hlo_analysis.overlap_analysis`` verifies the window
*structurally* on :meth:`ShardedRuntime.interval_hlo` output and
``benchmarks/bench_collectives.py`` gates the exposed-comm fraction.

On LB adoption the runtime *re-commits the sharding*: the new mapping
becomes a slot permutation applied on device (one gather program with
``out_shardings``; no device→host transfer) so the next interval runs with
the new placement.  In neighbour mode the adopted mapping is first pulled
back toward the slot curve (``repro.core.policies.locality_repair``) so
the directional offset set stays small, and the exchange plan is rebuilt
from the committed ``slot_box`` — correctness never depends on the repair,
only the hop count does.  Capacity awareness and the straggler loop ride
the shared ``repro.dist.runtime_api`` surface, same as ``BoxRuntime``.

Two interval pipelines drive the host loop (``pipeline=``):

``"sync"`` — the reference: dispatch round *k*, fetch its counter
history, run the balancer, commit any adoption, then dispatch *k+1*.
The host idles while the round runs; the device idles while the host
balances.

``"async"`` (double-buffered, via ``repro.pic.engine.IntervalPipeline``)
— round *k+1* is enqueued **under the current mapping** while round *k*
executes; *k*'s history is harvested behind the in-flight round (the
fetch overlaps device compute), the balancer runs on it, and an adopted
mapping is applied as a slot-permutation *correction* enqueued on the
in-flight round's output futures — it lands between rounds *k+1* and
*k+2* instead of stalling between *k* and *k+1*.  The staleness
contract: a mapping decided from round *k*'s counters takes effect at
round *k+2*; histories are always interpreted under the dispatch-time
``slot_box`` (it rides the pipeline as metadata), so physics and
conservation are identical to ``"sync"`` — only adoption timing shifts
by one interval.  Still exactly one device→host sync per interval,
now overlapped; ``flush()`` drains the pipeline and the observability
accessors flush implicitly (``benchmarks/bench_interval.py`` measures
the host-idle-fraction and steps/s win).
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import LoadBalancer
from ..core.policies import hop_radius, locality_repair
from ..launch.mesh import BOX_AXIS, make_box_mesh, slot_home_devices
from ..pic.boxes import (
    BoxDecomposition,
    box_slot_layout,
    frontier_cell_mask,
    halo_strip_tables,
    interior_cell_map,
    padded_cell_map,
)
from ..kernels.common import particle_blocking
from ..kernels.constants import DEPOSIT_TILE
from ..kernels.ops import default_interpret, particle_phase_slots
from ..pic.deposition import box_work_counters
from ..pic.engine import (
    IntervalPipeline,
    field_phase_stacked,
    particle_phase_stacked,
    particle_phase_stacked_frontier,
    particle_phase_stacked_interior,
)
from ..pic.fields import Fields, make_sponge
from ..pic.grid import Grid2D
from ..pic.particles import Particles, kinetic_energy
from ..pic.problem import ProblemSetup
from ..pic.stepper import Simulation
from .box_runtime import _MIN_HALO, _np_box_ids, _round_up
from .collectives import (
    neighbor_exchange,
    neighbor_exchange_done,
    neighbor_exchange_start,
    neighbor_reduce,
    ring_all_gather,
)
from .runtime_api import (
    _StragglerMixin,
    restore_balancer,
    snapshot_balancer,
    validate_engine_backend,
    validate_pipeline,
)
from .sharding import state_shardings

__all__ = ["ShardedRuntime"]

#: particle-buffer float fields travelling through the emigration exchange
_PKEYS = ("z", "x", "ux", "uy", "uz", "w")

#: vmap axes for slot-stacked Particles (scalar charge/mass not batched)
_P_AXES = Particles(z=0, x=0, ux=0, uy=0, uz=0, w=0, alive=0, q=None, m=None)

#: emigrant-pack capacity floor (adaptive resizing never goes below this)
_MIN_MIG = 16


def _pad_tables(tables) -> np.ndarray:
    """Stack per-direction index arrays into one ``(8, m_max)`` int32 table,
    padding with ``-1`` (the receivers route padding to a dump cell)."""
    m = max(len(t) for t in tables)
    out = -np.ones((len(tables), m), np.int32)
    for j, t in enumerate(tables):
        out[j, : len(t)] = t
    return out


def merge_slots(stay, fields, boxes, centers, gdest, gpack, cap: int):
    """Merge each slot's stayers with the arrivals addressed to its box.

    ``stay`` (slots, lanes) marks the lanes that keep their particle,
    ``fields`` holds the slot's ``_PKEYS`` arrays, ``boxes`` (slots,) and
    ``centers`` (slots, 2) each slot's box and box centre; ``gdest``
    (arrivals,) and ``gpack`` (each ``_PKEYS`` array of that width) are
    the arrivals, tagged with their destination box.  Returns the merged
    slots (``_PKEYS`` and ``alive``, ``cap`` lanes each) and the valid
    particles each slot dropped past ``cap``.

    One stable sort on validity carries the payload, so the valid lanes
    come first in their old order, with no per-lane gathers.  Slots are
    merged one at a time, which holds the sort's operands to one slot's
    lanes.
    """

    def merge(args):
        stay_r, fields_r, box_r, center_r = args
        valid = jnp.concatenate([stay_r, gdest == box_r])
        n_valid = valid.sum()
        sorted_ = jax.lax.sort(
            (jnp.where(valid, 0, 1),)
            + tuple(jnp.concatenate([fields_r[k], gpack[k]]) for k in _PKEYS),
            num_keys=1,
            is_stable=True,
        )
        out = {k: v[:cap] for k, v in zip(_PKEYS, sorted_[1:])}
        new_alive = jnp.arange(cap) < jnp.minimum(n_valid, cap)
        # park dead entries at the box centre, zero their payload
        out["z"] = jnp.where(new_alive, out["z"], center_r[0])
        out["x"] = jnp.where(new_alive, out["x"], center_r[1])
        for k in ("ux", "uy", "uz", "w"):
            out[k] = jnp.where(new_alive, out[k], 0.0)
        out["alive"] = new_alive
        dropped_c = n_valid - new_alive.sum()
        return out, dropped_c

    return jax.lax.map(merge, (stay, fields, boxes, centers))


class ShardedRuntime(_StragglerMixin):
    """Step a ``ProblemSetup`` as one sharded XLA program per LB interval.

    Parameters
    ----------
    problem:      grid + species + laser (``repro.pic.problem``).  The box
                  count must be divisible by ``n_devices`` (slots are
                  equal-count by construction).
    n_devices:    devices forming the box mesh (fake host devices via
                  ``REPRO_HOST_DEVICES`` / ``XLA_FLAGS`` on CPU).
    lb_interval:  steps per LB round (paper: 10) — also the scan length of
                  one fused program.
    halo:         guard depth of the per-slot tiles (>= 4, as
                  ``BoxRuntime``).
    comm:         ``"neighbor"`` (default) exchanges only guard strips and
                  destination-aware emigrant packs over directional
                  ``ppermute`` hops; ``"ring"`` is the reference
                  all-gather path (see the module docstring).
    overlap:      ``False`` (default) runs the monolithic step — one
                  deposit, collectives strictly between the phases (the
                  executable non-overlapped reference).  ``True`` enables
                  split-phase stepping: frontier deposit → strip sends
                  issued → interior deposit inside the collective window →
                  arrivals folded in (see the module docstring).  Same
                  physics to f32 rounding; costs a second masked deposit
                  sweep, buys the scheduler a latency-hiding window
                  (``benchmarks/bench_collectives.py`` measures both).
    pipeline:     ``"sync"`` (default) fetches each interval's counter
                  history before dispatching the next interval — the
                  executable reference.  ``"async"`` double-buffers the
                  interval: the next round is enqueued while the previous
                  one executes, its history is harvested behind it, and an
                  adopted mapping lands as a slot-permutation correction
                  one interval late (the staleness contract; see the
                  module docstring).  Same physics to f32 rounding, same
                  one sync per interval — the sync is overlapped instead
                  of serializing the loop.
    engine_backend: ``"xla"`` (default) runs the pure-jnp reference
                  particle phase and derives the balancer's work signal
                  from post-step alive counts via
                  ``repro.pic.deposition.box_work_counters``.  ``"pallas"``
                  runs the slot-batched Pallas kernels
                  (``repro.kernels.ops.particle_phase_slots``) inside the
                  same scanned interval program and feeds the balancer the
                  *in-kernel* executed-tile work counters — the paper's
                  in-situ measurement, with no host-side work model.
                  Composes with both ``comm`` modes and both ``pipeline``
                  modes; ``overlap=True`` raises (split-phase masking is
                  XLA-only).  On a TPU the kernels compile; on any other
                  backend they run in the Pallas interpreter
                  (``repro.kernels.ops.default_interpret``), so the backend
                  is testable on fake CPU devices.
    layout:       slot curve for ``comm="neighbor"`` —
                  ``"morton"`` (default) or ``"row"``
                  (``repro.pic.boxes.box_slot_layout``).  The initial
                  mapping follows the curve (curve-contiguous device
                  blocks); ``comm="ring"`` keeps the balancer's
                  round-robin initial mapping.
    locality_shift: adopted mappings are repaired so no box sits more than
                  this many ring hops from its curve-home device
                  (``repro.core.policies.locality_repair``; neighbour mode
                  only).
    mig_cap:      initial per-pack, per-species emigrant capacity of the
                  destination-aware exchange (default
                  ``max(16, cap // 8)``).  With ``adaptive_mig`` (default)
                  the capacity then tracks the observed per-interval
                  migration demand: packs grow when demand exceeds half
                  the capacity and shrink (after ``mig_patience`` quiet
                  intervals) when demand stays under a quarter of it;
                  overflow is counted in ``dropped_total`` rather than
                  silently lost, and resizes are logged in
                  :meth:`migration_stats`.
    adaptive_mig / mig_patience: the demand-driven capacity controller
                  (disable for strictly static shapes — each resize
                  recompiles the interval program).
    policy / improvement_threshold / shape_order / sponge_width /
    capacity_margin / capacity_round / devices: as ``BoxRuntime``.  The
                  knapsack runs with ``max_boxes_per_device=1.0`` (equal
                  counts); proposals from non-count-preserving policies are
                  repaired before adoption.
    """

    def __init__(
        self,
        problem: ProblemSetup,
        n_devices: int,
        lb_interval: int = 10,
        *,
        halo: int = _MIN_HALO,
        comm: str = "neighbor",
        overlap: bool = False,
        pipeline: str = "sync",
        engine_backend: str = "xla",
        layout: str = "morton",
        locality_shift: int = 1,
        policy: str = "knapsack",
        improvement_threshold: float = 0.10,
        shape_order: int = 3,
        sponge_width: int = 8,
        capacity_margin: float = 2.0,
        capacity_round: int = 64,
        mig_cap: Optional[int] = None,
        adaptive_mig: bool = True,
        mig_patience: int = 3,
        devices: Optional[Sequence] = None,
    ):
        grid = problem.grid
        if halo < _MIN_HALO:
            raise ValueError(f"halo must be >= {_MIN_HALO} (particle stencil support)")
        if min(grid.box_nz, grid.box_nx) < halo:
            raise ValueError(
                f"boxes ({grid.box_nz}x{grid.box_nx}) must be at least halo={halo} wide"
            )
        if grid.n_boxes % n_devices:
            raise ValueError(
                f"{grid.n_boxes} boxes do not split evenly over {n_devices} "
                "devices; the sharded runtime needs equal-count slots"
            )
        if comm not in ("ring", "neighbor"):
            raise ValueError(f"comm must be 'ring' or 'neighbor', got {comm!r}")
        self.grid = grid
        self.laser = problem.laser
        self.decomp = BoxDecomposition(grid)
        self.halo = halo
        self.comm = comm
        self.overlap = bool(overlap)
        self.pipeline = validate_pipeline(pipeline)
        self.engine_backend = validate_engine_backend(engine_backend)
        if self.engine_backend == "pallas" and self.overlap:
            raise ValueError(
                "engine_backend='pallas' does not compose with overlap=True: "
                "split-phase frontier/interior deposit masking exists only in "
                "the XLA particle phase (see docs/architecture.md, 'The "
                "kernel backend')"
            )
        if self.engine_backend == "pallas" and shape_order != 3:
            raise ValueError(
                "engine_backend='pallas' supports shape_order=3 only (the "
                f"kernels hard-code the order-3 B-spline), got {shape_order}"
            )
        #: run the Pallas kernels in interpreter mode (resolved once, at
        #: construction: true exactly when the backend is not a TPU)
        self.interpret = default_interpret()
        self.layout = layout
        self.locality_shift = int(locality_shift)
        self.shape_order = shape_order
        self.n_devices = n_devices
        self.lb_interval = lb_interval
        self.adaptive_mig = bool(adaptive_mig)
        self.mig_patience = int(mig_patience)
        self.t = 0.0
        self.step_idx = 0
        #: host dispatches (programs launched + host->device commits)
        self.host_dispatches = 0
        #: device->host syncs (exactly one per interval piece)
        self.host_syncs = 0
        #: emigrants lost to the capacity bound (should stay 0; see mig_cap)
        self.dropped_total = 0
        #: emigrant-pack resize events (adaptive mig_cap controller)
        self.mig_events: List[Dict] = []

        self.mesh = make_box_mesh(n_devices, devices=devices)
        self.devices = list(np.ravel(self.mesh.devices))
        self._bpd = grid.n_boxes // n_devices

        self.balancer = LoadBalancer(
            n_devices=n_devices,
            policy=policy,
            interval=lb_interval,
            improvement_threshold=improvement_threshold,
            max_boxes_per_device=1.0,  # equal counts: mappings stay slot-permutable
        )
        self.balancer.ensure_mapping(grid.n_boxes)

        # -- geometry tables (shared with BoxRuntime via the slice plans) --
        pnz, pnx = grid.box_nz + 2 * halo, grid.box_nx + 2 * halo
        self.local_grid = Grid2D(
            nz=pnz, nx=pnx, dz=grid.dz, dx=grid.dx, box_nz=pnz, box_nx=pnx, cfl=grid.cfl
        )
        self._cell_map = padded_cell_map(grid, halo)  # (n_boxes, pn, pn)
        self._int_map = interior_cell_map(grid)  # (n_boxes, bnz, bnx)
        self._strips = halo_strip_tables(grid, halo)
        self._origins = np.stack(
            [
                [(bz * grid.box_nz - halo) * grid.dz, (bx * grid.box_nx - halo) * grid.dx]
                for bz, bx in grid.box_coords
            ]
        ).astype(np.float32)
        self._centers = np.stack(
            [
                [(bz + 0.5) * grid.box_nz * grid.dz, (bx + 0.5) * grid.box_nx * grid.dx]
                for bz, bx in grid.box_coords
            ]
        ).astype(np.float32)

        sponge_g = np.pad(np.asarray(make_sponge(grid, sponge_width)), halo, mode="wrap")
        if self.laser is not None:
            prof_g = np.pad(np.asarray(self.laser.profile(grid)), halo, mode="wrap")
        else:
            prof_g = np.zeros_like(sponge_g)
        statics = []
        for bz, bx in grid.box_coords:
            sz = slice(bz * grid.box_nz, bz * grid.box_nz + pnz)
            sx = slice(bx * grid.box_nx, bx * grid.box_nx + pnx)
            statics.append(np.stack([sponge_g[sz, sx], prof_g[sz, sx]]))
        self._statics = np.stack(statics).astype(np.float32)  # (n_boxes, 2, pn, pn)

        # -- locality curve + initial slot assignment + state commit ------
        self._curve = (
            box_slot_layout(grid, layout)
            if comm == "neighbor"
            else np.arange(grid.n_boxes, dtype=np.int64)
        )
        self._home_dev = slot_home_devices(self._curve, n_devices)
        if comm == "neighbor":
            # start from the curve-contiguous mapping: perfectly
            # equal-count, and every neighbour hop is as short as the
            # curve allows (the balancer adopts away from it as costs ask)
            self.balancer.mapping = self._home_dev.astype(np.int64).copy()
        self._qm = [(float(p.q), float(p.m)) for p in problem.species]
        self._slot_box = self._slots_from_mapping(self.balancer.mapping)
        self._offsets: Tuple[int, ...] = ()
        self._pair_caps: Dict[int, int] = {}
        self._build_comm_plan()
        self._capacity_margin = float(capacity_margin)
        self._capacity_round = int(capacity_round)
        self._caps: List[int] = []
        self._mig_caps: List[Dict[int, int]] = []
        self._mig_idle: Dict[Tuple[int, int], int] = {}
        self._interval_cache: Dict[Tuple, Callable] = {}
        #: cache key of the last dispatched interval program (names the
        #: cause of the next cache miss in the ``interval_new_plan`` span)
        self._dispatched_key: Optional[Tuple] = None
        tiles, species = self._pack_initial(problem.species, mig_cap)
        self._commit_state(tiles, species)

        self.history: Dict[str, List] = {
            "field_energy": [],
            "kinetic_energy": [],
            "lb_steps": [],
        }

    # ------------------------------------------------------------------
    # placement: slots <-> boxes <-> devices
    # ------------------------------------------------------------------
    def _slots_from_mapping(self, mapping: np.ndarray) -> np.ndarray:
        """Initial slot_box: device ``d``'s slots hold its boxes in curve
        order (box-id order for ``comm="ring"``, where the curve is the
        identity)."""
        slot_box = np.empty(self.grid.n_boxes, np.int64)
        for d in range(self.n_devices):
            boxes = np.where(np.asarray(mapping) == d)[0]
            if len(boxes) != self._bpd:
                raise ValueError("mapping must give every device the same box count")
            boxes = boxes[np.argsort(self._curve[boxes], kind="stable")]
            slot_box[d * self._bpd : (d + 1) * self._bpd] = boxes
        return slot_box

    def device_of(self, box: int):
        """The jax device owning ``box`` under the current mapping."""
        return self.devices[int(self.balancer.mapping[box])]

    def devices_in_use(self) -> List[int]:
        """Distinct device ids currently holding box state."""
        return sorted({self.device_of(b).id for b in range(self.grid.n_boxes)})

    def _slot_of_box(self) -> np.ndarray:
        inv = np.empty(self.grid.n_boxes, np.int64)
        inv[self._slot_box] = np.arange(self.grid.n_boxes)
        return inv

    def _commit_state(self, tiles: np.ndarray, species) -> None:
        """Commit slot-major host state to the mesh (initial placement) —
        shardings come from the shared rule table
        (``repro.dist.sharding.state_shardings``) — and hand ownership of
        the rotating (tiles, species) buffer chain to the interval
        pipeline (``repro.pic.engine.IntervalPipeline``): depth 1 for
        ``pipeline="sync"`` (harvest immediately after dispatch — the
        reference serial loop), depth 2 for ``"async"`` (one round may
        stay in flight between ``run`` calls)."""
        state = (
            jnp.asarray(tiles),
            tuple({k: jnp.asarray(v) for k, v in sp.items()} for sp in species),
            jnp.asarray(self._slot_box.astype(np.int32)),
        )
        tiles_dev, species_dev, self._slot_box_dev = jax.device_put(
            state, state_shardings(state, self.mesh)
        )
        pipe = getattr(self, "_pipe", None)
        if pipe is not None:
            # re-commit into the existing pipeline (a checkpoint restore):
            # drain whatever is still in flight, then swap the chain
            pipe.drain()
            pipe.reset((tiles_dev, species_dev))
        else:
            self._pipe = IntervalPipeline(
                (tiles_dev, species_dev), depth=1 if self.pipeline == "sync" else 2
            )
        # the adoption permutation, built eagerly while the state is
        # concrete (applying it later must not barrier the pipeline)
        shardings = state_shardings((tiles_dev, species_dev), self.mesh)

        def reorder(tiles, species, p):
            with jax.named_scope("reorder"):
                return jax.tree_util.tree_map(lambda a: a[p], (tiles, species))

        self._reorder_fn = jax.jit(reorder, out_shardings=shardings)
        self._commit_slot_tables()
        self.host_dispatches += 1

    @property
    def _tiles(self):
        """Tail of the pipeline's buffer chain: the slot-major field tiles
        the next dispatch consumes (futures while a round is in flight)."""
        return self._pipe.state[0]

    @property
    def _species(self):
        """Tail of the pipeline's buffer chain: the slot-major per-species
        particle buffers (futures while a round is in flight)."""
        return self._pipe.state[1]

    def _commit_slot_tables(self) -> None:
        """Replicate the host-known slot tables (the inverse mapping the
        directional routing needs) — the former in-program slot-box ring
        broadcast, now a host-provided input."""
        self._slot_of_dev = jax.device_put(
            jnp.asarray(self._slot_of_box().astype(np.int32)),
            NamedSharding(self.mesh, P()),
        )
        self._sb_all_dev = jax.device_put(
            jnp.asarray(self._slot_box.astype(np.int32)),
            NamedSharding(self.mesh, P()),
        )

    # ------------------------------------------------------------------
    # the neighbour-exchange plan (host side)
    # ------------------------------------------------------------------
    def _build_comm_plan(self) -> None:
        """Derive the directional exchange plan from the committed
        ``slot_box``: the set of ring offsets with any (slot, direction)
        pair on them, and the per-offset pair capacity (max over devices —
        payload shapes must be uniform).  Offset 0 carries the
        same-device strips (no collective).  Rebuilt at every adoption;
        the interval-program cache is keyed on the result, so only a plan
        *change* recompiles."""
        if self.comm != "neighbor":
            self._offsets, self._pair_caps = (), {}
            return
        n, bpd = self.n_devices, self._bpd
        sb = self._slot_box
        dev_of_box = self._slot_of_box() // bpd
        send_to = self._strips.src_box[:, list(self._strips.opposite)]  # (S, 8)
        # pairs are enumerated sender-side: slot s (box sb[s]) sends its
        # direction-j strip to the owner of send_to[sb[s], j]
        offs = (dev_of_box[send_to[sb]] - (np.arange(len(sb)) // bpd)[:, None]) % n
        counts = np.zeros((n, n), np.int64)
        np.add.at(counts, ((np.arange(len(sb)) // bpd)[:, None], offs), 1)
        caps = counts.max(axis=0)
        self._offsets = tuple(int(o) for o in np.nonzero(caps)[0])
        self._pair_caps = {int(o): int(caps[o]) for o in self._offsets}

    def _plan_key(self) -> Tuple:
        if self.comm == "ring":
            return ("ring", self.overlap, tuple(d[0] for d in self._mig_caps))
        return (
            "neighbor",
            self.overlap,
            self._offsets,
            tuple(self._pair_caps[o] for o in self._offsets),
            tuple(tuple(sorted(d.items())) for d in self._mig_caps),
        )

    def _new_plan_cause(self, key: Tuple) -> str:
        """Why the interval program for ``key`` (``(n_steps, plan key)``)
        is not cached, against the last dispatched key: ``"first"`` (none
        since the cache was last emptied), ``"exchange_plan"`` (offsets or
        pair capacities changed), ``"pack_resize"`` (only the emigrant-pack
        capacities changed) or ``"piece_length"`` (only ``n_steps``)."""
        last = self._dispatched_key
        if last is None:
            return "first"
        plan, last_plan = key[1], last[1]
        # every plan key ends with the emigrant-pack capacities
        if plan[:-1] != last_plan[:-1]:
            return "exchange_plan"
        if plan[-1] != last_plan[-1]:
            return "pack_resize"
        return "piece_length"

    def hop_radius(self) -> int:
        """Largest ring distance between a box's device and its curve-home
        (0 on the initial neighbour-mode mapping; ``locality_repair``
        keeps it <= ``locality_shift`` across adoptions)."""
        return hop_radius(self.balancer.mapping, self._home_dev, self.n_devices)

    def comm_stats(self) -> Dict:
        """Per-step cross-device traffic of the committed exchange plan.

        Host-side accounting (no device sync): every ``ppermute`` payload
        byte of one scanned step, from the static plan shapes.  The
        benchmark claim lives here: ``bytes_per_step`` is O(strip) — flat
        in the box count — for ``comm="neighbor"`` and O(n_boxes · tile)
        for ``comm="ring"`` (``benchmarks/bench_collectives.py``).
        """
        n, bpd = self.n_devices, self._bpd
        n_sp = len(self._qm)
        pnz = self.grid.box_nz + 2 * self.halo
        pnx = self.grid.box_nx + 2 * self.halo
        if self.comm == "ring":
            interior = bpd * 6 * self.grid.box_nz * self.grid.box_nx
            padded = bpd * 3 * pnz * pnx
            emig = sum(bpd * d[0] * (len(_PKEYS) + 1) for d in self._mig_caps)
            # interiors + deposits + per species (dest tags, field pack)
            hops = (n - 1) * (1 + 1 + 2 * n_sp)
            return {
                "comm": "ring",
                "bytes_per_step": 4 * (n - 1) * (interior + padded + emig),
                "ppermutes_per_step": hops,
                "offsets": tuple(range(1, n)) if n > 1 else (),
            }
        m_max = max(len(t) for t in self._strips.paste_src)
        f_max = max(len(t) for t in self._strips.fold_src)
        cross = [o for o in self._offsets if o % n != 0]
        pair = sum(self._pair_caps[o] * (6 * m_max + 3 * f_max + 2 * 2) for o in cross)
        emig = sum(
            caps.get(o, 0) * (len(_PKEYS) + 1) for caps in self._mig_caps for o in cross
        )
        return {
            "comm": "neighbor",
            "bytes_per_step": 4 * (pair + emig),
            "ppermutes_per_step": len(cross) * (2 + n_sp),
            "offsets": self._offsets,
            "pair_caps": dict(self._pair_caps),
            "hop_radius": self.hop_radius(),
        }

    def compile_interval(self, n_steps: Optional[int] = None):
        """Ahead-of-time compile of the committed interval program.

        Lowers and compiles the exact program :meth:`run` would dispatch
        for an ``n_steps`` piece (default: one full ``lb_interval``) under
        the current exchange plan, and returns the ``jax.stages.Compiled``
        (``memory_analysis()`` gives its device bytes).  Nothing is
        executed and no buffer is donated, but the call waits for
        in-flight pipeline rounds (it reads the committed tail state for
        shapes/shardings).
        """
        n = int(n_steps) if n_steps else max(1, self.lb_interval)
        fn = self._interval_fn(n)
        tiles, species = self._pipe.state
        lowered = fn.lower(
            tiles, species, self._slot_box_dev, self._slot_of_dev,
            jnp.float32(self.t),
        )
        return lowered.compile()

    def interval_hlo(self, n_steps: Optional[int] = None) -> str:
        """Optimized (post-SPMD) HLO text of the committed interval program
        (:meth:`compile_interval`) — the input of
        ``benchmarks.hlo_analysis``'s structural checks
        (``overlap_analysis`` verifies the split-phase collective window
        on it; tests and ``bench_collectives`` gate the exposed-comm
        fraction)."""
        return self.compile_interval(n_steps).as_text()

    # ------------------------------------------------------------------
    # adaptive emigrant-pack capacity (observed-demand controller)
    # ------------------------------------------------------------------
    def _mig_keys(self) -> Tuple[int, ...]:
        """Pack keys: directional ring offsets for the neighbour exchange,
        or the single per-slot pack (key 0) for the ring path."""
        return self._offsets if self.comm == "neighbor" else (0,)

    def _init_mig_caps(self, base: int) -> Dict[int, int]:
        return {int(o): int(base) for o in self._mig_keys()}

    def migration_stats(self) -> Dict:
        """Emigrant-pack state: per-species pack capacities (keyed by ring
        offset in neighbour mode), the resize-event log of the adaptive
        controller, and the overflow count.  Flushes the interval pipeline
        first so every dispatched round's demand has been folded."""
        self.flush()
        return {
            "comm": self.comm,
            "caps": [dict(d) for d in self._mig_caps],
            "resizes": len(self.mig_events),
            "events": list(self.mig_events),
            "dropped_total": self.dropped_total,
        }

    def _adapt_mig(
        self,
        demand: np.ndarray,
        keys: Optional[Tuple[int, ...]] = None,
        step: Optional[int] = None,
    ) -> None:
        """Resize emigrant packs from one interval's observed demand.

        ``demand`` is the fetched per-step demand history: per (species,
        slot) on the ring path, per (species, device, offset) on the
        neighbour path — in both cases the *pre-capacity* emigrant count,
        so saturation is visible even while packs overflow.  Grow
        immediately when peak demand exceeds half the pack (demand beyond
        the pack is dropped particles); shrink only after
        ``mig_patience`` consecutive quiet intervals (peak under a
        quarter), with a floor of ``_MIN_MIG``.

        ``keys`` names the pack keys (ring offsets) the history was
        *dispatched* with — under ``pipeline="async"`` an adoption between
        dispatch and harvest may have rebuilt the exchange plan, so the
        demand columns are decoded with the dispatch-time keys and updates
        to offsets no longer in the plan are discarded (their packs are
        gone; demand-driven growth re-learns new offsets within one
        interval).  ``step`` stamps resize events with the measured round's
        boundary (the same stamp the balancer events use), not the
        dispatch frontier current at harvest time.
        """
        if not self.adaptive_mig:
            return
        if keys is None:
            keys = self._mig_keys()
        if step is None:
            step = self.step_idx
        for s in range(len(self._mig_caps)):
            if self.comm == "neighbor":
                # (n_steps, n_sp, n_devices * n_offsets)
                per = demand[:, s, :].reshape(demand.shape[0], self.n_devices, len(keys))
                peaks = {o: int(per[:, :, i].max()) for i, o in enumerate(keys)}
            else:
                peaks = {0: int(demand[:, s, :].max())}
            for o, peak in peaks.items():
                if o not in self._mig_caps[s]:
                    continue  # offset left the plan while this round flew
                cap = self._mig_caps[s][o]
                idle = self._mig_idle.get((s, o), 0)
                new = cap
                if 2 * peak > cap:
                    new, idle = _round_up(max(2 * peak, _MIN_MIG), 8), 0
                elif 4 * peak <= cap and cap > _MIN_MIG:
                    idle += 1
                    if idle >= self.mig_patience:
                        new, idle = max(_MIN_MIG, _round_up(2 * max(peak, 1), 8)), 0
                else:
                    idle = 0
                self._mig_idle[(s, o)] = idle
                if new != cap:
                    self._mig_caps[s][o] = new
                    self.mig_events.append(
                        {
                            "step": step,
                            "species": s,
                            "offset": o,
                            "old": cap,
                            "new": new,
                            "peak": peak,
                        }
                    )

    # ------------------------------------------------------------------
    # initial particle packing (slot-major, fixed capacity)
    # ------------------------------------------------------------------
    def _pack_pooled(self, pooled: List[Dict[str, np.ndarray]]) -> List[Dict[str, np.ndarray]]:
        """Bin per-species pooled alive particles (flat host arrays with
        domain-global positions) into slot-major fixed-capacity buffers
        under the committed ``slot_box``.  Grows ``self._caps`` when a box
        population no longer fits a species buffer — and clears the
        interval-program cache then, since the capacities are baked into
        the compiled closures.  Used for the initial packing and for a
        checkpoint restore (whose pooled form is device-count independent).
        """
        grid, S = self.grid, self.grid.n_boxes
        box_of_slot = self._slot_box
        slot_of_box = np.empty(S, np.int64)
        slot_of_box[box_of_slot] = np.arange(S)
        self._alive_by_box = np.zeros(S, np.float64)
        packed, grew = [], False
        for s_idx, pool in enumerate(pooled):
            ids = _np_box_ids(pool["z"], pool["x"], grid)
            order = np.argsort(ids, kind="stable")
            bounds = np.searchsorted(ids[order], np.arange(S + 1))
            counts = np.diff(bounds)
            peak = int(counts.max()) if len(ids) else 0
            need = self._slot_cap(int(peak * self._capacity_margin))
            if s_idx >= len(self._caps):
                self._caps.append(need)
            elif peak > self._caps[s_idx]:
                self._caps[s_idx] = max(need, self._slot_cap(peak))
                grew = True
            cap = self._caps[s_idx]
            buf = {
                "z": np.empty((S, cap), np.float32),
                "x": np.empty((S, cap), np.float32),
                "ux": np.zeros((S, cap), np.float32),
                "uy": np.zeros((S, cap), np.float32),
                "uz": np.zeros((S, cap), np.float32),
                "w": np.zeros((S, cap), np.float32),
                "alive": np.zeros((S, cap), bool),
            }
            # park dead padding at each slot's box centre (indices stay valid)
            buf["z"][:] = self._centers[box_of_slot, 0][:, None]
            buf["x"][:] = self._centers[box_of_slot, 1][:, None]
            for b in range(S):
                sel = order[bounds[b] : bounds[b + 1]]
                s, n = slot_of_box[b], len(sel)
                for k in _PKEYS:
                    buf[k][s, :n] = pool[k][sel]
                buf["alive"][s, :n] = True
                self._alive_by_box[b] += n
            packed.append(buf)
        if grew:
            self._interval_cache.clear()
            self._dispatched_key = None
        return packed

    def _slot_cap(self, lanes: int) -> int:
        """Slot capacity holding ``lanes`` particles.  Under the Pallas
        backend it is a whole number of kernel particle blocks, so the
        kernels' ``(slots, rows, 128)`` layout is a free reshape of the
        slot buffers (no padding copy per kernel call)."""
        cap = _round_up(lanes, self._capacity_round)
        if self.engine_backend == "pallas":
            cap = particle_blocking(cap, DEPOSIT_TILE)[1]
        return cap

    def _pack_initial(self, species, mig_cap):
        grid, S = self.grid, self.grid.n_boxes
        pooled = []
        for tpl in species:
            host = jax.device_get((tpl.z, tpl.x, tpl.ux, tpl.uy, tpl.uz, tpl.w, tpl.alive))
            z, x, ux, uy, uz, w, alive = (np.asarray(a) for a in host)
            keep = alive
            pooled.append(
                {
                    "z": z[keep], "x": x[keep], "ux": ux[keep],
                    "uy": uy[keep], "uz": uz[keep], "w": w[keep],
                }
            )
        packed = self._pack_pooled(pooled)
        for cap in self._caps:
            base = int(mig_cap) if mig_cap is not None else max(_MIN_MIG, cap // 8)
            self._mig_caps.append(self._init_mig_caps(base))
        tiles = np.zeros((S, 6, grid.box_nz, grid.box_nx), np.float32)
        return tiles, packed

    # ------------------------------------------------------------------
    # the fused interval program
    # ------------------------------------------------------------------
    def _interval_fn(self, n_steps: int) -> Callable:
        key = (n_steps, self._plan_key())
        if key in self._interval_cache:
            return self._interval_cache[key]

        grid, local_grid, halo = self.grid, self.local_grid, self.halo
        order, laser, dt = self.shape_order, self.laser, grid.dt
        comm, n_dev, bpd = self.comm, self.n_devices, self._bpd
        overlap = self.overlap
        engine_backend, interpret = self.engine_backend, self.interpret
        FRONTIER = (
            jnp.asarray(frontier_cell_mask(grid, halo, order)) if overlap else None
        )
        caps, qm = list(self._caps), list(self._qm)
        mig_caps = [dict(d) for d in self._mig_caps]
        offsets = self._offsets
        pair_caps = dict(self._pair_caps)
        CELL_MAP = jnp.asarray(self._cell_map)
        INT_MAP = jnp.asarray(self._int_map)
        STATICS = jnp.asarray(self._statics)
        ORIGINS = jnp.asarray(self._origins)
        CENTERS = jnp.asarray(self._centers)
        dv = np.float32(0.5 * grid.dz * grid.dx)
        bnz, bnx = grid.box_nz, grid.box_nx
        pnz, pnx = bnz + 2 * halo, bnx + 2 * halo
        BNSQ, PNSQ = bnz * bnx, pnz * pnx
        n_sp = len(qm)

        # directional strip geometry (static; identical for every box)
        strips = self._strips
        SEND_TO = jnp.asarray(strips.src_box[:, list(strips.opposite)].astype(np.int32))
        PASTE_SRC = jnp.asarray(_pad_tables(strips.paste_src))  # (8, m_max)
        PASTE_DST = jnp.asarray(_pad_tables(strips.paste_dst))
        FOLD_SRC = jnp.asarray(_pad_tables(strips.fold_src))  # (8, f_max)
        FOLD_DST = jnp.asarray(_pad_tables(strips.fold_dst))
        iz = (np.arange(bnz) + halo)[:, None]
        ix = (np.arange(bnx) + halo)[None, :]
        INT_IN_PAD = jnp.asarray((iz * pnx + ix).ravel().astype(np.int32))

        def to_particles(d: Dict[str, jax.Array], s: int) -> Particles:
            q, m = qm[s]
            return Particles(
                z=d["z"], x=d["x"], ux=d["ux"], uy=d["uy"], uz=d["uz"],
                w=d["w"], alive=d["alive"],
                q=jnp.float32(q), m=jnp.float32(m),
            )

        def exchange_ring(p: Particles, s: int, my_box, my_center):
            """Reference path: every pack rides the full ring (capacity-
            bounded all-to-all); every slot sees every leaver."""
            cap, mcap = caps[s], mig_caps[s][0]
            with jax.named_scope("emigrate"):
                new_box = grid.box_of_position(p.z, p.x)  # (bpd, cap) int32
                stay = p.alive & (new_box == my_box[:, None])
                emig = p.alive & ~stay
                demand = emig.sum(axis=1)  # per-slot, pre-capacity
                # compact leavers into the (mig_cap,) pack, destination-tagged
                eidx = jnp.argsort(jnp.where(emig, 0, 1), axis=1)[:, :mcap]
                ev = jnp.take_along_axis(emig, eidx, axis=1)
                edest = jnp.where(ev, jnp.take_along_axis(new_box, eidx, axis=1), -1)
                epack = {
                    k: jnp.take_along_axis(getattr(p, k), eidx, axis=1) for k in _PKEYS
                }
                dropped_e = emig.sum(axis=1) - ev.sum(axis=1)
            with jax.named_scope("exchange"):
                gdest = ring_all_gather(edest, BOX_AXIS).reshape(-1)  # (S*mcap,)
                gstack = ring_all_gather(
                    jnp.stack([epack[k] for k in _PKEYS], axis=-1), BOX_AXIS
                ).reshape(-1, len(_PKEYS))
                gpack = {k: gstack[:, ki] for ki, k in enumerate(_PKEYS)}
            with jax.named_scope("merge"):
                fields_rows = {k: getattr(p, k) for k in _PKEYS}
                out, dropped_c = merge_slots(
                    stay, fields_rows, my_box, my_center, gdest, gpack, cap
                )
            with jax.named_scope("diagnostics"):
                return out, out["alive"].sum(axis=1), dropped_e + dropped_c, demand

        def local_interval(tiles, species, slot_box, slot_of, t0):
            # local shapes: tiles (bpd, 6, bnz, bnx); species leaves
            # (bpd, cap); slot_box (bpd,) — the device's slice of the
            # mapping; slot_of (S,) — its host-provided inverse, replicated
            with jax.named_scope("pair_tables"):
                my_dev = jax.lax.axis_index(BOX_AXIS)
                my_origin = ORIGINS[slot_box]
                my_static = STATICS[slot_box]
                my_center = CENTERS[slot_box]
                my_box = slot_box

                if comm == "ring":
                    sb_all = ring_all_gather(slot_box, BOX_AXIS)  # (S,)
                    my_cmap = CELL_MAP[slot_box]  # (bpd, pn, pn)
                    cmap_all = CELL_MAP[sb_all]  # (S, pn, pn)
                    imap_all = INT_MAP[sb_all]  # (S, bnz, bnx)
                else:
                    # directional pair tables, once per interval: slot i sends
                    # its direction-j strip to the owner of SEND_TO[box, j];
                    # bucket the (slot, dir) pairs by ring offset, compacted to
                    # the host-computed per-offset capacity
                    send_to = SEND_TO[slot_box]  # (bpd, 8)
                    off_pair = (slot_of[send_to] // bpd - my_dev) % n_dev
                    flat_off = off_pair.reshape(-1)
                    flat_dst = send_to.reshape(-1)
                    pairs = {}
                    for o in offsets:
                        fl = flat_off == o
                        sel = jnp.argsort(jnp.where(fl, 0, 1))[: pair_caps[o]]
                        valid = fl[sel]
                        pairs[o] = (
                            (sel // 8).astype(jnp.int32),
                            (sel % 8).astype(jnp.int32),
                            jnp.where(valid, flat_dst[sel], -1).astype(jnp.int32),
                        )

            def strip_payloads(src_flat, table):
                """Per-offset (values, dst_box, dir) payloads gathered from
                ``src_flat`` (bpd, C, cells) through ``table`` (8, m)."""
                out = {}
                for o in offsets:
                    si, dj, dbox = pairs[o]
                    cells = table[dj]  # (K_o, m)
                    vals = jnp.take_along_axis(
                        src_flat[si],
                        jnp.clip(cells, 0, src_flat.shape[-1] - 1)[:, None, :],
                        axis=2,
                    )  # (K_o, C, m)
                    out[o] = (vals, dbox, dj)
                return out

            def strip_scatter(table):
                """Fold an arriving payload into a (C, bpd*PNSQ + 1) flat
                accumulator (last cell is the dump for padding/invalid)."""

                def fold(acc, o, arr):
                    vals, dbox, dj = arr
                    u = slot_of[dbox] - my_dev * bpd  # (K_o,)
                    cells = table[dj]  # (K_o, m)
                    ok = (
                        (dbox >= 0)[:, None]
                        & (cells >= 0)
                        & (u >= 0)[:, None]
                        & (u < bpd)[:, None]
                    )
                    idx = jnp.where(ok, u[:, None] * PNSQ + cells, bpd * PNSQ)
                    nc = vals.shape[1]
                    return acc.at[:, idx.reshape(-1)].add(
                        vals.transpose(1, 0, 2).reshape(nc, -1)
                    )

                return fold

            def halo_paste_neighbor(tiles):
                tflat = tiles.reshape(bpd, 6, BNSQ)
                own = (
                    jnp.arange(bpd, dtype=jnp.int32)[:, None] * PNSQ + INT_IN_PAD[None, :]
                ).reshape(-1)
                acc0 = (
                    jnp.zeros((6, bpd * PNSQ + 1), jnp.float32)
                    .at[:, own]
                    .add(tflat.transpose(1, 0, 2).reshape(6, -1), unique_indices=True)
                )
                acc = neighbor_reduce(
                    acc0, strip_payloads(tflat, PASTE_SRC), strip_scatter(PASTE_DST),
                    BOX_AXIS,
                )
                return (
                    acc[:, : bpd * PNSQ].reshape(6, bpd, pnz, pnx).transpose(1, 0, 2, 3)
                )

            def current_fold_neighbor(j3):
                jflat = j3.reshape(bpd, 3, PNSQ)
                acc0 = jnp.concatenate(
                    [
                        j3.transpose(1, 0, 2, 3).reshape(3, -1),
                        jnp.zeros((3, 1), jnp.float32),
                    ],
                    axis=1,
                )
                acc = neighbor_reduce(
                    acc0, strip_payloads(jflat, FOLD_SRC), strip_scatter(FOLD_DST),
                    BOX_AXIS,
                )
                return (
                    acc[:, : bpd * PNSQ].reshape(3, bpd, pnz, pnx).transpose(1, 0, 2, 3)
                )

            def exchange_neighbor(p: Particles, s: int):
                """Destination-aware directional packs: leavers binned by
                the ring offset of their destination's owner, one hop per
                offset, arrivals merged into the addressed slots."""
                cap = caps[s]
                with jax.named_scope("emigrate"):
                    new_box = grid.box_of_position(p.z, p.x)  # (bpd, cap)
                    stay = p.alive & (new_box == my_box[:, None])
                    emig = (p.alive & ~stay).reshape(-1)
                    nb_flat = new_box.reshape(-1)
                    e_off = (slot_of[nb_flat] // bpd - my_dev) % n_dev
                    fields_flat = {k: getattr(p, k).reshape(-1) for k in _PKEYS}
                    payloads, demand, packed = {}, [], 0
                    for o in offsets:
                        fl = emig & (e_off == o)
                        sel = jnp.argsort(jnp.where(fl, 0, 1))[: mig_caps[s][o]]
                        valid = fl[sel]
                        pk = jnp.stack([fields_flat[k][sel] for k in _PKEYS], axis=-1)
                        payloads[o] = (pk, jnp.where(valid, nb_flat[sel], -1))
                        demand.append(fl.sum())
                        packed = packed + valid.sum()
                    dropped_e = emig.sum() - packed  # off-plan or overflow
                with jax.named_scope("exchange"):
                    arrivals = neighbor_exchange(payloads, BOX_AXIS)
                    gstack = jnp.concatenate([arrivals[o][0] for o in offsets])
                    gdest = jnp.concatenate([arrivals[o][1] for o in offsets])
                    gpack = {k: gstack[:, ki] for ki, k in enumerate(_PKEYS)}
                with jax.named_scope("merge"):
                    fields_rows = {k: getattr(p, k) for k in _PKEYS}
                    out, dropped_c = merge_slots(
                        stay, fields_rows, my_box, my_center, gdest, gpack, cap
                    )
                with jax.named_scope("diagnostics"):
                    dropped = dropped_c.at[0].add(dropped_e)
                    alive = out["alive"].sum(axis=1)
                # with one offset this stack is a lone broadcast that folds
                # into the step's stack of the species' demands; XLA names
                # a folded instruction after the part behind the last
                # scope of its locations, so a scope here would rename it
                with (
                    jax.named_scope("emigrate")
                    if len(offsets) > 1
                    else contextlib.nullcontext()
                ):
                    return out, alive, dropped, jnp.stack(demand).astype(jnp.int32)

            def step(carry, i):
                tiles, species = carry
                with jax.named_scope("field_phase"):
                    t = t0 + i * dt
                # 1. halo paste: guard strips (neighbor) or interiors
                #    around the full ring (ring reference)
                with jax.named_scope("halo_paste"):
                    if comm == "ring":
                        ints_all = ring_all_gather(tiles, BOX_AXIS)  # (S, 6, bnz, bnx)
                        gF = (
                            jnp.zeros((6, grid.n_cells), jnp.float32)
                            .at[:, imap_all.reshape(-1)]
                            .set(
                                ints_all.transpose(1, 0, 2, 3).reshape(6, -1),
                                unique_indices=True,
                            )
                        )
                        padded = jnp.moveaxis(gF[:, my_cmap], 1, 0)  # (bpd, 6, pn, pn)
                    else:
                        padded = halo_paste_neighbor(tiles)
                # 2. particle phase on all owned slots at once
                sp_in = tuple(to_particles(d, s) for s, d in enumerate(species))
                if overlap:
                    # split-phase: advance everything, deposit the frontier
                    # only — the strips the fold sends are complete now
                    with jax.named_scope("particle_phase"):
                        sp2, jF, counts, flags = particle_phase_stacked_frontier(
                            padded, sp_in, my_origin, local_grid,
                            domain_grid=grid, shape_order=order,
                            frontier_mask=FRONTIER,
                        )
                        work = box_work_counters(counts, grid)
                    # 3. issue the fold collectives from the frontier
                    #    deposit, run the interior deposit inside their
                    #    dataflow window, fold arrivals in afterwards
                    if comm == "ring":
                        with jax.named_scope("current_fold"):
                            jF, (sp2, flags) = jax.lax.optimization_barrier(
                                (jF, (sp2, flags))
                            )
                            j_all = ring_all_gather(jF, BOX_AXIS)  # (S, 3, pn, pn)
                        with jax.named_scope("particle_phase"):
                            jI = particle_phase_stacked_interior(
                                sp2, my_origin, local_grid,
                                shape_order=order, frontier_flags=flags,
                            )
                        with jax.named_scope("current_fold"):
                            gJ = (
                                jnp.zeros((3, grid.n_cells), jnp.float32)
                                .at[:, cmap_all.reshape(-1)]
                                .add(j_all.transpose(1, 0, 2, 3).reshape(3, -1))
                            )
                            # interior deposits never reach another frame's
                            # view (they sit >= halo inside their own box), so
                            # the local tile add reproduces the global fold
                            jp = jnp.moveaxis(gJ[:, my_cmap], 1, 0) + jI
                    else:
                        with jax.named_scope("current_fold"):
                            handle, (sp2, flags) = neighbor_exchange_start(
                                strip_payloads(jF.reshape(bpd, 3, PNSQ), FOLD_SRC),
                                BOX_AXIS,
                                carry=(sp2, flags),
                            )
                        with jax.named_scope("particle_phase"):
                            jI = particle_phase_stacked_interior(
                                sp2, my_origin, local_grid,
                                shape_order=order, frontier_flags=flags,
                            )
                        with jax.named_scope("current_fold"):
                            j3 = jF + jI
                            acc = jnp.concatenate(
                                [
                                    j3.transpose(1, 0, 2, 3).reshape(3, -1),
                                    jnp.zeros((3, 1), jnp.float32),
                                ],
                                axis=1,
                            )
                            arrivals = neighbor_exchange_done(handle)
                            fold = strip_scatter(FOLD_DST)
                            for o in sorted(arrivals):
                                acc = fold(acc, o, arrivals[o])
                            jp = (
                                acc[:, : bpd * PNSQ]
                                .reshape(3, bpd, pnz, pnx)
                                .transpose(1, 0, 2, 3)
                            )
                else:
                    with jax.named_scope("particle_phase"):
                        if engine_backend == "pallas":
                            # slot-batched Pallas kernels: the balancer's work
                            # signal is the in-kernel executed-tile counters,
                            # not the host-derived box_work_counters formula
                            sp2, j3, counts, work = particle_phase_slots(
                                padded, sp_in, my_origin, local_grid,
                                domain_grid=grid, interpret=interpret,
                            )
                        else:
                            sp2, j3, counts = particle_phase_stacked(
                                padded, sp_in, my_origin, local_grid,
                                domain_grid=grid, shape_order=order,
                            )
                            work = box_work_counters(counts, grid)
                    # 3. current fold: overlapping deposit strips scatter-
                    #    add into each padded frame (strip form of
                    #    halo_fold_plan)
                    with jax.named_scope("current_fold"):
                        if comm == "ring":
                            j_all = ring_all_gather(j3, BOX_AXIS)  # (S, 3, pn, pn)
                            gJ = (
                                jnp.zeros((3, grid.n_cells), jnp.float32)
                                .at[:, cmap_all.reshape(-1)]
                                .add(j_all.transpose(1, 0, 2, 3).reshape(3, -1))
                            )
                            jp = jnp.moveaxis(gJ[:, my_cmap], 1, 0)  # (bpd, 3, pn, pn)
                        else:
                            jp = current_fold_neighbor(j3)
                # 4. field phase, keep interiors
                with jax.named_scope("field_phase"):
                    tiles2 = field_phase_stacked(
                        padded, jp, my_static, t, local_grid, halo, laser=laser
                    )
                # 5. emigration: destination-aware packs (or the full ring);
                #    the exchanges scope their own emigrate / exchange /
                #    merge / diagnostics parts
                new_species, alive, dropped = [], 0, 0
                demand = []
                ke = 0.0
                for s, p in enumerate(sp2):
                    if comm == "ring":
                        out, alive_s, dropped_s, demand_s = exchange_ring(
                            p, s, my_box, my_center
                        )
                    else:
                        out, alive_s, dropped_s, demand_s = exchange_neighbor(p, s)
                    new_species.append(out)
                    demand.append(demand_s)
                    with jax.named_scope("diagnostics"):
                        alive = alive + alive_s
                        dropped = dropped + dropped_s
                        ke = ke + jax.vmap(kinetic_energy, in_axes=(_P_AXES,))(
                            to_particles(out, s)
                        )
                with jax.named_scope("diagnostics"):
                    fe = dv * jnp.sum(tiles2.astype(jnp.float32) ** 2, axis=(1, 2, 3))
                    outs = {
                        "counts": counts,
                        "work": work,
                        "alive": alive.astype(jnp.int32),
                        "dropped": dropped.astype(jnp.int32),
                        "field_energy": fe,
                        "kinetic_energy": ke,
                        "emig_demand": jnp.stack(demand).astype(jnp.int32),
                    }
                return (tiles2, tuple(new_species)), outs

            (tiles, species), ys = jax.lax.scan(
                step, (tiles, species), jnp.arange(n_steps, dtype=jnp.float32)
            )
            return tiles, species, ys

        sp_tiles = P(BOX_AXIS, None, None, None)
        sp_part = P(BOX_AXIS, None)
        # structure from the host-known species list, not the pipeline tail
        # (reading the tail would barrier on in-flight dispatches)
        specs_species = tuple(
            {k: sp_part for k in ("alive",) + _PKEYS} for _ in self._qm
        )
        sp_hist = P(None, BOX_AXIS)
        specs_ys = {
            k: sp_hist
            for k in ("counts", "work", "alive", "dropped", "field_energy", "kinetic_energy")
        }
        specs_ys["emig_demand"] = P(None, None, BOX_AXIS)
        fn = jax.jit(
            jax.shard_map(
                local_interval,
                mesh=self.mesh,
                in_specs=(sp_tiles, specs_species, P(BOX_AXIS), P(), P()),
                out_specs=(sp_tiles, specs_species, specs_ys),
                # pallas_call has no varying-manual-axes rule; every output
                # spec here is explicit, so the check is inert
                check_vma=engine_backend != "pallas",
            ),
            donate_argnums=(0, 1),
        )
        self._interval_cache[key] = fn
        return fn

    # ------------------------------------------------------------------
    # host driver: one dispatch + one sync per interval piece
    # ------------------------------------------------------------------
    def run(self, n_steps: int) -> None:
        """Advance ``n_steps`` steps, one fused program per LB round (chunk
        boundaries stay aligned to ``lb_interval`` multiples, as the
        single-host fused driver does)."""
        interval = max(1, self.lb_interval)
        remaining = n_steps
        while remaining > 0:
            chunk = min(remaining, interval - (self.step_idx % interval))
            for piece in Simulation._chunk_pieces(chunk, interval):
                self._run_piece(piece)
            remaining -= chunk

    def step(self) -> Dict[str, float]:
        """Advance a single step (one-step program; prefer :meth:`run`).
        Under ``pipeline="async"`` the returned diagnostics reflect the
        last *harvested* round (one step behind the dispatch frontier)."""
        self._run_piece(1)
        lag = 1 if self.pipeline == "sync" else 2
        return {
            "step": self.step_idx,
            "alive": float(self._alive_by_box.sum()),
            "adopted": bool(
                self.history["lb_steps"]
                and self.history["lb_steps"][-1] >= self.step_idx - lag
            ),
        }

    def flush(self) -> None:
        """Drain the interval pipeline: harvest every in-flight round's
        history (feeding the balancer / straggler loop / pack controller)
        and commit any resulting adoption.  A no-op when nothing is in
        flight — ``pipeline="sync"`` harvests inside :meth:`_run_piece`."""
        while self._pipe.pending:
            self._harvest_one()

    def pipeline_stats(self) -> Dict:
        """Interval-pipeline accounting: the mode, rounds currently in
        flight, rounds harvested, the seconds the host spent *blocked* on
        device work (``host_blocked_s`` — dispatch + in-flight waits +
        history fetches; the numerator of the host-idle fraction
        ``benchmarks/bench_interval.py`` reports) and the host seconds
        spent with a round in flight (``overlapped_host_s`` — the balancer
        turnaround ``"async"`` hides behind device compute; ~0 under
        ``"sync"``)."""
        return {
            "pipeline": self.pipeline,
            "depth": self._pipe.depth,
            "pending": self._pipe.pending,
            "harvests": self._pipe.harvests,
            "host_blocked_s": self._pipe.host_blocked_s,
            "overlapped_host_s": self._pipe.overlapped_host_s,
            "host_syncs": self.host_syncs,
        }

    def _run_piece(self, n_steps: int) -> None:
        """Dispatch one interval piece under the current mapping, then
        harvest down to the pipeline's depth: immediately for ``"sync"``
        (depth 1 — the serial reference), behind one in-flight round for
        ``"async"`` (depth 2 — the previous round's history is fetched
        while this piece executes, and any adoption it triggers corrects
        the in-flight state one interval late)."""
        key = (n_steps, self._plan_key())
        cause = None if key in self._interval_cache else self._new_plan_cause(key)
        with jax.profiler.TraceAnnotation("interval_dispatch"), (
            jax.profiler.TraceAnnotation("interval_new_plan", cause=cause)
            if cause
            else contextlib.nullcontext()
        ):
            fn = self._interval_fn(n_steps)
            meta = {
                "n_steps": n_steps,
                "step_idx": self.step_idx,
                "lb_due": self.balancer.should_run(self.step_idx),
                # histories are slot-ordered under the *dispatch-time* mapping;
                # the harvester must not read them through a later slot_box
                # (nor credit their work through a later box->device mapping)
                "slot_box": self._slot_box.copy(),
                "mapping": self.balancer.mapping.copy(),
                "mig_keys": self._mig_keys(),
            }

            def program(state, slot_box_dev, slot_of_dev, t):
                tiles, species, ys = fn(state[0], state[1], slot_box_dev, slot_of_dev, t)
                return (tiles, species), ys

            self._pipe.enqueue(
                program,
                self._slot_box_dev,
                self._slot_of_dev,
                jnp.float32(self.t),
                meta=meta,
            )
        self._dispatched_key = key
        self.host_dispatches += 1
        self.step_idx += n_steps
        self.t += n_steps * self.grid.dt
        while self._pipe.pending >= self._pipe.depth:
            self._harvest_one()

    def _harvest_one(self) -> None:
        """Fetch the oldest in-flight round's history (the interval's ONLY
        device->host sync), fold it into the host bookkeeping, and run the
        balancer if that round opened an LB interval.  An adopted mapping
        is committed as a slot permutation on the pipeline's *tail* state
        — under ``"async"`` that is the in-flight round's output, so the
        correction lands one interval after the measurements it came
        from."""
        harvested = self._pipe.harvest()
        if harvested is None:
            return
        host, meta = harvested
        self.host_syncs += 1
        with jax.profiler.TraceAnnotation("history_decode"):
            n_steps = meta["n_steps"]
            sb = meta["slot_box"]  # (S,) box per slot at dispatch time
            n_boxes = self.grid.n_boxes
            work_box = np.empty((n_steps, n_boxes))
            work_box[:, sb] = np.asarray(host["work"], np.float64)
            counts_box = np.empty((n_steps, n_boxes))
            counts_box[:, sb] = np.asarray(host["counts"], np.float64)
            alive_box = np.empty((n_steps, n_boxes))
            alive_box[:, sb] = np.asarray(host["alive"], np.float64)
            self._alive_by_box = alive_box[-1]
            self.dropped_total += int(np.asarray(host["dropped"]).sum())
            self._adapt_mig(
                np.asarray(host["emig_demand"]),
                keys=meta["mig_keys"],
                step=meta["step_idx"],
            )
            self.history["field_energy"].extend(
                float(v) for v in np.asarray(host["field_energy"]).sum(axis=1)
            )
            self.history["kinetic_energy"].extend(
                float(v) for v in np.asarray(host["kinetic_energy"]).sum(axis=1)
            )

        if not meta["lb_due"]:
            return
        with jax.profiler.TraceAnnotation("lb_decide"):
            # row 0 is the round-boundary step — what per-step execution
            # would have fed the balancer
            self._observe_straggler(work_box[0], meta["mapping"])
            new_mapping = self.balancer.step(
                meta["step_idx"],
                work_box[0],
                box_coords=self.decomp.coords,
                box_bytes=self.decomp.box_bytes(counts_box[0]),
            )
            if new_mapping is not None:
                new_mapping = self._equalize(new_mapping, work_box[0])
                if self.comm == "neighbor":
                    new_mapping = locality_repair(
                        new_mapping,
                        work_box[0],
                        self._home_dev,
                        self.n_devices,
                        max_shift=self.locality_shift,
                    )
        if new_mapping is not None:
            self.balancer.mapping = new_mapping
            self.history["lb_steps"].append(meta["step_idx"])
            self._recommit(new_mapping)

    # ------------------------------------------------------------------
    # adoption: re-commit the sharding as a slot permutation
    # ------------------------------------------------------------------
    def _equalize(self, mapping: np.ndarray, costs: np.ndarray) -> np.ndarray:
        """Repair a mapping to exactly ``bpd`` boxes per device (no-op for
        the equal-count knapsack; needed for e.g. the sfc policy whose
        contiguous segments may be uneven)."""
        m = np.asarray(mapping, np.int64).copy()
        counts = np.bincount(m, minlength=self.n_devices)
        while counts.max() > self._bpd:
            src = int(np.argmax(counts))
            boxes = np.where(m == src)[0]
            b = boxes[np.argmin(costs[boxes])]  # cheapest box moves
            under = np.where(counts < self._bpd)[0]
            loads = np.array([costs[m == d].sum() for d in under])
            dst = int(under[np.argmin(loads)])
            m[b] = dst
            counts[src] -= 1
            counts[dst] += 1
        return m

    def apply_mapping(self, new_mapping) -> None:
        """Adopt an externally-decided distribution mapping (the shared
        commit/adoption API): update the balancer and re-commit the
        sharding.  The mapping must give every device exactly ``bpd``
        boxes (use the equal-count knapsack, or repair first).  In
        neighbour mode the exchange plan is rebuilt from the committed
        slots — a low-locality mapping stays correct, it just widens the
        directional offset set.  The pipeline is flushed first so the
        external adoption orders deterministically after every dispatched
        round."""
        self.flush()
        new = np.asarray(new_mapping, dtype=np.int64)
        if new.shape != (self.grid.n_boxes,) or new.min() < 0 or new.max() >= self.n_devices:
            raise ValueError("mapping must assign every box to a valid device slot")
        if np.any(np.bincount(new, minlength=self.n_devices) != self._bpd):
            raise ValueError(
                "sharded runtime mappings must give every device exactly "
                f"{self._bpd} boxes"
            )
        self.balancer.mapping = new
        self._recommit(new)

    def _recommit(self, new_mapping: np.ndarray) -> None:
        """Realize an adopted mapping as a slot permutation, applied on
        device (one gather program, no device->host transfer).  Incoming
        boxes fill freed slots in curve order, keeping slot order aligned
        with the locality layout.  The permutation is enqueued on the
        pipeline's tail state, so under ``pipeline="async"`` it corrects
        the in-flight round's output — landing one interval after the
        counters that motivated it, without a stall."""
        moved = int(np.count_nonzero(self._slot_of_box() // self._bpd != new_mapping))
        with jax.profiler.TraceAnnotation("recommit", boxes_moved=moved):
            S, bpd = self.grid.n_boxes, self._bpd
            old_slot_of_box = np.empty(S, np.int64)
            old_slot_of_box[self._slot_box] = np.arange(S)
            new_slot_box = -np.ones(S, np.int64)
            for d in range(self.n_devices):
                slots = np.arange(d * bpd, (d + 1) * bpd)
                # boxes staying on d keep their slots (they do not move at all)
                stay = [s for s in slots if new_mapping[self._slot_box[s]] == d]
                for s in stay:
                    new_slot_box[s] = self._slot_box[s]
                incoming = [
                    b
                    for b in np.where(new_mapping == d)[0]
                    if new_slot_box[old_slot_of_box[b]] != b
                ]
                incoming.sort(key=lambda b: self._curve[b])
                free = [s for s in slots if new_slot_box[s] < 0]
                for s, b in zip(free, incoming):
                    new_slot_box[s] = b
            assert (new_slot_box >= 0).all() and len(set(new_slot_box)) == S
            perm = old_slot_of_box[new_slot_box]

            self._pipe.correct(
                lambda state, p: self._reorder_fn(state[0], state[1], p),
                jnp.asarray(perm),
            )
            self._slot_box = new_slot_box
            slot_dev = jnp.asarray(new_slot_box.astype(np.int32))
            self._slot_box_dev = jax.device_put(
                slot_dev, state_shardings(slot_dev, self.mesh)
            )
            self._commit_slot_tables()
            if self.comm == "neighbor":
                old_offsets = self._offsets
                self._build_comm_plan()
                if self._offsets != old_offsets:
                    # keep learned pack capacities on surviving offsets; new
                    # offsets start from the capacity floor (demand-driven
                    # growth reacts within one interval if they run hot)
                    for s, d in enumerate(self._mig_caps):
                        self._mig_caps[s] = {
                            o: d.get(o, _MIN_MIG) for o in self._offsets
                        }
                    self._mig_idle = {
                        (s, o): v
                        for (s, o), v in self._mig_idle.items()
                        if o in self._offsets
                    }
            self.host_dispatches += 2  # the reorder program + the mapping commit

    # ------------------------------------------------------------------
    # capacity awareness (straggler mitigation hook)
    # ------------------------------------------------------------------
    def update_capacities(self, capacities: Optional[np.ndarray]) -> None:
        """Feed a per-device capacity vector into the knapsack and force
        the next LB round to rebalance against it (shared API with
        ``BoxRuntime``)."""
        self.balancer.set_capacities(capacities)
        self.balancer.force_rebalance()

    # ------------------------------------------------------------------
    # observability (diagnostic fetches; never on the hot path)
    # ------------------------------------------------------------------
    def n_slots(self) -> int:
        """Balancer work items this runtime places: one slot per box
        (the workload-agnostic ``BalancedRuntime`` surface)."""
        return self.grid.n_boxes

    def slot_costs(self) -> Optional[np.ndarray]:
        """Smoothed per-box in-situ work-counter costs as of the last LB
        round (``LoadBalancer.smoothed_costs``); ``None`` before it."""
        return self.balancer.smoothed_costs

    def total_alive(self) -> int:
        """Alive particles across all boxes and species, from the last
        fetched interval history (flushes the pipeline so that history is
        the last *dispatched* round; no extra device sync beyond it)."""
        self.flush()
        return int(self._alive_by_box.sum())

    def box_counts(self) -> np.ndarray:
        """Alive particles per box (all species), from the last interval
        (pipeline flushed first)."""
        self.flush()
        return self._alive_by_box.copy()

    def slot_occupancy(self) -> Dict[str, np.ndarray]:
        """The slot layout as it stands (pipeline flushed first), read from
        each device's shard of the particle buffers: per slot, the box it
        holds (``box``, ``(S,)``), the id of the device holding it
        (``device``, ``(S,)``), and per species its alive particles
        (``alive``, ``(n_species, S)``) and particle lanes (``lanes``,
        ``(n_species, S)``).  One device sync per shard; never on the hot
        path.  Lanes not holding an alive particle are the dead lanes every
        step's exchange still sorts."""
        self.flush()
        S = self.grid.n_boxes
        device = np.full(S, -1, np.int64)
        alive = np.zeros((len(self._qm), S), np.int64)
        lanes = np.zeros((len(self._qm), S), np.int64)
        for s, d in enumerate(self._species):
            for shard in d["alive"].addressable_shards:
                slots = np.arange(S)[shard.index[0]]
                device[slots] = shard.device.id
                alive[s, slots] = np.asarray(jnp.sum(shard.data, axis=1, dtype=jnp.int32))
                lanes[s, slots] = shard.data.shape[1]
        return {"box": self._slot_box.copy(), "device": device, "alive": alive, "lanes": lanes}

    # ------------------------------------------------------------------
    # recovery surface (see repro.dist.recovery)
    # ------------------------------------------------------------------
    def snapshot(self) -> Dict:
        """Minimal recoverable state at the last committed interval
        boundary, as a host pytree of numpy leaves in **box-major** layout
        (device-count independent): interior field tiles re-ordered to box
        id, pooled alive particles per species (box membership is implied
        by position), per-box counts, sim time/step, the committed
        mapping, balancer EWMA state, and the adaptive ``mig_cap`` tables.
        Flushes the pipeline first — an async in-flight round is *not*
        committed and never appears in a snapshot (the staleness
        contract's commit point)."""
        self.flush()
        inv = self._slot_of_box()  # slot of each box
        tiles = np.asarray(jax.device_get(self._tiles), np.float32)[inv]
        species_host = jax.device_get(self._species)
        species = []
        for d in species_host:
            alive = np.asarray(d["alive"], bool).reshape(-1)
            species.append(
                {
                    k: np.asarray(d[k], np.float32).reshape(-1)[alive]
                    for k in _PKEYS
                }
            )
        snap: Dict = {
            "tiles": tiles,
            "species": species,
            "counts": self._alive_by_box.copy(),
            "t": np.float64(self.t),
            "step_idx": np.int64(self.step_idx),
            "mapping": np.asarray(self.balancer.mapping, np.int64).copy(),
            "n_devices": np.int64(self.n_devices),
            "mig_caps": [
                {int(o): np.int64(c) for o, c in d.items()} for d in self._mig_caps
            ],
        }
        snap.update(snapshot_balancer(self.balancer))
        rng = getattr(self, "rng_key", None)
        if rng is not None:
            snap["rng_key"] = np.asarray(jax.device_get(rng))
        return snap

    def restore(self, snap: Dict) -> None:
        """Adopt a :meth:`snapshot` — possibly taken on a **different
        device count**.  The checkpointed per-box populations are
        re-knapsacked onto *this* runtime's mesh (the gate is bypassed,
        capacities are honoured, and in neighbour mode the mapping is
        locality-repaired exactly like an LB adoption), state is
        re-committed slot-major under the rebuilt plan, and the adaptive
        emigrant-pack capacities are restored conservatively: when the
        device count changed, each new offset's pack starts at the *sum*
        of the snapshot's learned capacities (per-pack demand concentrates
        when hops collapse; the adaptive controller trims the excess after
        ``mig_patience`` quiet intervals)."""
        grid, S = self.grid, self.grid.n_boxes
        tiles = np.asarray(snap["tiles"], np.float32)
        if tiles.shape != (S, 6, grid.box_nz, grid.box_nx):
            raise ValueError(
                f"snapshot tiles {tiles.shape} do not fit this grid "
                f"({S} boxes of 6x{grid.box_nz}x{grid.box_nx})"
            )
        if len(snap["species"]) != len(self._qm):
            raise ValueError("snapshot species count does not match this problem")
        self.flush()
        restore_balancer(self.balancer, snap, n_boxes=S)
        # re-knapsack the checkpointed populations onto THIS mesh
        counts = np.nan_to_num(np.asarray(snap["counts"], np.float64), nan=0.0)
        costs = np.maximum(counts, 0.0)
        mapping = np.asarray(
            self.balancer.propose(costs, box_coords=self.decomp.coords), np.int64
        )
        mapping = self._equalize(mapping, costs)
        if self.comm == "neighbor":
            mapping = locality_repair(
                mapping, costs, self._home_dev, self.n_devices,
                max_shift=self.locality_shift,
            )
        self.balancer.mapping = mapping
        self.balancer.force_rebalance()
        self._slot_box = self._slots_from_mapping(mapping)
        self._build_comm_plan()
        # emigrant packs: exact per-offset restore on the same device
        # count; concentrate (sum) + floor when the mesh shrank or grew
        saved = snap.get("mig_caps")
        same_mesh = int(snap.get("n_devices", self.n_devices)) == self.n_devices
        if saved is not None and len(saved) == len(self._mig_caps):
            for s, d in enumerate(saved):
                table = {int(o): int(c) for o, c in d.items()}
                base = max(_MIN_MIG, self._caps[s] // 8) if s < len(self._caps) else _MIN_MIG
                if same_mesh:
                    self._mig_caps[s] = {
                        o: max(base, table.get(o, base)) for o in self._mig_keys()
                    }
                else:
                    pooled_cap = max(base, sum(table.values()))
                    self._mig_caps[s] = {o: pooled_cap for o in self._mig_keys()}
            self._mig_idle = {}
        pooled = [
            {k: np.asarray(sp[k], np.float32) for k in _PKEYS}
            for sp in snap["species"]
        ]
        packed = self._pack_pooled(pooled)
        self._commit_state(tiles[self._slot_box], packed)
        self.t = float(snap["t"])
        self.step_idx = int(snap["step_idx"])
        if "rng_key" in snap:
            self.rng_key = jnp.asarray(snap["rng_key"])

    @property
    def fields(self) -> Fields:
        """Global field state assembled from the sharded slot tiles (the
        pipeline is flushed first so pending adoptions have committed and
        ``slot_box`` matches the fetched tiles)."""
        self.flush()
        grid = self.grid
        tiles = np.asarray(jax.device_get(self._tiles))  # (S, 6, bnz, bnx)
        out = np.zeros((6, grid.nz, grid.nx), np.float32)
        for s, b in enumerate(self._slot_box):
            bz, bx = grid.box_coords[b]
            out[
                :,
                bz * grid.box_nz : (bz + 1) * grid.box_nz,
                bx * grid.box_nx : (bx + 1) * grid.box_nx,
            ] = tiles[s]
        return Fields(*(jnp.asarray(c) for c in out))

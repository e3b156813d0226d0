"""Device-resident multi-step PIC execution engine.

The paper's central finding is that in-situ cost assessment must be cheap
relative to the physics (arXiv 2104.11385 §2.2): the balancer only consumes
costs every ``lb_interval`` steps, so nothing in the hot loop should touch
the host more often than that.  This module provides the pure, jitted side
of that contract:

  * :func:`particle_phase` / :func:`field_phase` — the two halves of one
    PIC step (gather+push+move+deposit, then the Maxwell leapfrog with
    laser/sponge).  They are exposed separately because the distributed box
    runtime (``repro.dist.box_runtime``) must interleave a cross-box
    current-halo exchange between them; both accept a *local* grid plus an
    ``origin``/``domain_grid`` so the same physics runs on a halo-padded
    per-box tile as on the global grid.
  * :func:`build_step_body` — one PIC step as a pure function
    ``(fields, species, t) -> (fields, species, StepOutputs)``, composed
    from the two phases.  All per-box accounting (particle counts,
    executed-work counters) is computed device-side inside the body; the
    Pallas path threads the in-kernel counters straight out of
    ``repro.kernels`` instead of recomputing them.
  * :func:`make_interval_fn` — wraps the step body in a ``jax.lax.scan``
    over ``n_steps`` steps with **donated** field/particle buffers
    (``donate_argnums``), so the interval runs as one XLA computation with
    no per-step dispatch, no per-step buffer copies, and no host transfer.
    Per-step counts, work counters and scalar diagnostics come back stacked
    into device-side history buffers of shape ``(n_steps, ...)`` — one
    fetch delivers the whole interval.
  * :class:`IntervalPipeline` — interval programs as **re-enqueueable
    closures**: the pipeline owns the rotating state-buffer chain, so the
    host can enqueue round *k+1* while round *k*'s history is still in
    flight (jax async dispatch keeps the device saturated) and fetch *k*'s
    history afterwards, hiding the balancer's host work behind device
    compute.  Donation stays safe because the pipeline is the only owner
    of the state futures — round *k*'s donated outputs are consumed
    exclusively by round *k+1*'s enqueue (the A/B buffer rotation), never
    by a host fetch racing the in-flight round.

The host-side driver that owns the LoadBalancer / VirtualCluster bookkeeping
lives in ``repro.pic.stepper``; the distributed runtimes (``repro.dist``)
reuse the same scanned body and drive :class:`IntervalPipeline` behind
their ``pipeline="sync"|"async"`` flag.
"""
from __future__ import annotations

import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Deque, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .deposition import box_particle_counts, box_work_counters, deposit_current
from .fields import Fields, apply_sponge, field_energy, step_b_half, step_e
from .grid import Grid2D
from .particles import (
    Particles,
    advance_positions,
    boris_push,
    gather_fields,
    kinetic_energy,
)

__all__ = [
    "StepOutputs",
    "particle_phase",
    "field_phase",
    "particle_phase_stacked",
    "particle_phase_stacked_frontier",
    "particle_phase_stacked_interior",
    "field_phase_stacked",
    "build_step_body",
    "make_interval_fn",
    "IntervalPipeline",
]


class StepOutputs(NamedTuple):
    """Per-step device-side accounting emitted by the step body.

    Under :func:`make_interval_fn` each leaf gains a leading ``(n_steps,)``
    axis (the scan's stacked ys) — the interval's history buffers.
    """

    counts: jax.Array  # (n_boxes,) f32 — alive particles per box
    work: jax.Array  # (n_boxes,) f32 — executed work units (in-kernel counters)
    field_energy: jax.Array  # scalar f32
    kinetic_energy: jax.Array  # scalar f32
    dropped: jax.Array  # scalar i32 — particles lost to the bin capacity guard


def particle_phase(
    fields: Fields,
    species: Tuple[Particles, ...],
    grid: Grid2D,
    shape_order: int = 3,
    *,
    domain_grid: Optional[Grid2D] = None,
    origin: Tuple = (0.0, 0.0),
):
    """Gather + Boris push + move + current deposit for all species.

    ``grid`` is the grid the *fields* live on — the global grid for the
    single-host engine, or a halo-padded per-box tile in the distributed
    runtime.  ``origin`` is the physical position of ``grid``'s cell (0, 0)
    in the domain frame (particles keep domain-global positions so box
    migration never rebases coordinates), and ``domain_grid`` bounds the
    kill-at-boundary check (defaults to ``grid``).

    Returns ``(species', (jx, jy, jz), counts)`` with ``counts`` the alive
    particles per box of ``grid`` — for a padded tile whose box is the whole
    tile this is a 1-vector holding the box's population.
    """
    dom = grid if domain_grid is None else domain_grid
    oz, ox = origin
    shifted = not (isinstance(oz, float) and isinstance(ox, float) and oz == 0.0 and ox == 0.0)
    jx = jnp.zeros(grid.shape, jnp.float32)
    jy = jnp.zeros(grid.shape, jnp.float32)
    jz = jnp.zeros(grid.shape, jnp.float32)
    counts = jnp.zeros(grid.n_boxes, jnp.float32)
    out_species = []
    for p in species:
        z_loc = p.z - oz if shifted else p.z
        x_loc = p.x - ox if shifted else p.x
        eb = gather_fields(fields, z_loc, x_loc, grid, shape_order)
        p = advance_positions(boris_push(p, eb, grid.dt), dom, grid.dt)
        out_species.append(p)
        p_loc = p._replace(z=p.z - oz, x=p.x - ox) if shifted else p
        jx_, jy_, jz_ = deposit_current(p_loc, grid, shape_order)
        jx, jy, jz = jx + jx_, jy + jy_, jz + jz_
        counts = counts + box_particle_counts(p_loc, grid)
    return tuple(out_species), (jx, jy, jz), counts


def field_phase(
    fields: Fields,
    j,
    grid: Grid2D,
    *,
    sponge: Optional[jax.Array] = None,
    laser=None,
    t=0.0,
    laser_profile: Optional[jax.Array] = None,
) -> Fields:
    """Maxwell leapfrog (B half, E full, B half) + laser injection + sponge.

    ``laser_profile`` selects the offset-aware injection path (a fixed
    spatial profile times a time-dependent scalar — see
    ``LaserAntenna.inject_profile``) used by per-box tiles whose frame
    differs from the global grid; without it the antenna injects on its
    global row as before.
    """
    fields = step_b_half(fields, grid)
    fields = step_e(fields, j, grid)
    fields = step_b_half(fields, grid)
    if laser is not None:
        if laser_profile is None:
            fields = laser.inject(fields, grid, t)
        else:
            fields = laser.inject_profile(fields, laser_profile, grid, t)
    if sponge is not None:
        fields = apply_sponge(fields, sponge)
    return fields


def particle_phase_stacked(
    tiles6: jax.Array,
    species: Tuple[Particles, ...],
    origins: jax.Array,
    local_grid: Grid2D,
    *,
    domain_grid: Grid2D,
    shape_order: int = 3,
):
    """Slot-batched :func:`particle_phase`: many padded box tiles at once.

    The collective-aware variant used by ``repro.dist.sharded_runtime``:
    each device owns a stack of box *slots* and advances all of them in one
    vmapped call between collectives, instead of one jit dispatch per box
    (``BoxRuntime``).  Inputs carry a leading slot axis — ``tiles6`` is
    ``(slots, 6, pnz, pnx)``, ``origins`` is ``(slots, 2)`` (physical
    position of each tile's cell ``(0, 0)``), and every ``Particles`` leaf
    is ``(slots, cap)`` except the scalar ``q``/``m``.

    Returns ``(species', j3, counts)`` with ``j3`` the stacked
    ``(slots, 3, pnz, pnx)`` per-tile deposits (still un-folded — the
    caller owns the cross-box current fold) and ``counts`` the ``(slots,)``
    alive-particle counts, summed over species.
    """

    def one(tile6, sp, origin):
        sp2, (jx, jy, jz), counts = particle_phase(
            Fields(*tile6),
            sp,
            local_grid,
            shape_order,
            domain_grid=domain_grid,
            origin=(origin[0], origin[1]),
        )
        return sp2, jnp.stack([jx, jy, jz]), counts[0]

    sp_axes = tuple(
        Particles(z=0, x=0, ux=0, uy=0, uz=0, w=0, alive=0, q=None, m=None)
        for _ in species
    )
    return jax.vmap(one, in_axes=(0, sp_axes, 0))(tiles6, species, origins)


def _frontier_flag(p: Particles, origin, grid: Grid2D, mask: jax.Array) -> jax.Array:
    """Whether each particle's post-move cell lies on the frontier.

    ``mask`` is the padded-tile bool map of ``repro.pic.boxes.
    frontier_cell_mask``; the cell lookup is clipped to the tile so a
    particle observed outside it (mid-migration extremes, parked dead
    padding) classifies through the boundary cells — which are always
    frontier by construction.
    """
    cz = jnp.clip((p.z - origin[0]) / grid.dz, 0.0, grid.nz - 1).astype(jnp.int32)
    cx = jnp.clip((p.x - origin[1]) / grid.dx, 0.0, grid.nx - 1).astype(jnp.int32)
    return mask[cz, cx]


def particle_phase_stacked_frontier(
    tiles6: jax.Array,
    species: Tuple[Particles, ...],
    origins: jax.Array,
    local_grid: Grid2D,
    *,
    domain_grid: Grid2D,
    shape_order: int = 3,
    frontier_mask: jax.Array,
):
    """Frontier half of the split-phase step: advance everything, deposit
    only what the halo exchange depends on.

    Same advance (gather + Boris push + move) as
    :func:`particle_phase_stacked` for **all** particles — the split never
    recomputes the push — but the current deposit masks to particles whose
    post-move cell is on the frontier (``frontier_mask``, from
    ``repro.pic.boxes.frontier_cell_mask``): exactly the deposits the fold
    strips can see.  Masking zeroes the deposit coefficient (an exact 0.0
    contribution), so the returned ``j3`` equals the monolithic deposit
    bitwise on every strip-sent cell — the strip collectives can be issued
    from it immediately, before any interior deposit work.

    Returns ``(species', j3_frontier, counts, frontier_flags)``:
    ``species'``/``counts`` are identical to the monolithic pass (counts
    cover all alive particles — the in-situ cost assessment is
    unchanged); ``frontier_flags`` is one ``(slots, cap)`` bool array per
    species for :func:`particle_phase_stacked_interior` to deposit the
    exact complement.
    """
    dom = domain_grid

    def one(tile6, sp, origin):
        fields = Fields(*tile6)
        jx = jnp.zeros(local_grid.shape, jnp.float32)
        jy = jnp.zeros(local_grid.shape, jnp.float32)
        jz = jnp.zeros(local_grid.shape, jnp.float32)
        counts = jnp.zeros(local_grid.n_boxes, jnp.float32)
        out_species, flags = [], []
        for p in sp:
            z_loc, x_loc = p.z - origin[0], p.x - origin[1]
            eb = gather_fields(fields, z_loc, x_loc, local_grid, shape_order)
            p = advance_positions(boris_push(p, eb, local_grid.dt), dom, local_grid.dt)
            out_species.append(p)
            on_frontier = _frontier_flag(p, origin, local_grid, frontier_mask)
            flags.append(on_frontier)
            p_loc = p._replace(z=p.z - origin[0], x=p.x - origin[1])
            jx_, jy_, jz_ = deposit_current(
                p_loc._replace(alive=p_loc.alive & on_frontier),
                local_grid,
                shape_order,
            )
            jx, jy, jz = jx + jx_, jy + jy_, jz + jz_
            counts = counts + box_particle_counts(p_loc, local_grid)
        return (
            tuple(out_species),
            jnp.stack([jx, jy, jz]),
            counts[0],
            tuple(flags),
        )

    sp_axes = tuple(
        Particles(z=0, x=0, ux=0, uy=0, uz=0, w=0, alive=0, q=None, m=None)
        for _ in species
    )
    # keep q/m scalar on the way out (out_axes=None), so the advanced
    # species feed straight into particle_phase_stacked_interior's
    # unbatched-charge vmap axes
    return jax.vmap(
        one,
        in_axes=(0, sp_axes, 0),
        out_axes=(sp_axes, 0, 0, tuple(0 for _ in species)),
    )(tiles6, species, origins)


def particle_phase_stacked_interior(
    species: Tuple[Particles, ...],
    origins: jax.Array,
    local_grid: Grid2D,
    *,
    shape_order: int = 3,
    frontier_flags: Tuple[jax.Array, ...],
):
    """Interior half of the split-phase step: the complement deposit.

    Takes the **already advanced** species and per-species frontier flags
    from :func:`particle_phase_stacked_frontier` (no physics is recomputed)
    and deposits the particles the frontier pass masked out.  By
    construction of ``frontier_cell_mask`` these deposits cannot touch any
    strip-sent cell, so this entire pass is data-independent of the strip
    collectives — the compute window the overlap schedules them behind.
    ``j3_frontier + j3_interior`` matches the monolithic deposit to f32
    rounding (the split only reorders the per-cell sum).
    """

    def one(sp, origin, fl):
        jx = jnp.zeros(local_grid.shape, jnp.float32)
        jy = jnp.zeros(local_grid.shape, jnp.float32)
        jz = jnp.zeros(local_grid.shape, jnp.float32)
        for p, on_frontier in zip(sp, fl):
            p_loc = p._replace(z=p.z - origin[0], x=p.x - origin[1])
            jx_, jy_, jz_ = deposit_current(
                p_loc._replace(alive=p_loc.alive & ~on_frontier),
                local_grid,
                shape_order,
            )
            jx, jy, jz = jx + jx_, jy + jy_, jz + jz_
        return jnp.stack([jx, jy, jz])

    sp_axes = tuple(
        Particles(z=0, x=0, ux=0, uy=0, uz=0, w=0, alive=0, q=None, m=None)
        for _ in species
    )
    flag_axes = tuple(0 for _ in species)
    return jax.vmap(one, in_axes=(sp_axes, 0, flag_axes))(
        species, origins, frontier_flags
    )


def field_phase_stacked(
    tiles6: jax.Array,
    j3: jax.Array,
    static2: jax.Array,
    t,
    local_grid: Grid2D,
    halo: int,
    *,
    laser=None,
) -> jax.Array:
    """Slot-batched :func:`field_phase` on padded tiles, keeping interiors.

    ``tiles6``/``j3`` are ``(slots, 6|3, pnz, pnx)`` padded E,B / folded J
    tiles; ``static2`` is ``(slots, 2, pnz, pnx)`` holding each slot's
    sponge mask and laser injection profile (``LaserAntenna.profile``
    sliced per box).  Returns the advanced ``(slots, 6, bnz, bnx)``
    interiors — with ``halo >= 4`` the three one-cell-deep leapfrog
    sub-updates never contaminate the interior, so the result matches the
    global solver to f32 rounding (same argument as ``BoxRuntime``).
    """

    def one(tile6, j, static):
        f = field_phase(
            Fields(*tile6),
            tuple(j),
            local_grid,
            sponge=static[0],
            laser=laser,
            t=t,
            laser_profile=static[1],
        )
        return jnp.stack(f)[:, halo:-halo, halo:-halo]

    return jax.vmap(one)(tiles6, j3, static2)


def build_step_body(
    grid: Grid2D,
    *,
    shape_order: int = 3,
    sponge: Optional[jax.Array] = None,
    laser=None,
    use_pallas: bool = False,
    pallas_cap: Optional[int] = None,
    interpret: bool,
) -> Callable:
    """Build the pure single-step body (not jitted — compose freely).

    ``interpret`` runs the Pallas kernels (``use_pallas``) in the
    interpreter; callers pass the value resolved for their backend
    (``repro.kernels.ops.default_interpret``).

    Returns ``step(fields, species, t) -> (fields, species, StepOutputs)``.
    """
    if use_pallas:
        if shape_order != 3:
            raise ValueError("the Pallas kernels implement order-3 shapes only")
        if pallas_cap is None:
            raise ValueError("use_pallas=True requires pallas_cap")
        from ..kernels import ops as kops

    def step(fields: Fields, species: Tuple[Particles, ...], t):
        dt = grid.dt
        jx = jnp.zeros(grid.shape, jnp.float32)
        jy = jnp.zeros(grid.shape, jnp.float32)
        jz = jnp.zeros(grid.shape, jnp.float32)
        counts = jnp.zeros(grid.n_boxes, jnp.float32)
        dropped = jnp.int32(0)
        if use_pallas:
            work = jnp.zeros(grid.n_boxes, jnp.float32)
            new_species = []
            for p in species:
                p2, (jx_, jy_, jz_), counters, counts_b, nd = kops.pic_substep_body(
                    fields, p, grid=grid, dt=dt, cap=pallas_cap, interpret=interpret
                )
                new_species.append(p2)
                jx, jy, jz = jx + jx_, jy + jy_, jz + jz_
                counts = counts + counts_b.astype(jnp.float32)
                work = work + counters.astype(jnp.float32)
                # the bin_particles capacity guard silently truncates a box
                # beyond cap; those particles leave the simulation and must
                # reach the runtime's dropped_total conservation accounting
                dropped = dropped + nd
            species = tuple(new_species)
        else:
            # push + move + deposit all species with E^n, B^n
            species, (jx, jy, jz), counts = particle_phase(
                fields, species, grid, shape_order
            )
            work = box_work_counters(counts, grid)
        fields = field_phase(
            fields, (jx, jy, jz), grid, sponge=sponge, laser=laser, t=t
        )
        out = StepOutputs(
            counts=counts,
            work=work,
            field_energy=field_energy(fields, grid),
            kinetic_energy=sum(kinetic_energy(p) for p in species),
            dropped=dropped,
        )
        return fields, species, out

    return step


def make_interval_fn(step_body: Callable, grid: Grid2D) -> Callable:
    """Fuse ``n_steps`` applications of ``step_body`` into one jitted scan.

    Returns ``interval(fields, species, t0, n_steps) ->
    (fields, species, StepOutputs)`` where the outputs carry a leading
    ``(n_steps,)`` history axis.  ``n_steps`` is static (one compile per
    distinct chunk length — the driver uses at most the LB interval plus a
    remainder).  The incoming field/particle buffers are donated: XLA
    updates them in place instead of copying every step.
    """
    dt = grid.dt

    def interval(fields: Fields, species, t0, n_steps: int):
        def body(carry, i):
            f, s = carry
            f, s, out = step_body(f, s, t0 + i * dt)
            return (f, s), out

        (fields_, species_), outs = jax.lax.scan(
            body, (fields, species), jnp.arange(n_steps, dtype=jnp.float32)
        )
        return fields_, species_, outs

    return jax.jit(interval, static_argnames=("n_steps",), donate_argnums=(0, 1))


class IntervalPipeline:
    """Interval programs as re-enqueueable closures over a rotating state.

    The serialization the async LB pipeline removes: after dispatching the
    interval program for round *k*, the host blocks on the history fetch,
    runs the balancer, commits the next mapping — and only then enqueues
    round *k+1*, leaving the device idle for the whole host turnaround
    (and the host idle for the whole device turn).  This class
    double-buffers that loop: it owns the state-buffer chain (the *only*
    reference to the donated buffers — that exclusivity is what makes
    donation safe while a round is in flight), so the driver can

      1. :meth:`enqueue` round *k+1* immediately under the current mapping
         (the dispatch runs on the pipeline's worker thread, so the driver
         is not blocked even on backends whose jit dispatch executes
         synchronously — e.g. multi-device ``shard_map`` programs on
         XLA:CPU; on accelerators jax's own async dispatch stacks on top),
      2. :meth:`harvest` round *k*'s stacked history while *k+1* executes
         (the wait + ``device_get`` accumulate in :attr:`host_blocked_s`),
      3. apply any resulting state transformation (e.g. the stale-mapping
         slot permutation) with :meth:`correct` — enqueued behind the
         in-flight round, so it lands between rounds *k+1* and *k+2*
         without a stall.

    ``depth`` bounds the rounds in flight: 1 reproduces fully synchronous
    stepping (inline dispatch, harvest immediately — the executable
    reference; no worker thread involved), 2 is the double-buffered
    pipeline.  Per-round metadata (the dispatch-time mapping, step index,
    whether an LB round is due) rides the queue so the harvester
    interprets each history under the placement it was *dispatched* with,
    not the one current at fetch time.

    Accounting: :attr:`host_blocked_s` is every second the driver thread
    spent waiting on device work (inline dispatch, in-flight waits, the
    history fetch); :attr:`overlapped_host_s` is the driver-side time
    spent *between* pipeline calls while a round was in flight — the
    balancer turnaround the pipeline hides (≈0 under depth 1, the whole
    LB turn under depth 2).  ``benchmarks/bench_interval.py`` turns both
    into the sync-vs-async comparison.
    """

    def __init__(self, state: Any, *, depth: int = 2):
        if depth < 1:
            raise ValueError("pipeline depth must be >= 1")
        self.depth = depth
        self._state = state
        self._inflight: Deque[Tuple[Any, Any]] = deque()
        # all dispatches ride one worker so they execute in enqueue order
        # and the state chain is only ever touched by one thread at a time
        self._exec = (
            ThreadPoolExecutor(max_workers=1, thread_name_prefix="interval-pipeline")
            if depth > 1
            else None
        )
        #: seconds the driver thread spent blocked on device work
        self.host_blocked_s = 0.0
        #: driver-side seconds spent between pipeline calls with a round in
        #: flight — host work hidden behind device compute
        self.overlapped_host_s = 0.0
        #: rounds harvested (each one device->host sync)
        self.harvests = 0
        self._resume_t: Optional[float] = None
        self._correct_err: Optional[BaseException] = None

    # -- overlap accounting: the window between returning control to the
    # -- driver (with work in flight) and the driver's next pipeline call
    def _absorb_overlap(self) -> None:
        if self._resume_t is not None:
            self.overlapped_host_s += time.perf_counter() - self._resume_t
            self._resume_t = None

    def _mark_resume(self) -> None:
        self._resume_t = time.perf_counter() if self._inflight else None

    def _check_correction(self) -> None:
        """Surface an exception a worker-side :meth:`correct` raised.
        Corrections cannot block on their own future (that would stall the
        pipeline behind the in-flight round on synchronous-dispatch
        backends), so failures are captured on the worker and re-raised at
        the next pipeline call — before the caller can act on state the
        correction never produced."""
        if self._correct_err is not None:
            err, self._correct_err = self._correct_err, None
            raise RuntimeError("enqueued pipeline correction failed") from err

    @property
    def state(self) -> Any:
        """The tail of the buffer chain: the state the *next* enqueue will
        consume.  Waits for any in-flight dispatches first (counted in
        :attr:`host_blocked_s`); prefer :meth:`harvest` for histories."""
        if self._exec is not None:
            self._absorb_overlap()
            t0 = time.perf_counter()
            self._exec.submit(lambda: None).result()  # barrier: drain dispatches
            self.host_blocked_s += time.perf_counter() - t0
            self._check_correction()
            self._mark_resume()
        return self._state

    @property
    def pending(self) -> int:
        """Rounds enqueued but not yet harvested."""
        return len(self._inflight)

    @property
    def full(self) -> bool:
        """True when another enqueue would exceed ``depth`` rounds in
        flight (the driver must harvest first)."""
        return len(self._inflight) >= self.depth

    def _dispatch(self, program: Callable, args: Tuple) -> Any:
        self._state, history = program(self._state, *args)
        return history

    def enqueue(self, program: Callable, *args, meta: Any = None) -> None:
        """Dispatch ``program(state, *args) -> (state', history)`` on the
        current tail state — inline under depth 1, on the worker thread
        otherwise (non-blocking for the driver).  The history handle and
        ``meta`` join the in-flight queue and come back, in dispatch
        order, from :meth:`harvest`."""
        if self.full:
            raise RuntimeError(
                f"pipeline full ({self.depth} rounds in flight); harvest first"
            )
        self._check_correction()
        self._absorb_overlap()
        t0 = time.perf_counter()
        if self._exec is None:
            history = self._dispatch(program, args)
        else:
            history = self._exec.submit(self._dispatch, program, args)
        self.host_blocked_s += time.perf_counter() - t0
        self._inflight.append((history, meta))
        self._mark_resume()

    def correct(self, fn: Callable, *args) -> None:
        """Replace the tail state with ``fn(state, *args)`` — an enqueued,
        non-blocking, on-device transformation (the async driver's
        stale-mapping slot permutation).  Applies after every round already
        in flight and before anything enqueued later."""
        if self._exec is None:
            self._state = fn(self._state, *args)
        else:

            def apply():
                try:
                    self._state = fn(self._state, *args)
                except BaseException as e:  # surfaced by _check_correction
                    self._correct_err = e

            self._exec.submit(apply)

    def harvest(self) -> Optional[Tuple[Any, Any]]:
        """Fetch the oldest in-flight round's history (one device->host
        sync) and return ``(host_history, meta)``; ``None`` when nothing is
        in flight.  The wait + fetch accumulate in :attr:`host_blocked_s`
        — under ``depth >= 2`` the balancer work that follows overlaps the
        next round's device compute, which is the pipeline's win.  In a
        profile they are the host spans ``harvest_wait`` (the round's
        dispatch and device work) and ``harvest_fetch`` (the copy)."""
        if not self._inflight:
            return None
        self._absorb_overlap()
        history, meta = self._inflight.popleft()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("harvest_wait"):
            if isinstance(history, Future):
                history = history.result()
            # queue the copies before the wait, as a bare device_get
            # would: they start as soon as the runtime marks the round done
            for leaf in jax.tree_util.tree_leaves(history):
                if isinstance(leaf, jax.Array):
                    leaf.copy_to_host_async()
            jax.block_until_ready(history)
        with jax.profiler.TraceAnnotation("harvest_fetch"):
            host = jax.device_get(history)
        self.host_blocked_s += time.perf_counter() - t0
        # every task enqueued before this round's dispatch has run by now,
        # so a failed correction preceding it is visible here
        self._check_correction()
        self.harvests += 1
        self._mark_resume()
        return host, meta

    def drain(self) -> list:
        """Harvest every round still in flight, in dispatch order, and
        return the ``(host_history, meta)`` pairs.  Afterwards nothing is
        in flight and :attr:`state` is the committed tail — the consistent
        cut a checkpoint snapshot needs (the staleness contract's commit
        point: an un-harvested round is *not* committed and never appears
        in a snapshot)."""
        out = []
        while self._inflight:
            out.append(self.harvest())
        return out

    def reset(self, state: Any) -> None:
        """Replace the buffer chain with ``state`` — the restore hook.
        Refuses while rounds are in flight (drain first): swapping the
        state under an in-flight dispatch would race the worker and leak
        the donated chain."""
        if self._inflight:
            raise RuntimeError(
                f"cannot reset with {len(self._inflight)} rounds in flight; drain first"
            )
        if self._exec is not None:
            self._exec.submit(lambda: None).result()  # barrier: idle the worker
            self._check_correction()
        self._state = state
        self._resume_t = None

    def close(self) -> None:
        """Release the worker thread (after draining any queued
        dispatches).  Long-lived drivers that build many pipelines should
        call this — or just drop the last reference; the worker also exits
        when the pipeline is garbage collected.  The pipeline must not be
        used after ``close``."""
        if self._exec is not None:
            self._exec.shutdown(wait=True)
            self._exec = None

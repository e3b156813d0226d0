"""The comparison that decides ``correct``.

The program's state after the first ``n_steps`` steps of the run (taken in
set-up, from the same ``run()`` calls and compiled interval programs the
window drives) is compared with the plain reference (:mod:`reference`)
advanced the same steps from the same initial particles.  Each number has
its limit in ``limits/<cell>.json``; a run is correct when every number is
at or under its limit.

- ``field_gap``: max |E, B (program) - E, B (reference)| over all six
  components, over the reference's largest field value.  It covers the
  particle kernels (gather, push, deposit), the strip exchange and current
  fold, and the field phase.
- ``moment_gap``: per species, the grid moments ``w (1, ux, uy, uz)`` of
  the program's alive particles against the reference's (both deposited
  by the reference at full float32), the largest gap over its moment's
  largest value.  Particles are compared as a population, so the order in
  which the program keeps them does not matter; it covers the push, the
  emigration and merge, and (on several chips) the adoption permutation.
- ``census``: |alive (program) - alive (reference)|, in particles
  (exact: limit 0).
- ``counters``: the work counters each checked LB round fed the balancer
  against the counter the kernels define, from the reference's per-box
  populations at that round's first step: per species and box
  ``ceil(n / TILE) * TILE * OPS_PER_LANE + CELLS_PER_BOX * OPS_PER_CELL``.
  The largest difference over rounds and boxes (exact: limit 0).
- ``dropped``: particles the program lost to a capacity bound, in set-up
  and in the window (exact: limit 0).

The reference follows the program's float32 arithmetic step for step
(box-local positions, the same order of operations in the push and the
move), so a particle is absorbed, or changes box, in both or in neither;
the exact numbers read 0 on every sound run.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

import reference
from problems import PARTICLE_KEYS

#: particle lanes per counter tile, and the counted operations per
#: particle lane (gather 96 + push 32 + deposit 48) and per cell (FDTD 24)
#: of the in-kernel work counter the balancer is fed
TILE = 256
OPS_PER_LANE = 176
OPS_PER_CELL = 24


def counter_formula(counts: np.ndarray, cells_per_box: int) -> np.ndarray:
    """Work counter per box from per-species populations ``(n_sp, n_boxes)``."""
    counts = np.asarray(counts, np.float64)
    tiles = np.ceil(counts / TILE)
    return (tiles * TILE * OPS_PER_LANE + cells_per_box * OPS_PER_CELL).sum(axis=0)


def _pooled(sp) -> Dict[str, np.ndarray]:
    """A padded reference species tuple -> its alive particles on the host."""
    host = [np.asarray(a) for a in sp]
    alive = host[6].astype(bool)
    return {k: host[i][alive] for i, k in enumerate(PARTICLE_KEYS)}


def reference_state(
    config: Dict, dom, laser, initial, n_steps: int, precision: str, guard: int
):
    """Advance the reference; returns the state in the form the program's
    is compared in, plus the per-step per-box populations."""
    sponge_spec = (int(config["sponge"]["width_cells"]), float(config["sponge"]["strength"]))
    f6, species, counts = reference.run(
        dom, laser, initial, n_steps, precision=precision, sponge_spec=sponge_spec,
        guard=guard,
    )
    pooled = [_pooled(sp) for sp in species]
    state = {
        "fields": np.asarray(f6),
        "species": pooled,
        "alive": int(sum(len(p["z"]) for p in pooled)),
        "dropped": 0,
    }
    return state, np.asarray(counts)


def _moments(pool: Dict[str, np.ndarray], dom) -> np.ndarray:
    padded = tuple(jnp.asarray(a) for a in reference.pad_species(pool, reference.BLOCK))
    return np.asarray(reference.moments(padded, dom=dom, block=reference.BLOCK))


def gaps(
    state: Dict, ref: Dict, dom, fed: List[np.ndarray], ref_counts: np.ndarray,
    interval: int,
) -> Dict[str, float]:
    """The compared numbers of ``state`` against the reference's ``ref``."""
    ff, rf = np.asarray(state["fields"], np.float64), np.asarray(ref["fields"], np.float64)
    out = {"field_gap": float(np.abs(ff - rf).max() / np.abs(rf).max())}
    mg = 0.0
    for ps, rs in zip(state["species"], ref["species"]):
        pm, rm = _moments(ps, dom), _moments(rs, dom)
        for k in range(pm.shape[0]):
            scale = np.abs(rm[k]).max()
            if scale > 0:
                mg = max(mg, float(np.abs(pm[k] - rm[k]).max() / scale))
    out["moment_gap"] = mg
    out["census"] = abs(int(state["alive"]) - int(ref["alive"]))
    cells = dom.box * dom.box
    gap = 0.0
    for r, costs in enumerate(fed):
        if costs is None:
            gap = float("inf")
            continue
        expect = counter_formula(ref_counts[r * interval], cells)
        gap = max(gap, float(np.abs(np.asarray(costs) - expect).max()))
    out["counters"] = gap
    return out


def compare(
    config: Dict, dom, laser, initial, program_state: Dict, fed: List[Optional[np.ndarray]],
    *, n_steps: int, interval: int, guard: int, limits: Dict, window_dropped: int,
) -> Dict[str, Dict]:
    """Every compared number of one run with its limit."""
    ref, counts = reference_state(config, dom, laser, initial, n_steps, "highest", guard)
    numbers = gaps(program_state, ref, dom, fed, counts, interval)
    numbers["dropped"] = program_state["dropped"] + window_dropped
    missing = set(numbers) - set(limits)
    if missing:
        raise KeyError(f"no limit for {sorted(missing)}")
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}

"""Test-only entry: one run of a benchmark cell on CPU devices, at a tiny
size, with the Pallas kernels in interpret mode.

    python rehearse_cell.py <cell> [--chips N] [--fault NAME] [--seed N]

The command proper (``benchmarks/chip/run.py``) refuses any backend but a
TPU; this entry drives the same ``harness.run_cell`` with that look
skipped, on as many virtual CPU devices as the cell asks for (or
``--chips``: the cell's mode spread over that many devices, the domain
growing with them), and prints the result object as its last line.  ``--fault`` breaks the timed path
underneath the harness, to show that ``correct`` comes out false:

- ``unchanged``: every interval returns the state it was given;
- ``half``: half of the particles (every other lane) leave the deposit,
  and the rest deposit with twice their weight, so the total is kept;
- ``no_exchange``: emigrants never arrive at their new box (the exchange
  between boxes, and between chips, is left out);
- ``altered``: one field value is altered where the interval produces it.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: the tiny size: 32^2 cells per chip in 16^2 boxes, 4 particles per cell
TINY = {"cells_per_chip": [32, 32], "box_cells": 16, "ppc": 4}


def rehearse(cell: str, *extra: str) -> dict:
    """Run this entry in a process of its own; returns its result object."""
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, str(HERE / "rehearse_cell.py"), cell, *extra],
        capture_output=True, text=True, timeout=600, env=env,
    )
    if p.returncode:
        raise RuntimeError(p.stderr[-3000:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.dist import sharded_runtime as sr

    if fault == "unchanged":
        real = sr.ShardedRuntime._interval_fn

        def interval_fn(self, n_steps):
            fn = real(self, n_steps)

            def unchanged(tiles, species, *args):
                keep = jax.tree_util.tree_map(jnp.copy, (tiles, species))
                _, _, ys = fn(tiles, species, *args)
                return keep[0], keep[1], ys

            return unchanged

        sr.ShardedRuntime._interval_fn = interval_fn
    elif fault == "half":
        real = sr.particle_phase_slots

        def half(tiles6, species, *args, **kw):
            lane = jnp.arange(species[0].w.shape[1])[None, :]
            scale = jnp.where(lane % 2 == 0, 2.0, 0.0)
            out = real(tiles6, tuple(p._replace(w=p.w * scale) for p in species), *args, **kw)
            restored = tuple(q._replace(w=p.w) for p, q in zip(species, out[0]))
            return (restored,) + tuple(out[1:])

        sr.particle_phase_slots = half
    elif fault == "no_exchange":
        real = sr.neighbor_exchange

        def no_exchange(payloads, axis):
            arrivals = real(payloads, axis)
            return {o: (pk, jnp.full_like(dest, -1)) for o, (pk, dest) in arrivals.items()}

        sr.neighbor_exchange = no_exchange
    elif fault == "altered":
        real = sr.field_phase_stacked

        def altered(*args, **kw):
            tiles = real(*args, **kw)
            return tiles.at[0, 0, 5, 5].add(0.1 * jnp.abs(tiles).max())

        sr.field_phase_stacked = altered
    else:
        raise ValueError(f"unknown fault {fault!r}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--chips", type=int)
    ap.add_argument("--fault")
    ap.add_argument("--seed", type=int, default=2**31 + 5)
    args = ap.parse_args()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    chips = args.chips or next(w["chips"] for w in spec["workloads"] if w["name"] == args.cell)
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [str(ROOT / "benchmarks" / "chip"), str(ROOT / "src")]
    import harness

    cell = harness.load_cell(spec, args.cell)
    cell.config = dict(cell.config, **TINY)
    cell.chips = chips
    if args.fault:
        plant(args.fault)
    out = harness.run_cell(cell, args.seed, 0.5, False, require_tpu=False)
    problems = harness.result_problems(out, cell, False)
    if problems:
        print(f"malformed: {problems}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

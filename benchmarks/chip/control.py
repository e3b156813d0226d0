"""Readings that the correctness limits are set from, on this machine's chips.

    python3 benchmarks/chip/control.py --cell laser_ion.1chip --seeds 11 12 13

For each seed, at the cell's own size and over its checked steps:

- ``sound``: the program as the configuration states it, against the
  reference (what every benchmark run compares);
- ``control``: the reference computed with every matrix product of
  bfloat16 operands, put in the program's place: the nearest precision
  below the configuration's float32;
- ``reference_high``: the same with three-pass (``high``) products, for
  the record;
- ``program_DEFAULT`` (with ``--program-precision DEFAULT``): the program
  with its kernels' matrix products at ``jax.lax.Precision.DEFAULT``
  (:func:`kernel_precision`).  ``HIGH`` is not offered: Mosaic lowers a
  kernel's dot at ``DEFAULT`` or ``HIGHEST`` only.

One JSON line per reading: the compared numbers of ``check.gaps``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@contextlib.contextmanager
def kernel_precision(name: str):
    """The program's Pallas kernels with their matrix products at
    ``jax.lax.Precision.<name>``.  Each kernel module binds
    ``DOT_PRECISION`` by name when it is imported, so the constant is set
    in those modules (setting it in ``repro.kernels.common`` alone reaches
    no kernel), and jax's caches are cleared on the way in and out so that
    the kernels are traced anew."""
    import jax
    from repro.kernels import deposition, gather_push

    mods = (deposition, gather_push)
    saved = [m.DOT_PRECISION for m in mods]
    for m in mods:
        m.DOT_PRECISION = getattr(jax.lax.Precision, name)
    jax.clear_caches()
    try:
        yield
    finally:
        for m, v in zip(mods, saved):
            m.DOT_PRECISION = v
        jax.clear_caches()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program-precision", nargs="*", default=[], choices=("DEFAULT",))
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "tpu"
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from repro.launch.xla import use_compilation_cache

    use_compilation_cache()
    import numpy as np

    import check
    import harness
    import problems

    cell = harness.load_cell(harness.load_spec(), args.cell)
    interval = int(cell.mode["runtime"]["lb_interval"])
    n_steps = int(cell.mode["check_intervals"]) * interval

    def emit(kind, seed, numbers):
        print(json.dumps({"cell": cell.name, "kind": kind, "seed": seed, **numbers}), flush=True)

    for seed in args.seeds:
        out = harness.run_cell(cell, seed, 0.0, False)
        emit("sound", seed, {k: v[0] for k, v in out["checks"].items()})
        dom, laser, species = problems.make_state(cell.config, cell.chips, seed)
        initial = [(q, m, {k: np.asarray(a[k]) for k in problems.PARTICLE_KEYS}) for q, m, a in species]
        del species
        guard = int(cell.mode["runtime"]["halo"])
        ref, counts = check.reference_state(cell.config, dom, laser, initial, n_steps, "highest", guard)
        cells = dom.box * dom.box
        for precision in ("bfloat16", "high"):
            low, low_counts = check.reference_state(
                cell.config, dom, laser, initial, n_steps, precision, guard
            )
            fed = [check.counter_formula(low_counts[r * interval], cells) for r in range(n_steps // interval)]
            kind = "control" if precision == "bfloat16" else f"reference_{precision}"
            emit(kind, seed, check.gaps(low, ref, dom, fed, counts, interval))
        del ref, low

        for prec in args.program_precision:
            with kernel_precision(prec):
                out = harness.run_cell(cell, seed, 0.0, False)
            emit(f"program_{prec}", seed, {k: v[0] for k, v in out["checks"].items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())

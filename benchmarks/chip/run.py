"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <window> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout.  The last line of standard output is one JSON object:
``correct``, ``attempted`` (steps in the window), ``failed`` (window
intervals that lost particles), ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace
1`` a ``breakdown``, and last ``checks``: each compared number beside its
limit.  The same numbers end standard error.

There is no CPU fallback: without a TPU, or with fewer chips than the cell
asks for, the command exits non-zero and prints no result.  JAX's
persistent compilation cache is where ``JAX_COMPILATION_CACHE_DIR`` says,
else in ``.jax_cache/`` at the root of the checkout (the program's
``repro.launch.use_compilation_cache``), so only a checkout's first run of
a cell compiles.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        print(f"run.py: no TPU: JAX_PLATFORMS={platforms!r} excludes it", file=sys.stderr)
        return 2
    # a TPU that fails to initialise stops the run; nothing falls back
    os.environ["JAX_PLATFORMS"] = "tpu"
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from repro.launch.xla import use_compilation_cache

    use_compilation_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import harness

    try:
        cell = harness.load_cell(harness.load_spec(), args.workload)
        out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (harness.CellError, RuntimeError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    problems = harness.result_problems(out, cell, bool(args.trace))
    if problems:
        print(f"run.py: malformed result: {problems}", file=sys.stderr)
        return 1
    for name, (value, limit) in out["checks"].items():
        print(f"{name} {value!r} limit {limit!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

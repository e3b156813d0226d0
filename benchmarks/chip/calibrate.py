"""Calibrate particles per cell: the time per step of each configuration
at candidate densities, on this machine's chip.

    python3 benchmarks/chip/calibrate.py --cell laser_ion.1chip \
        --cell uniform_plasma.1chip --ppc 64 128 256 512 900

Each (cell, density) runs in a process of its own: build, warm up, one
timed LB interval, no comparison.  A density is skipped once the one
below it predicts more than ``--max-step-s`` per step (linear in ppc).
One JSON line per run; the densities chosen and why are in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def one(cell: str, ppc: int, seed: int) -> dict:
    os.environ["JAX_PLATFORMS"] = "tpu"
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from repro.launch.xla import use_compilation_cache

    use_compilation_cache()
    import harness

    c = harness.load_cell(harness.load_spec(), cell)
    c.config = dict(c.config, ppc=ppc)
    out = harness.run_cell(c, seed, 0.0, False, compare=False)
    return {"cell": cell, "ppc": ppc, **{k: v["value"] for k, v in out["metrics"].items()},
            "attempted": out["attempted"], "device": out["device"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cell", action="append", required=True)
    ap.add_argument("--ppc", type=int, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 7)
    ap.add_argument("--max-step-s", type=float, default=2.0)
    ap.add_argument("--timeout", type=float, default=600)
    ap.add_argument("--one", nargs=2, metavar=("CELL", "PPC"))
    args = ap.parse_args()
    if args.one:
        print(json.dumps(one(args.one[0], int(args.one[1]), args.seed)), flush=True)
        return 0
    for cell in args.cell:
        last = None
        for ppc in sorted(args.ppc):
            if last and last["step_s"] * ppc / last["ppc"] > args.max_step_s:
                print(json.dumps({"cell": cell, "ppc": ppc, "skipped": "predicted step_s over the limit"}), flush=True)
                continue
            cmd = [sys.executable, __file__, "--cell", cell, "--ppc", str(ppc), "--seed", str(args.seed),
                   "--one", cell, str(ppc)]
            try:
                p = subprocess.run(cmd, capture_output=True, text=True, timeout=args.timeout)
            except subprocess.TimeoutExpired:
                print(json.dumps({"cell": cell, "ppc": ppc, "error": "timeout"}), flush=True)
                break
            lines = [l for l in p.stdout.splitlines() if l.startswith("{")]
            if p.returncode or not lines:
                print(json.dumps({"cell": cell, "ppc": ppc, "error": p.stderr[-1500:]}), flush=True)
                break
            last = json.loads(lines[-1])
            print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

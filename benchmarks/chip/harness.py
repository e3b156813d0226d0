"""One run of one benchmark cell: build, warm up, time a window, check.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a run mode; each is a file of its own, found by name:
``configs/<config>.json`` (the deployment and its sizes) and
``modes/<traffic>.json`` (chips, the runtime's settings, warm-up and
check lengths).  Each per-layer metric is a reader of its own,
``metrics/<name>.py``, and each cell's correctness limits are
``limits/<cell>.json``.  A later change adds a cell, a mode, a metric or a
configuration with new files and a new ``BENCHMARK.json`` entry; nothing
here names one.

The run (:func:`run_cell`):

1. Make the initial plasma on the device from the seed
   (:mod:`problems`) and hand it to the program's ``ShardedRuntime``.
2. Warm up: the first ``check_intervals`` LB intervals run through
   ``run()``, the call the window drives; the program's state after them is
   kept on the host for the check, with the work counters each LB round
   fed the balancer.  Warm-up then runs to ``warmup_intervals`` intervals,
   so that adoptions and their recompiles land before the window.
3. The window: ``run(lb_interval)`` until ``seconds`` have passed, ended by
   ``block_until_ready`` on the runtime's state.  With ``trace`` the window
   runs under the profiler and the per-layer readers reduce its trace.
4. Once the window has closed and the peak memory is read, the program is
   freed and the plain reference (:mod:`reference`) runs the checked steps
   from the same initial state; :mod:`check` compares.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import check
import problems
import trace_reduce

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent


#: warm-up intervals past the mode's ``warmup_intervals``, at most, spent
#: compiling the programs that late adoptions ask for
MAX_EXTRA_WARMUP = 4


class CellError(RuntimeError):
    """The cell cannot run here (no accelerator, too few chips, bad spec)."""


# ---------------------------------------------------------------------------
# the spec: BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------


def load_spec(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(kind: str, name: str) -> Dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise CellError(f"no {kind[:-1]} file {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mode: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_cell(spec: Dict, name: str) -> Cell:
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise CellError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    config = _load_json("configs", w["config"])
    mode = _load_json("modes", w["traffic"])
    if int(mode["chips"]) != int(w["chips"]):
        raise CellError(f"{name}: mode {w['traffic']} runs {mode['chips']} chips, cell asks {w['chips']}")

    def mine(m: Dict) -> bool:
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        mode=mode,
        limits=_load_json("limits", name),
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)],
    )


def load_reader(metric: str) -> Callable:
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise CellError(f"no reader for per-layer metric {metric!r} ({path.name})")
    spec = importlib.util.spec_from_file_location(f"_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


def result_problems(out: Dict, cell: Cell, trace: bool) -> List[str]:
    """What is wrong with a result object, by the shape the command's last
    line must have; empty when it is sound."""
    bad = [f"missing {k!r}" for k in RESULT_KEYS if k not in out]
    if bad:
        return bad
    if list(out)[-1] != "checks":
        bad.append("'checks' is not the last key")
    want = cell.per_layer if trace else cell.end_to_end
    names = {m["name"]: m for m in want}
    for name, m in out["metrics"].items():
        if name not in names:
            bad.append(f"metric {name!r} is not the cell's")
        elif m.get("unit") != names[name]["unit"]:
            bad.append(f"metric {name!r} has unit {m.get('unit')!r}")
        elif not isinstance(m.get("value"), (int, float)):
            bad.append(f"metric {name!r} has no number")
        elif name.endswith("_roofline") and m["value"] > 105:
            bad.append(f"{name} reads {m['value']} > 105%")
    if not trace:
        bad += [f"end-to-end metric {n!r} missing" for n in names if n not in out["metrics"]]
    dev = out["device"]
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        if k not in dev:
            bad.append(f"device has no {k!r}")
    if trace:
        if not dev.get("busy_s", 0) > 0 or "window_s" not in dev:
            bad.append("a traced run needs device busy_s > 0 and window_s")
        for k in ("device_ops", "idle_gaps"):
            if len(out.get("breakdown", {}).get(k, [])) > 10:
                bad.append(f"breakdown {k} has more than 10 entries")
    return bad


# ---------------------------------------------------------------------------
# what one run records, for the readers
# ---------------------------------------------------------------------------


@dataclass
class RunRecord:
    """Everything the per-layer readers may read about one run."""

    cell: Cell
    window_s: float
    steps: int
    intervals: int
    host_blocked_s: float
    compiles_in_window: int
    #: alive particles and particle lanes per device at the window's end
    alive_per_device: List[int]
    lanes_per_device: List[int]
    #: per-species alive particles per box at the window's end
    alive_per_box: List[np.ndarray]
    domain: problems.Domain
    device_kind: str
    trace: Optional[trace_reduce.Trace] = None
    #: traced runs: instruction name -> the JAX op it was made from, in
    #: the compiled interval program the window ran
    program_ops: Optional[Dict[str, str]] = None


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache."""

    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def __call__(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += seconds


_COUNTER: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    """The process's one listener (jax keeps listeners for the life of the
    process, so a second run in one process must not add another)."""
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(_COUNTER)
    return _COUNTER


def _problem(dom, laser, species, config):
    """The program's problem object around the benchmark's arrays."""
    from repro.pic.grid import Grid2D
    from repro.pic.laser import LaserAntenna
    from repro.pic.particles import Particles
    from repro.pic.problem import ProblemSetup

    grid = Grid2D(nz=dom.nz, nx=dom.nx, dz=dom.dz, dx=dom.dx, box_nz=dom.box, box_nx=dom.box, cfl=dom.cfl)
    sp = tuple(
        Particles(
            z=a["z"], x=a["x"], ux=a["ux"], uy=a["uy"], uz=a["uz"], w=a["w"],
            alive=jnp.ones(a["z"].shape, bool), q=jnp.float32(q), m=jnp.float32(m),
        )
        for q, m, a in species
    )
    antenna = None
    if laser is not None:
        antenna = LaserAntenna(
            a0=laser.a0, omega0=laser.omega0, waist=laser.waist,
            duration=laser.duration, t_peak=laser.t_peak,
            z_pos=laser.row * dom.dz, x_center=laser.x_center,
        )
    return ProblemSetup(grid=grid, species=sp, laser=antenna, name=config["name"])


def _occupancy(rt, devices, n_boxes: int):
    """Alive particles and particle lanes per device, and per-species alive
    particles per box, at the window's end: ``(alive, lanes, alive_box)``.

    The runtime has no public accessor for its slot layout, so this reads
    its private state: the particle buffers
    (``rt._species``, per species a dict of ``(slots, lanes)`` arrays
    sharded over the chips) and the slot-to-box table (``rt._slot_box``).
    Lanes are counted per shard, as the buffers hold them; nothing assumes
    that slots or devices hold alike."""
    where = {d.id: i for i, d in enumerate(devices)}
    alive_dev = [0] * len(devices)
    lanes_dev = [0] * len(devices)
    alive_box = []
    slot_box = np.asarray(rt._slot_box)
    for d in rt._species:
        for shard in d["alive"].addressable_shards:
            i = where[shard.device.id]
            lanes_dev[i] += int(shard.data.size)
            alive_dev[i] += int(jnp.sum(shard.data, dtype=jnp.int32))
        by_box = np.zeros(n_boxes, np.int64)
        by_box[slot_box] = np.asarray(jnp.sum(d["alive"], axis=1, dtype=jnp.int32))
        alive_box.append(by_box)
    return alive_dev, lanes_dev, alive_box


def _plan(rt):
    """The committed exchange plan, from the program's public accounting
    (``comm_stats``, ``migration_stats``): an adoption or an emigrant-pack
    resize that changes it asks for an interval program of its own."""
    comm = rt.comm_stats()
    caps = rt.migration_stats()["caps"]
    return (
        comm["offsets"],
        tuple(sorted(comm.get("pair_caps", {}).items())),
        tuple(tuple(sorted(c.items())) for c in caps),
    )


def _program_state(rt) -> Dict:
    """The program's state as the check compares it: global fields and
    each species' alive particles, on the host."""
    snap = rt.snapshot()
    return {
        "fields": np.stack([np.asarray(c) for c in rt.fields]),
        "species": snap["species"],
        "alive": rt.total_alive(),
        "dropped": rt.dropped_total,
    }


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check_devices(chips: int, require_tpu: bool):
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise CellError(f"no TPU: the default device is {devices[0].platform}")
    if len(devices) < chips:
        raise CellError(f"the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    require_tpu: bool = True,
    compare: bool = True,
) -> Dict:
    """Run ``cell`` once; returns the result object the command prints."""
    from repro.dist.sharded_runtime import ShardedRuntime

    devices = check_devices(cell.chips, require_tpu)
    compiles = compile_counter()
    mode = cell.mode
    interval = int(mode["runtime"]["lb_interval"])
    check_intervals = int(mode["check_intervals"])
    warmup_intervals = max(int(mode["warmup_intervals"]), check_intervals)

    # -- set-up: build, place, warm up ------------------------------------
    t_setup = time.perf_counter()
    dom, laser, species = problems.make_state(cell.config, cell.chips, seed)
    jax.block_until_ready(species)
    t = time.perf_counter()
    initial = [
        (q, m, {k: np.asarray(a[k]) for k in problems.PARTICLE_KEYS}) for q, m, a in species
    ]
    check_s = time.perf_counter() - t
    rt = ShardedRuntime(
        _problem(dom, laser, species, cell.config),
        n_devices=cell.chips,
        devices=devices,
        shape_order=int(cell.config["shape_order"]),
        sponge_width=int(cell.config["sponge"]["width_cells"]),
        **mode["runtime"],
    )
    del species
    fed: List[np.ndarray] = []
    k = 0
    plans_run = set()
    # past warmup_intervals, go on while an adoption has asked for an
    # exchange plan that no interval has run under yet
    while k < warmup_intervals or (
        k < warmup_intervals + MAX_EXTRA_WARMUP and _plan(rt) not in plans_run
    ):
        plans_run.add(_plan(rt))
        with jax.profiler.TraceAnnotation(f"warmup_interval_{k}"):
            rt.run(interval)
        if k < check_intervals:
            fed.append(rt.slot_costs())
        if k == check_intervals - 1:
            t = time.perf_counter()
            program_state = _program_state(rt)
            check_s += time.perf_counter() - t
        k += 1
    jax.block_until_ready(rt._pipe.state)
    setup_s = time.perf_counter() - t_setup - check_s
    log(f"# set-up {setup_s:.3f} s, {k} intervals ({compiles.n} programs, {compiles.seconds:.3f} s compiling)")

    # -- the window -------------------------------------------------------
    n_compiles0 = compiles.n
    blocked0 = rt.pipeline_stats()["host_blocked_s"]
    dropped0 = rt.dropped_total
    intervals = 0
    failed = 0
    tmp = None
    if trace:
        tmp = tempfile.TemporaryDirectory(prefix="trace_")
        # host spans from the runtime and the benchmark; no Python tracer,
        # which would time every Python call the driver makes
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp.name, profiler_options=options)
    t0 = time.perf_counter()
    ends = []
    try:
        while True:
            with jax.profiler.TraceAnnotation("window_interval"):
                before = rt.dropped_total
                rt.run(interval)
                failed += rt.dropped_total != before
            intervals += 1
            ends.append(time.perf_counter())
            if ends[-1] - t0 >= seconds:
                break
        jax.block_until_ready(rt._pipe.state)
        window_s = time.perf_counter() - t0
    finally:
        if trace:
            jax.profiler.stop_trace()
    blocked = rt.pipeline_stats()["host_blocked_s"] - blocked0
    n_compiles = compiles.n - n_compiles0
    steps = intervals * interval
    log(f"# window {window_s:.6f} s, {steps} steps, {n_compiles} programs compiled in it")
    log("# window intervals s: " + " ".join(f"{b - a:.6f}" for a, b in zip([t0] + ends, ends)))

    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]
    alive_dev, lanes_dev, alive_box = _occupancy(
        rt, devices, (dom.nz // dom.box) * (dom.nx // dom.box)
    )
    end_dropped = rt.dropped_total - dropped0
    record = RunRecord(
        cell=cell, window_s=window_s, steps=steps, intervals=intervals,
        host_blocked_s=blocked, compiles_in_window=n_compiles,
        alive_per_device=alive_dev, lanes_per_device=lanes_dev,
        alive_per_box=alive_box, domain=dom, device_kind=devices[0].device_kind,
    )
    if trace:
        record.program_ops = trace_reduce.program_ops(rt.interval_hlo(interval))
    del rt
    gc.collect()

    if trace:
        record.trace = trace_reduce.load(tmp.name, [d.id for d in devices])
        tmp.cleanup()

    metrics: Dict[str, Dict] = {}
    if trace:
        for m in cell.per_layer:
            value = load_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        e2e = {
            "step_s": window_s / steps,
            "peak_hbm_gb": max(peaks) / 1e9,
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}

    # -- the check, once the program is freed ----------------------------
    t = time.perf_counter()
    numbers = {} if not compare else check.compare(
        cell.config, dom, laser, initial, program_state, fed,
        n_steps=check_intervals * interval, interval=interval,
        guard=int(mode["runtime"]["halo"]), limits=cell.limits,
        window_dropped=end_dropped,
    )
    log(f"# check {time.perf_counter() - t:.3f} s")
    correct = all(n["value"] <= n["limit"] for n in numbers.values())

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks),
    }
    out = {
        "correct": bool(correct),
        "attempted": steps,
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = record.trace.busy_s_mean()
        device["window_s"] = record.trace.window_s()
        out["breakdown"] = record.trace.breakdown()
    out["checks"] = {name: [n["value"], n["limit"]] for name, n in numbers.items()}
    return out

"""Host spans of the LB turn, as the profiler records them.

``ShardedRuntime`` and its ``IntervalPipeline`` annotate the host's LB turn
with ``jax.profiler.TraceAnnotation`` spans (``docs/architecture.md``,
"Observability"): ``interval_dispatch`` (with ``interval_new_plan`` inside
it on a program-cache miss, labelled with its ``cause``),
``harvest_wait``, ``harvest_fetch``, ``history_decode``, ``lb_decide`` and
``recommit`` (labelled ``boxes_moved``).  The run here takes three LB
intervals on two fake devices, with one external adoption that changes the
exchange plan between the first and the second, under the real profiler;
the spans are read back from its ``.xplane.pb`` with ``ProfileData``.

The run needs two devices, so it is a subprocess with its own
``XLA_FLAGS`` (this process keeps one device).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SPANS = (
    "interval_dispatch",
    "interval_new_plan",
    "harvest_wait",
    "harvest_fetch",
    "history_decode",
    "lb_decide",
    "recommit",
)

_RUN = r"""
import glob, json, os, sys, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, sys.argv[2])
import jax
import numpy as np
from jax.profiler import ProfileData
from repro.pic import laser_ion_problem
from repro.dist.sharded_runtime import ShardedRuntime

names = set(json.loads(sys.argv[3]))
rt = ShardedRuntime(
    laser_ion_problem(nz=32, nx=32, box_cells=8, ppc=2, seed=0), 2, lb_interval=2,
)
out = {"blocked": [], "plans": []}
log = tempfile.mkdtemp()
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
jax.profiler.start_trace(log, profiler_options=options)
for k in range(3):
    if k == 1:
        # swap the first box of device 0 with the last of device 1
        m = np.asarray(rt.balancer.mapping).copy()
        a, b = np.where(m == 0)[0][0], np.where(m == 1)[0][-1]
        m[a], m[b] = m[b], m[a]
        rt.apply_mapping(m)
    out["plans"].append(repr(sorted(rt.comm_stats()["pair_caps"].items())))
    before = rt.pipeline_stats()["host_blocked_s"]
    rt.run(2)
    out["blocked"].append(rt.pipeline_stats()["host_blocked_s"] - before)
jax.profiler.stop_trace()
out["lb_rounds"] = len(rt.balancer.events)
out["adoptions"] = 1 + len(rt.history["lb_steps"])
out["host_syncs"] = rt.host_syncs
path = sorted(glob.glob(os.path.join(log, "**", "*.xplane.pb"), recursive=True))[-1]
spans = []
for plane in ProfileData.from_file(path).planes:
    if plane.name.startswith("/host:"):
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    s = int(ev.start_ns)
                    spans.append([ev.name, s, s + int(ev.duration_ns), dict(ev.stats), line.name])
out["spans"] = sorted(spans, key=lambda x: (x[1], -x[2]))
with open(sys.argv[1], "w") as f:
    json.dump(out, f, default=str)
"""


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """Spans and accounting of three profiled intervals on two devices."""
    src = Path(__file__).resolve().parents[1] / "src"
    path = tmp_path_factory.mktemp("spans") / "run.json"
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, "-c", _RUN, str(path), str(src), json.dumps(SPANS)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(path.read_text())


def _named(run, name):
    return [s for s in run["spans"] if s[0] == name]


def test_each_interval_dispatches_waits_fetches_decodes_in_order(run):
    turn = ("interval_dispatch", "harvest_wait", "harvest_fetch", "history_decode")
    seq = [s[0] for s in run["spans"] if s[0] in turn]
    assert seq == list(turn) * 3
    assert run["host_syncs"] == 3
    # all on the calling thread, none overlapping another of the four
    assert len({s[4] for s in run["spans"]}) == 1
    ivs = [(s[1], s[2]) for s in run["spans"] if s[0] in turn]
    assert all(a[1] <= b[0] for a, b in zip(ivs, ivs[1:]))


def test_lb_decide_once_per_round_and_recommit_once_per_adoption(run):
    assert len(_named(run, "lb_decide")) == run["lb_rounds"] == 3
    recommits = _named(run, "recommit")
    assert len(recommits) == run["adoptions"] >= 1
    assert all(int(r[3]["boxes_moved"]) >= 2 for r in recommits)
    # the balancer's turn and the recommit are separate spans
    for d in _named(run, "lb_decide"):
        assert not any(d[1] <= r[1] < d[2] for r in recommits)


def test_new_plan_names_its_cause(run):
    assert run["plans"][0] != run["plans"][1], "the adoption changes the exchange plan"
    new = _named(run, "interval_new_plan")
    assert [n[3]["cause"] for n in new] == ["first", "exchange_plan"]
    dispatches = _named(run, "interval_dispatch")
    # nested in the dispatches of intervals 1 and 2; interval 3 is a hit
    for n, d in zip(new, dispatches[:2]):
        assert d[1] <= n[1] and n[2] <= d[2]


def test_host_blocked_grows_by_the_wait_and_the_fetch(run):
    waits = _named(run, "harvest_wait")
    fetches = _named(run, "harvest_fetch")
    dispatches = _named(run, "interval_dispatch")
    for blocked, w, f, d in zip(run["blocked"], waits, fetches, dispatches):
        inside = (w[2] - w[1] + f[2] - f[1]) * 1e-9
        # the two spans sit inside the bracket that feeds host_blocked_s;
        # besides them it holds only the inline dispatch (pipeline="sync")
        assert inside - 1e-4 <= blocked <= inside + (d[2] - d[1]) * 1e-9 + 1e-3

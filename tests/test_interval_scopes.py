"""Named scopes of the interval program.

``ShardedRuntime._interval_fn`` names each phase of the scanned step with
``jax.named_scope`` (the table in ``docs/architecture.md``,
"Observability").  The scope lands in the ``op_name`` XLA keeps in each
instruction's metadata, so a profile can sum device time by phase.  These
tests parse the compiled program (``rt.interval_hlo()``) and check that
every op of the scan body carries exactly one scope of the table, and that
every scope the mode has is there.

Cases on one device run in this process; cases on two fake devices run in
a subprocess with its own ``XLA_FLAGS`` (this process keeps one device).
"""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

#: the scopes of one scanned step
STEP_SCOPES = (
    "halo_paste",
    "particle_phase",
    "current_fold",
    "field_phase",
    "emigrate",
    "exchange",
    "merge",
    "diagnostics",
)
#: the scope of the per-interval prologue
PROLOGUE = "pair_tables"
SCOPES = (PROLOGUE,) + STEP_SCOPES

#: (comm, engine_backend, overlap); split-phase stepping is XLA-only
MODES = [
    ("neighbor", "xla", False),
    ("neighbor", "pallas", False),
    ("ring", "xla", False),
    ("ring", "pallas", False),
    ("neighbor", "xla", True),
    ("ring", "xla", True),
]

#: ops ``lax.scan`` adds to the loop body: the counter, the slice of the
#: scanned input and the writes of the stacked outputs
SCAN_LOOP_OPS = {"add", "dynamic_slice", "dynamic_update_slice"}
#: instructions that compute nothing; jax and XLA give literals and
#: plumbing the name of the enclosing call (``shard_map``, the scan body)
PLUMBING = {
    "constant", "iota", "broadcast", "parameter", "get-tuple-element",
    "tuple", "bitcast", "copy",
}

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(")
_CALLEES = re.compile(r"\b(?:calls|body|condition)=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _parse(text):
    """Computation name -> its instruction lines, and the entry's name."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m and not line.startswith(" "):
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
        elif cur is not None and _INSTRUCTION.match(line):
            comps[cur].append(line)
    return comps, entry


def _scopes(op_name):
    """Table scopes named in an ``op_name`` (a merged name joins several
    paths with ``;``)."""
    return {c for path in op_name.split(";") for c in path.split("/") if c in SCOPES}


def _scan_body(comps, entry):
    """Instruction lines of the scan's loop body and of every computation
    it runs (fusions, nested loops).  Reducers and comparators are part of
    the op that calls them and are not followed."""
    loops = [line for line in comps[entry] if " while(" in line]
    assert len(loops) == 1, "the interval program has one scan loop"
    todo = [re.search(r"body=%?([\w.\-]+)", loops[0]).group(1)]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += _CALLEES.findall(line)
    return [line for c in seen for line in comps[c]]


def _fusion_is_plumbing(line, comps):
    callee = re.search(r"calls=%?([\w.\-]+)", line).group(1)
    return all(_INSTRUCTION.match(x).group(2) in PLUMBING for x in comps[callee])


def check_scopes(text):
    """Faults in a compiled interval program's scopes (empty when sound),
    and the count of scan-body instructions per scope."""
    comps, entry = _parse(text)
    faults, found = [], dict.fromkeys(STEP_SCOPES, 0)
    for line in _scan_body(comps, entry):
        m = _OP_NAME.search(line)
        if not m or not m.group(1).startswith("jit("):
            continue  # no metadata, or a reducer's own
        name, opcode = m.group(1), _INSTRUCTION.match(line).group(2)
        scopes = _scopes(name)
        if len(scopes) == 1:
            scope = scopes.pop()
            if scope in found:
                found[scope] += 1
            continue
        last = name.split(";")[-1].rsplit("/", 1)[-1]
        if not scopes and re.search(r"/while/body/[^/;]+$", name) and last in SCAN_LOOP_OPS:
            continue
        if not scopes and last in ("closed_call", "shard_map", "while") and (
            opcode in PLUMBING or (opcode == "fusion" and _fusion_is_plumbing(line, comps))
        ):
            continue
        faults.append(f"{len(scopes)} scopes {sorted(scopes)}: {line.strip()[:160]}")
    for scope in SCOPES:
        if f"/{scope}/" not in text:
            faults.append(f"scope {scope} missing from the program")
    return faults, found


def _problem():
    from repro.pic import laser_ion_problem

    return laser_ion_problem(nz=32, nx=32, box_cells=8, ppc=2, seed=0)


def _runtime(n_devices, comm, backend, overlap):
    from repro.dist.sharded_runtime import ShardedRuntime

    return ShardedRuntime(
        _problem(), n_devices, lb_interval=2, comm=comm,
        engine_backend=backend, overlap=overlap,
    )


@pytest.mark.parametrize("comm, backend, overlap", MODES)
def test_every_step_op_carries_one_scope_one_device(comm, backend, overlap):
    faults, found = check_scopes(_runtime(1, comm, backend, overlap).interval_hlo())
    assert faults == []
    assert all(found[s] > 0 for s in STEP_SCOPES), found


_TWO_DEVICES = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [sys.argv[2], sys.argv[3]]
import jax
from test_interval_scopes import MODES, _runtime
assert jax.device_count() == 2
out = {}
for comm, backend, overlap in MODES:
    out[f"{comm}-{backend}-{overlap}"] = _runtime(2, comm, backend, overlap).interval_hlo()
with open(sys.argv[1], "w") as f:
    json.dump(out, f)
"""


@pytest.fixture(scope="module")
def two_device_programs(tmp_path_factory):
    """The compiled interval program of every mode on two fake devices."""
    here = Path(__file__).resolve().parent
    path = tmp_path_factory.mktemp("scopes") / "programs.json"
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, "-c", _TWO_DEVICES, str(path), str(here), str(here.parent / "src")],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(path.read_text())


@pytest.mark.parametrize("comm, backend, overlap", MODES)
def test_every_step_op_carries_one_scope_two_devices(two_device_programs, comm, backend, overlap):
    text = two_device_programs[f"{comm}-{backend}-{overlap}"]
    faults, found = check_scopes(text)
    assert faults == []
    assert all(found[s] > 0 for s in STEP_SCOPES), found
    assert "collective-permute" in text  # the exchanges cross devices here


def test_unscoped_step_op_is_a_fault():
    """The check itself: an op of the step outside every scope, and one
    under two scopes, are both reported."""
    text = _runtime(1, "neighbor", "xla", False).interval_hlo()
    line = next(
        x for x in text.splitlines()
        if 'op_name="jit(local_interval)/while/body/closed_call/merge/' in x
        and " = " in x and "fusion(" not in x
    )
    bare = line.replace("/closed_call/merge/", "/closed_call/")
    twice = line.replace("/closed_call/merge/", "/closed_call/merge/exchange/")
    for planted in (bare, twice):
        faults, _ = check_scopes(text.replace(line, planted, 1))
        assert len(faults) == 1


def test_reorder_program_is_scoped():
    """The adoption permutation is a program of its own, named ``reorder``."""
    rt = _runtime(1, "neighbor", "xla", False)
    tiles, species = rt._pipe.state
    perm = jnp.arange(rt.grid.n_boxes)
    text = rt._reorder_fn.lower(tiles, species, perm).compile().as_text()
    names = [m.group(1) for m in _OP_NAME.finditer(text) if "/" in m.group(1)]
    assert names and all("/reorder/" in n for n in names), names[:5]

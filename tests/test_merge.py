"""The exchange's merge (``repro.dist.sharded_runtime.merge_slots``).

The merge compacts each slot's stayers and the arrivals addressed to its
box into the slot's ``cap`` lanes, valid lanes first in their old order.
It is one stable sort on validity that carries the particle arrays.  The
reference below is the formulation it replaced (an ``argsort`` of validity
and one per-lane gather per array); the sort must reproduce it bit for
bit, since the kernels' dead-block skipping and the exact work counters
depend on the lane order.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import sharded_runtime
from repro.dist.sharded_runtime import _PKEYS, ShardedRuntime, merge_slots


def reference_merge(stay, fields, boxes, centers, gdest, gpack, cap):
    """``merge_slots`` as an argsort of validity and per-lane gathers,
    under ``vmap`` over the slots."""

    def merge(stay_r, fields_r, box_r, center_r):
        valid = jnp.concatenate([stay_r, gdest == box_r])
        kidx = jnp.argsort(jnp.where(valid, 0, 1))[:cap]
        new_alive = valid[kidx]
        out = {k: jnp.concatenate([fields_r[k], gpack[k]])[kidx] for k in _PKEYS}
        out["z"] = jnp.where(new_alive, out["z"], center_r[0])
        out["x"] = jnp.where(new_alive, out["x"], center_r[1])
        for k in ("ux", "uy", "uz", "w"):
            out[k] = jnp.where(new_alive, out[k], 0.0)
        out["alive"] = new_alive
        return out, valid.sum() - new_alive.sum()

    return jax.vmap(merge)(stay, fields, boxes, centers)


CAP = 64


def _inputs(case, n_slots, seed=0):
    """Slots and arrivals for one case; the slots hold boxes 3, 4, ...
    and box 99 is another device's."""
    rng = np.random.default_rng(seed)
    boxes = np.arange(3, 3 + n_slots, dtype=np.int32)
    if case == "all_dead":
        stay = np.zeros((n_slots, CAP), bool)
    else:
        stay = rng.random((n_slots, CAP)) < (0.7 if case == "overflow" else 0.4)
    dest = []
    for i, b in enumerate(boxes):
        free = CAP - stay[i].sum()
        n = {"no_arrivals": 0, "all_dead": 0, "fill_free": free, "overflow": free + 5}[case]
        dest += [b] * int(n)
    width = len(dest) + 24
    dest += [-1] * 12 + [99] * 12  # padding and arrivals for other boxes
    gdest = rng.permutation(np.asarray(dest, np.int32))
    fields = {k: rng.normal(size=(n_slots, CAP)).astype(np.float32) for k in _PKEYS}
    gpack = {k: rng.normal(size=width).astype(np.float32) for k in _PKEYS}
    # payloads whose bits a float comparison would not tell apart
    fields["ux"][:, ::7] = -0.0
    gpack["uy"][::5] = np.nan
    centers = rng.normal(size=(n_slots, 2)).astype(np.float32)
    return stay, fields, boxes, centers, gdest, gpack


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("n_slots", [1, 4])
@pytest.mark.parametrize("case", ["no_arrivals", "fill_free", "overflow", "all_dead"])
def test_merge_equals_argsort_gather_reference(case, n_slots):
    ins = tuple(jax.tree.map(jnp.asarray, _inputs(case, n_slots)))
    got, got_dropped = jax.jit(merge_slots, static_argnums=6)(*ins, CAP)
    want, want_dropped = jax.jit(reference_merge, static_argnums=6)(*ins, CAP)
    assert set(got) == set(want) == set(_PKEYS) | {"alive"}
    for k in want:
        assert got[k].shape == (n_slots, CAP)
        np.testing.assert_array_equal(_bits(got[k]), _bits(want[k]), err_msg=k)
    np.testing.assert_array_equal(got_dropped, want_dropped)
    alive, dropped = np.asarray(got["alive"]), np.asarray(got_dropped)
    if case == "fill_free":
        assert alive.all() and not dropped.any()
    elif case == "overflow":
        assert alive.all() and (dropped == 5).all()
    elif case == "all_dead":
        assert not alive.any() and not dropped.any()
    else:
        assert not dropped.any()


# ---------------------------------------------------------------------------
# the compiled interval program
# ---------------------------------------------------------------------------

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _problem():
    from repro.pic import laser_ion_problem

    return laser_ion_problem(nz=32, nx=32, box_cells=8, ppc=2, seed=0)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("comm", ["neighbor", "ring"])
def test_merge_scope_sorts_and_gathers_nothing(comm, backend):
    rt = ShardedRuntime(_problem(), 1, lb_interval=2, comm=comm, engine_backend=backend)
    opcodes = []
    for line in rt.interval_hlo().splitlines():
        m, name = _INSTRUCTION.match(line), _OP_NAME.search(line)
        if m and name and "/merge/" in name.group(1):
            opcodes.append(m.group(1))
    assert "sort" in opcodes
    assert "gather" not in opcodes


@pytest.fixture
def reference_interval(monkeypatch):
    """One interval's outcome with the reference merge in the program."""
    with monkeypatch.context() as m:
        m.setattr(sharded_runtime, "merge_slots", reference_merge)
        return _one_interval()


def _one_interval():
    rt = ShardedRuntime(_problem(), 1, lb_interval=4, comm="neighbor", engine_backend="xla")
    before = rt.box_counts().copy()
    rt.run(4)
    return {
        "species": jax.device_get(rt._species),
        "fields": jax.device_get(rt.fields),
        "counters": rt.slot_costs(),
        "history": {k: list(v) for k, v in rt.history.items()},
        "box_counts": rt.box_counts(),
        "moved": not np.array_equal(before, rt.box_counts()),
        "dropped": rt.dropped_total,
    }


def test_interval_equals_reference_merge_bitwise(reference_interval):
    got, want = _one_interval(), reference_interval
    assert want["moved"]  # particles crossed boxes, so the merge had arrivals
    leaves_got, tree_got = jax.tree.flatten(got)
    leaves_want, tree_want = jax.tree.flatten(want)
    assert tree_got == tree_want
    for a, b in zip(leaves_got, leaves_want):
        np.testing.assert_array_equal(_bits(a), _bits(b))

"""Trace reduction against a small hand-made trace whose sums are known."""
import json
from pathlib import Path

import pytest

import trace_reduce as tr

HERE = Path(__file__).resolve().parent


@pytest.fixture
def trace():
    with open(HERE / "small_trace.json") as f:
        return tr.Trace.from_json(json.load(f))


def test_union_and_overlap():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)]) == [(1, 4), (5, 8)]
    assert tr.overlap([(1, 4), (5, 8)], [(3, 6)]) == 2


def test_window_busy_and_idle(trace):
    assert trace.window() == [(1000, 3000)]
    assert trace.window_s() == pytest.approx(2000e-9)
    # device 0: [1000,1400) + [1600,1700) + [1800,3000) inside the window
    assert trace.busy_s(0) == pytest.approx(1700e-9)
    assert trace.busy_s(1) == pytest.approx(1000e-9)
    assert trace.busy_s_mean() == pytest.approx(1350e-9)
    assert trace.idle_share() == pytest.approx(1 - 1350 / 2000)


@pytest.mark.parametrize(
    "pattern, per_device",
    [
        (tr.SORT, (200, 0)),
        (tr.KERNEL, (150, 1000)),
        (tr.COLLECTIVE, (100, 0)),
    ],
)
def test_op_sums_by_category(trace, pattern, per_device):
    match = tr.matches(pattern)
    for dev, ns in enumerate(per_device):
        assert trace.op_s(dev, match) == pytest.approx(ns * 1e-9)
    assert trace.op_s_mean(match) == pytest.approx(sum(per_device) / 2 * 1e-9)


def test_breakdown_names_gaps_by_host_span(trace):
    b = trace.breakdown()
    names = [n for n, _ in b["device_ops"]]
    # the while op holds fusion.4 and is left out of the ops
    assert names[0] == "fusion.4" and "sort.2" in names and "while.5" not in names
    assert b["device_ops"][0][1] == pytest.approx(1100 / 2 * 1e-9)
    assert b["idle_gaps"][0] == ["window_interval", pytest.approx(1000e-9)]
    assert b["idle_gaps"][1] == ["run_lb", pytest.approx(200e-9)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_op_name_from_hlo_text():
    text = "%fusion.322 = pred[4276224]{0} fusion(pred[9,534528] %pad.4, s32[4] %sort.100), kind=kCustom"
    assert tr.op_name(text) == "fusion.322"
    assert not tr.SORT.search(tr.op_name(text))
    assert tr.op_name("%gather_push_move.1") == "gather_push_move.1"
    assert tr.KERNEL.search("deposit_local_tiles.15")


def test_no_window_span_is_an_error():
    t = tr.Trace(devices={0: []}, host=[])
    with pytest.raises(ValueError, match="window_interval"):
        t.window()


def _covered(ivs, lo, hi):
    """Brute force: cut [lo, hi) at every interval edge and count the
    pieces whose middle some interval covers."""
    import numpy as np

    if not ivs:
        return 0
    a = np.array(ivs, dtype=np.int64)
    edges = np.unique(np.clip(np.concatenate([a.ravel(), [lo, hi]]), lo, hi))
    mid = (edges[:-1] + edges[1:]) / 2
    hit = ((a[:, :1] <= mid) & (mid < a[:, 1:])).any(axis=0)
    return int(np.diff(edges)[hit].sum())


def test_recorded_trace_against_brute_force():
    """One step of laser_ion.1chip at ppc 64, recorded on a TPU v5e chip
    (names reduced to instruction names)."""
    with open(HERE / "recorded_trace.json") as f:
        t = tr.Trace.from_json(json.load(f))
    (lo, hi), = t.window()
    ops = t.devices[0]
    assert t.busy_s(0) == pytest.approx(_covered([(o.start, o.end) for o in ops], lo, hi) * 1e-9)
    for pattern in (tr.SORT, tr.KERNEL):
        sel = [(o.start, o.end) for o in ops if pattern.search(o.name)]
        assert sel
        assert t.op_s(0, tr.matches(pattern)) == pytest.approx(_covered(sel, lo, hi) * 1e-9)
    assert 0 <= t.idle_share() < 0.01
    b = t.breakdown()
    assert len(b["device_ops"]) == 10 and not any(n.startswith("while") for n, _ in b["device_ops"])


#: lines of a merge, ``concatenate([slot, arrivals])[kidx]`` over 9 slots,
#: as XLA compiled it for a described TPU v5e (backend configs cut)
MERGE_HLO = """\
%fused_computation (param_0.2: f32[9,9216], param_1.5: s32[73728]) -> f32[73728] {
  %gather.4 = f32[9,8192]{1,0:T(8,128)} gather(%param_0.2, %custom-call.1), offset_dims={}, metadata={op_name="jit(f)/vmap()/gather" stack_frame_id=8}
}
ENTRY %main.1 (slot.1: f32[9,8192], arr.1: f32[9,1024]) -> (f32[9,8192], pred[9,8192]) {
  %sort.1 = s32[9,9216]{1,0:T(8,128)S(1)} get-tuple-element(%sort.0), index=1, metadata={op_name="jit(f)/vmap(jit(argsort))/sort" stack_frame_id=7}
  %pad_add_fusion = pred[9,9216]{1,0:T(8,128)(4,1)S(1)} fusion(%broadcast_compare_fusion.1, %broadcast_compare_fusion), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(f)/vmap()/concatenate" stack_frame_id=5}, backend_config={"flag_configs":[]}
  %fusion.1 = pred[73728]{0:T(1024)(128)(4,1)S(1)} fusion(%pad_add_fusion, %reshape), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(f)/vmap()/gather" stack_frame_id=9}, backend_config={"flag_configs":[]}
  %fusion = f32[73728]{0:T(1024)S(1)} fusion(%pad_maximum_fusion, %reshape), kind=kCustom, calls=%fused_computation, metadata={op_name="jit(f)/vmap()/gather" stack_frame_id=8}
  ROOT %tuple.2 = (f32[9,8192]{1,0}, pred[9,8192]{1,0}) tuple(%bitcast.1, %bitcast.2)
}
"""


def test_program_ops_names_fusions_by_jax_op():
    ops = tr.program_ops(MERGE_HLO)
    assert ops["fusion"] == ops["fusion.1"] == ops["gather.4"] == "gather"
    assert ops["pad_add_fusion"] == "concatenate" and ops["sort.1"] == "sort"
    assert "tuple.2" not in ops  # no metadata, no op


def test_merge_reader_sums_gather_and_concatenate_ops():
    import harness

    trace = tr.Trace.from_json({
        "devices": {"0": [["fusion", 100, 400], ["pad_add_fusion", 400, 450],
                          ["sort.1", 450, 700], ["fusion.1", 700, 1000],
                          ["deposit_local_tiles", 1000, 1100]]},
        "host": [["window_interval", 0, 2000]],
    })

    class Run:
        steps = 2
        program_ops = tr.program_ops(MERGE_HLO)

    Run.trace = trace
    read = harness.load_reader("merge_ms_per_step")
    assert read(Run) == pytest.approx(1e3 * 650e-9 / 2)
    Run.program_ops = None
    assert read(Run) is None

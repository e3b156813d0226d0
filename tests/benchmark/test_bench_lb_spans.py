"""The readers of the program's LB-turn host spans, on a hand-made trace.

Times are in ns.  The window is [1000, 3000) (two ``window_interval``
spans), two LB intervals.

- device 0 runs [900, 1200) and [1500, 2600): idle [1200, 1500), [2600, 3000);
- device 1 runs [1000, 1300) and [2000, 2600): idle [1300, 2000), [2600, 3000).

LB-turn spans: ``interval_dispatch`` [800, 1100) (half outside the window)
and [2550, 2700); ``harvest_fetch`` [1400, 1450); ``history_decode``
[1450, 1600); ``recommit`` [1550, 1700), overlapping the decode;
``lb_decide`` [1600, 1650) (nested in the recommit) and [2700, 2800), and
two outside the window, [500, 600) and [3100, 3200).  ``harvest_wait``
[1100, 1400) is not an LB-turn span.  Their union inside the window is
[1000, 1100) + [1400, 1700) + [2550, 2800): 650 ns.

Device idle under it: device 0, [1400, 1500) + [2600, 2800) = 300 ns;
device 1, [1400, 1700) + [2600, 2800) = 500 ns.  Mean 400 ns over two
intervals: 200 ns = 2e-4 ms an interval.  ``lb_decide`` inside the window:
50 and 100 ns, mean 75 ns = 7.5e-5 ms.
"""
from types import SimpleNamespace

import pytest

import harness
import trace_reduce as tr

DEVICES = {"0": [["fusion.1", 900, 1200], ["fusion.2", 1500, 2600]],
           "1": [["fusion.1", 1000, 1300], ["fusion.2", 2000, 2600]]}
WINDOW = [["window_interval", 1000, 2000], ["window_interval", 2000, 3000]]
TURN = [
    ["interval_dispatch", 800, 1100],
    ["harvest_wait", 1100, 1400],
    ["harvest_fetch", 1400, 1450],
    ["history_decode", 1450, 1600],
    ["recommit", 1550, 1700],
    ["lb_decide", 1600, 1650],
    ["interval_dispatch", 2550, 2700],
    ["lb_decide", 2700, 2800],
    ["lb_decide", 500, 600],
    ["lb_decide", 3100, 3200],
]


def _run(devices=DEVICES, host=WINDOW + TURN, intervals=2):
    trace = tr.Trace.from_json({"devices": devices, "host": host})
    return SimpleNamespace(trace=trace, intervals=intervals)


@pytest.fixture(scope="module")
def lb_decide_ms():
    return harness.load_reader("lb_decide_ms")


@pytest.fixture(scope="module")
def lb_turn_idle_ms():
    return harness.load_reader("lb_turn_idle_ms")


def test_lb_decide_counts_spans_starting_in_the_window(lb_decide_ms):
    assert lb_decide_ms(_run()) == pytest.approx(75e-6)


def test_lb_turn_idle_averages_the_devices(lb_turn_idle_ms):
    assert lb_turn_idle_ms(_run()) == pytest.approx(400e-6 / 2)


@pytest.mark.parametrize("device, idle_ns", [("0", 300), ("1", 500)])
def test_busy_time_under_a_span_is_not_idle(lb_turn_idle_ms, device, idle_ns):
    run = _run(devices={device: DEVICES[device]}, intervals=1)
    assert lb_turn_idle_ms(run) == pytest.approx(idle_ns * 1e-6)


def test_spans_outside_the_window_do_not_count(lb_turn_idle_ms, lb_decide_ms):
    outside = [["lb_decide", 3000, 3500], ["recommit", 0, 1000], ["history_decode", 3200, 3300]]
    run = _run(host=WINDOW + TURN + outside)
    assert lb_turn_idle_ms(run) == pytest.approx(400e-6 / 2)
    assert lb_decide_ms(run) == pytest.approx(75e-6)


def test_nested_and_overlapping_spans_count_once(lb_turn_idle_ms):
    """The same spans twice over, and one more nested in the recommit,
    leave the union, and so the reading, as it was."""
    nested = [["history_decode", 1560, 1590]]
    run = _run(host=WINDOW + TURN + TURN + nested)
    assert lb_turn_idle_ms(run) == pytest.approx(400e-6 / 2)


def test_no_span_reads_none(lb_turn_idle_ms, lb_decide_ms):
    """A program without the spans (the window alone) reads nothing."""
    bare = _run(host=WINDOW)
    assert lb_decide_ms(bare) is None and lb_turn_idle_ms(bare) is None
    no_decide = _run(host=WINDOW + [s for s in TURN if s[0] != "lb_decide"])
    assert lb_decide_ms(no_decide) is None
    assert lb_turn_idle_ms(no_decide) is not None
    untraced = SimpleNamespace(trace=None, intervals=2)
    assert lb_decide_ms(untraced) is None and lb_turn_idle_ms(untraced) is None

"""Reduction of a profiler trace to device busy time, op sums and gaps.

:func:`load` reads the ``.xplane.pb`` the JAX profiler wrote and keeps
what the readers need as a :class:`Trace`: per device, the ops that ran
(instruction name, start and end in ns, on the host's clock); per host
thread, the spans (name, start, end).  :meth:`Trace.from_json` reads the
same from plain JSON, which is how the tests keep small traces.

The window is the union of the host spans named ``window_interval`` (the
benchmark's own annotation around each ``run()`` of the window); every sum
counts only the parts of ops that fall inside it.

A device op names only its instruction; :func:`program_ops` reads from the
compiled program's text which JAX op each instruction was made from, so a
reader can sum ops by kind where XLA gives them anonymous fusion names.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

#: the benchmark's host span around each timed ``run()`` call
WINDOW_SPAN = "window_interval"

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"

#: op-name patterns of each category the readers sum
SORT = re.compile(r"sort", re.I)
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all|"
    r"ppermute|\bsend\b|\brecv\b|send-done|recv-done",
    re.I,
)
#: the Pallas kernels of the particle phase: the custom calls carry the
#: names of their jitted wrappers (``deposit_local_tiles``,
#: ``gather_push_move``) or of the kernel functions
KERNEL = re.compile(r"deposit_local_tiles|gather_push_move|_deposition_kernel|_gather_push_kernel")
#: control-flow ops whose events span the ops they run (a scanned interval
#: is one ``while`` op): they count towards busy time, not the breakdown
CONTAINER = re.compile(r"^(while|conditional|call)\b")


@dataclass
class Op:
    """A device op: its instruction name and ``[start, end)`` in ns."""

    name: str
    start: int
    end: int


@dataclass
class Trace:
    #: device id -> ops, sorted by start
    devices: Dict[int, List[Op]]
    #: host spans: (name, start, end), any thread
    host: List[Tuple[str, int, int]]

    @classmethod
    def from_json(cls, obj: Dict) -> "Trace":
        """``{"devices": {id: [[name, start, end], ...]}, "host": [[name,
        start, end], ...]}`` -> a trace."""
        devices = {
            int(d): sorted((Op(n, int(s), int(e)) for n, s, e in ops), key=lambda o: o.start)
            for d, ops in obj["devices"].items()
        }
        host = [(n, int(s), int(e)) for n, s, e in obj["host"]]
        return cls(devices=devices, host=host)

    # -- the window --------------------------------------------------------
    def window(self) -> List[Tuple[int, int]]:
        """Merged intervals of the window's host spans."""
        spans = [(s, e) for n, s, e in self.host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        return union(spans)

    def window_s(self) -> float:
        w = self.window()
        return (w[-1][1] - w[0][0]) * 1e-9

    # -- device time -------------------------------------------------------
    def busy_s(self, device: int) -> float:
        """Seconds in the window in which some op ran on ``device``."""
        busy = union((o.start, o.end) for o in self.devices[device])
        return overlap(busy, [(self.window()[0][0], self.window()[-1][1])]) * 1e-9

    def busy_s_mean(self) -> float:
        return float(np.mean([self.busy_s(d) for d in self.devices]))

    def idle_share(self) -> float:
        """1 - busy over the window, averaged over the devices."""
        return 1.0 - self.busy_s_mean() / self.window_s()

    def op_s(self, device: int, match: Callable[[Op], bool]) -> float:
        """Seconds of ``device``'s ops that ``match``, inside the window
        (overlapping ops of one device counted once)."""
        w = [(self.window()[0][0], self.window()[-1][1])]
        ivs = union((o.start, o.end) for o in self.devices[device] if match(o))
        return overlap(ivs, w) * 1e-9

    def op_s_mean(self, match: Callable[[Op], bool]) -> float:
        return float(np.mean([self.op_s(d, match) for d in self.devices]))

    # -- what the next reader of the ledger sees ---------------------------
    def breakdown(self, top: int = 10) -> Dict[str, List]:
        """The ``top`` ops by device seconds in the window (mean over
        devices), and the ``top``
        longest idle gaps on any device, each named by the innermost host
        span that covers it."""
        lo, hi = self.window()[0][0], self.window()[-1][1]
        by_name: Dict[str, float] = {}
        for ops in self.devices.values():
            for o in ops:
                if CONTAINER.match(o.name):
                    continue
                d = min(o.end, hi) - max(o.start, lo)
                if d > 0:
                    by_name[o.name] = by_name.get(o.name, 0.0) + d * 1e-9 / len(self.devices)
        device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for dev, ops in self.devices.items():
            busy = overlap_clip(union((o.start, o.end) for o in ops), lo, hi)
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((b - a, a, b))
        gaps.sort(reverse=True)
        idle_gaps = [
            [self.host_activity(a, b), d * 1e-9] for d, a, b in gaps[:top]
        ]
        return {"device_ops": [[n, s] for n, s in device_ops], "idle_gaps": idle_gaps}

    def host_activity(self, a: int, b: int) -> str:
        """Name of the shortest host span covering the middle of
        ``[a, b)``, or ``"host idle"``."""
        mid = (a + b) // 2
        covering = [(e - s, n) for n, s, e in self.host if s <= mid < e]
        return min(covering)[1] if covering else "host idle"


def op_name(text: str) -> str:
    """The instruction name of a device op event, whose name may be the
    whole HLO instruction (``%fusion.3 = f32[...] fusion(...), ...``)."""
    head = text.split(" = ", 1)[0] if " = " in text else text
    return head.strip().lstrip("%")


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, merged ``[start, end)`` intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap_clip(ivs: Sequence[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in ivs if min(e, hi) > max(s, lo)]


def overlap(a: Sequence[Tuple[int, int]], b: Sequence[Tuple[int, int]]) -> int:
    """Total length of the intersection of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def matches(pattern: re.Pattern) -> Callable[[Op], bool]:
    return lambda o: bool(pattern.search(o.name))


_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*\bmetadata=\{[^}]*?op_name="([^"]*)"')


def program_ops(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> the JAX op it was made from, read from the
    ``op_name`` XLA keeps in each instruction's metadata (its last path
    component: ``gather``, ``concatenate``, ``sort``, ...), over every
    computation of a compiled program's text.  A fusion carries the op of
    its root, so anonymous fusions are named by what they do."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2).rsplit("/", 1)[-1]
    return out


# ---------------------------------------------------------------------------
# reading the profiler's file
# ---------------------------------------------------------------------------


def load(trace_dir: str, device_ids: Optional[Sequence[int]] = None) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices: Dict[int, List[Op]] = {}
    host: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if device_ids is not None and dev not in device_ids:
                continue
            ops = []
            for line in plane.lines:
                if line.name != _OPS_LINE:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    ops.append(Op(op_name(ev.name), s, s + int(ev.duration_ns)))
            devices[dev] = sorted(ops, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    host.append((ev.name, s, s + int(ev.duration_ns)))
    if not devices:
        raise ValueError(f"no TPU device plane in {files[-1]}")
    return Trace(devices=devices, host=host)

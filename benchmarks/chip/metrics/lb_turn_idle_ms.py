"""Host driver + LB turn: device-idle milliseconds per LB interval spent
under the program's LB-turn host spans (``interval_dispatch``,
``harvest_fetch``, ``history_decode``, ``lb_decide``, ``recommit``): the
device idle inside the window that falls in the union of those spans,
summed per device, averaged over the devices, over the window's intervals.
It is the LB turn as the device sees it, what ``pipeline="async"`` should
hide.  None where the program has no such span."""

from trace_reduce import overlap, overlap_clip, union

LB_TURN = ("interval_dispatch", "harvest_fetch", "history_decode", "lb_decide", "recommit")


def read(run):
    if run.trace is None or run.intervals == 0:
        return None
    w = run.trace.window()
    lo, hi = w[0][0], w[-1][1]
    turn = overlap_clip(union((s, e) for n, s, e in run.trace.host if n in LB_TURN), lo, hi)
    if not turn:
        return None
    covered = sum(e - s for s, e in turn)
    idle = [
        covered - overlap(turn, overlap_clip(union((o.start, o.end) for o in ops), lo, hi))
        for ops in run.trace.devices.values()
    ]
    return 1e-6 * sum(idle) / len(idle) / run.intervals

"""Host driver + LB turn: host milliseconds per LB interval in which the
driver was not blocked on the device (the window less the program's
``pipeline_stats()["host_blocked_s"]``, over the window's intervals)."""


def read(run):
    if run.intervals == 0:
        return None
    return 1e3 * (run.window_s - run.host_blocked_s) / run.intervals

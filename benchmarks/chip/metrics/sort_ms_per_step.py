"""Exchange: device milliseconds per step of sort ops (the emigration and
merge argsorts), averaged over the chips."""

import trace_reduce


def read(run):
    if run.trace is None or run.steps == 0:
        return None
    s = run.trace.op_s_mean(trace_reduce.matches(trace_reduce.SORT))
    return 1e3 * s / run.steps if s > 0 else None

"""Host driver + LB turn: the mean duration, in ms, of the program's
``lb_decide`` host spans that start inside the window: the balancer's own
turn (straggler observation, knapsack, count repair, locality repair), the
overhead of the paper's Fig. 6a.  None where the program has no such span."""


def read(run):
    if run.trace is None:
        return None
    w = run.trace.window()
    lo, hi = w[0][0], w[-1][1]
    spans = [e - s for n, s, e in run.trace.host if n == "lb_decide" and lo <= s < hi]
    return 1e-6 * sum(spans) / len(spans) if spans else None

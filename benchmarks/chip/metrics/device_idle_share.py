"""Device: 1 less the union of device-busy intervals over the traced
window, averaged over the chips."""


def read(run):
    if run.trace is None:
        return None
    return run.trace.idle_share()

"""Slot layout: particle lanes not holding an alive particle over all
particle lanes, on the device that holds the most alive particles, at the
window's end (an exact count)."""


def read(run):
    d = max(range(len(run.alive_per_device)), key=lambda i: run.alive_per_device[i])
    lanes = run.lanes_per_device[d]
    return 1.0 - run.alive_per_device[d] / lanes if lanes else None

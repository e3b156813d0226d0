"""Particle kernels: the least time the kernels' required work takes on
this chip (``work.particle_phase_work`` from the alive particles at the
window's end, over ``work.roofline_s``) over the kernels' device time per
step, in percent.  The bound is memory bandwidth (see ``work``)."""

import trace_reduce
import work


def read(run):
    if run.trace is None or run.steps == 0:
        return None
    kernel_s = run.trace.op_s_mean(trace_reduce.matches(trace_reduce.KERNEL)) / run.steps
    if kernel_s <= 0:
        return None
    ops, bytes_ = work.particle_phase_work(run.alive_per_box, run.domain.box)
    least, _ = work.roofline_s(ops / run.cell.chips, bytes_ / run.cell.chips, run.device_kind)
    return 100.0 * least / kernel_s

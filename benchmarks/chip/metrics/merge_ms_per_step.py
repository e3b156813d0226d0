"""Exchange: device milliseconds per step of the ops the interval program
makes from JAX ``gather`` and ``concatenate``, averaged over the chips.

In the runtime these are the merge (``concatenate([slot, arrivals])[kidx]``
over every lane of every slot, for each particle array of each species)
and the emigrant packs (``take_along_axis`` over the pack width).  XLA
fuses each into an anonymous ``fusion.<n>``, so ops are found by the JAX
op their instruction was made from in the compiled program
(``trace_reduce.program_ops``), never by fusion number."""

MERGE_OPS = ("gather", "concatenate")


def read(run):
    if run.trace is None or run.steps == 0 or not run.program_ops:
        return None
    names = {n for n, op in run.program_ops.items() if op in MERGE_OPS}
    s = run.trace.op_s_mean(lambda o: o.name in names)
    return 1e3 * s / run.steps if s > 0 else None

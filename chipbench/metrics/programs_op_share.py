"""Share of several programs' device time spent in the operations whose
trace names match ``pattern``, in percent: ``program_op_share`` over
every program of ``programs`` (keys of the configuration's
``device_programs``) that ran in the traced seconds, matched seconds
over all seconds, summed.  For the decode rows of a cell whose ticks
mostly carry a prompt chunk: their attention runs in ``jit_step`` and,
carried, in ``jit_chunk``, and some traced windows hold no plain step
at all."""
from . import program_op_share


def read(run, programs, pattern):
    got = [g for g in (program_op_share.matched_and_total(run, p, pattern)
                       for p in programs) if g]
    total = sum(t for _m, t in got)
    return 100.0 * sum(m for m, _t in got) / total if total else None

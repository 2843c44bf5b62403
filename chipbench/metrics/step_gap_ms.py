"""Median, over the decode steps of the traced seconds, of the host's
time on one side of the step program's run on the device, in ms.
``side`` "launch": from the start of the program's ``decode.step`` span
to the program's start on the device (inputs to the device, dispatch,
and the wait for whatever the device still runs).  ``side`` "return":
from the program's end on the device to the end of the span (the copy
back of tokens and logits, and the wake-up).  The span's clock is put
on the trace's by the line through the two marks, good to 0.1 ms."""
import statistics

from .. import program_spans as ps


def read(run, side):
    gaps = ps.step_gaps(run, ps.spans(run))
    if not gaps:
        return None
    return 1e3 * statistics.median(g[side == 'return'] for g in gaps)

"""Share of a program's device time spent in the operations whose trace
names match ``pattern``, in percent.  ``program`` names a key of the
configuration's ``device_programs``; the names are those of
``xplane.op_seconds`` (``<op>:<fusion kind>:<largest result shape>``),
so a pattern can name an operation (``ragged-dot``), a kernel, or a
shape only one layer produces.  PERF.md justifies each pattern from the
program's HLO."""
import re

from .. import xplane


def matched_and_total(run, program, pattern):
    """(seconds in matching ops, seconds in all ops) of the program
    inside the traced window; None without a trace of it."""
    tr = run.obs.get('trace')
    win = tr and xplane.window(tr)
    if not win:
        return None
    name = run.config['device_programs'][program]
    ops = [(k.split('/', 1)[1], v)
           for k, v in xplane.op_seconds(tr, win).items()
           if k.split('/', 1)[0] == name]
    total = sum(v for _k, v in ops)
    if not total:
        return None
    rx = re.compile(pattern)
    return sum(v for k, v in ops if rx.search(k)), total


def read(run, program, pattern):
    got = matched_and_total(run, program, pattern)
    return 100.0 * got[0] / got[1] if got else None

"""Median device milliseconds of one execution of a program, from the
trace's module line; ``program`` names a key of the configuration's
``device_programs``."""
import statistics

from .. import xplane


def read(run, program):
    tr = run.obs.get('trace')
    if tr is None:
        return None
    calls = xplane.module_calls(tr, xplane.window(tr),
                                run.config['device_programs'][program])
    return 1e3 * statistics.median(calls) if calls else None

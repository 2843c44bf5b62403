"""Milliseconds per step in which a collective ran on a chip and no
compute did, averaged over the chips."""
from .. import xplane


def read(run, program):
    tr = run.obs.get('trace')
    if tr is None:
        return None
    win = xplane.window(tr)
    calls = xplane.module_calls(tr, win,
                                run.config['device_programs'][program])
    exposed = xplane.exposed_collective_s(tr, win)
    if not calls or exposed is None:
        return None
    return 1e3 * exposed / len(calls)

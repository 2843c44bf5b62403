"""The window group's live pages over what the same streams hold live
in the group that keeps every position, in percent, over the window's
decode steps and the chunks that carried one: the two counters of their
spans (``kv_window_live_pages``, ``kv_full_live_pages``).  A program
whose spans do not carry them gives none."""
from .. import program_spans as ps
from . import held_steps


def read(run):
    lo, hi = ps.window(run)
    got = [s for s in held_steps.steps(run, lo, hi)
           if 'kv_window_live_pages' in s.args]
    full = sum(s.args['kv_full_live_pages'] for s in got)
    return 100.0 * sum(s.args['kv_window_live_pages']
                       for s in got) / full if full else None

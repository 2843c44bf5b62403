"""Mean self time of one of the program's spans over the untraced
window, in ms: its duration less its child spans' (for ``server.tick``:
what the loop does itself around admission and the decode step, the
benchmark's tap at the engine's boundary included)."""
import statistics

from .. import program_spans as ps


def read(run, span):
    events = ps.spans(run)
    mine = events and ps.inside(events, *ps.window(run), name=span)
    if not mine:
        return None
    kids = ps.children(events)
    return 1e3 * statistics.mean(ps.self_seconds(s, kids) for s in mine)

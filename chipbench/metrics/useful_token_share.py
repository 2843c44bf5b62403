"""Share of the prefill programs' token positions that held a prompt
token, in percent: sum of ``tokens`` over sum of ``bucket`` over the
engine's prefill spans of the untraced window (the rest is padding to
the bucket).  A count, not a time."""
from .. import program_spans as ps


def read(run):
    events = ps.spans(run)
    calls = [s for s in ps.inside(events or (), *ps.window(run))
             if s.name in ps.PREFILLS]
    padded = sum(s.args['bucket'] for s in calls)
    return 100.0 * sum(s.args['tokens'] for s in calls) / padded \
        if padded else None

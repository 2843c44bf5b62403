"""A percentile of the durations of one of the program's per-request
spans (``server.request.queued``, ``.prefill``, ``.decode``), over the
requests the program completed in the untraced window, in ms."""
from .. import harness, program_spans as ps


def read(run, span, q):
    events = ps.spans(run)
    if not events:
        return None
    lo, hi = ps.window(run)
    # a request's spans share ``rid``; it completed when ``.decode`` ended
    done = {s.args['rid'] for s in events
            if s.name == 'server.request.decode' and lo <= s.t1 < hi}
    d = [s.t1 - s.t0 for s in events
         if s.name == span and s.args['rid'] in done]
    return 1e3 * harness.percentile(d, q) if d else None

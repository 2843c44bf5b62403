"""How long a tick's prefills held the running streams back, in ms: for
every tick of the untraced window that ran a decode step, the seconds of
the engine's prefill calls in that tick before the step; a percentile
over those ticks (most of them ran no prefill and count as 0)."""
from .. import harness, program_spans as ps


def read(run, q):
    events = ps.spans(run)
    stalls = events and ps.prefill_stalls(events, *ps.window(run))
    return 1e3 * harness.percentile(stalls, q) if stalls else None

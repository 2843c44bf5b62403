"""A percentile of the time from a request's due time to its first
token, over the requests completed in the untraced window, in ms."""
from .. import harness


def read(run, q):
    done = run.obs.get('done')
    if not done:
        return None
    return 1e3 * harness.percentile([r.ttft() for r in done], q)

"""The decode step's share of its roofline, in percent.  A step must
read every weight once (all but the embedding table, of which it reads
one row a slot) and the cached keys and values of every running request
once; at batch 16 it is bound by bytes, not FLOPs.  Least time = those
bytes / the HBM peak; the share is that over the step's device time."""
import statistics

from .. import flops
from ..kinds import serving


def read(run):
    tr = run.obs.get('trace')
    if tr is None or run.peaks is None:
        return None
    calls = serving.program_seconds(run, tr, 'step')
    # the steps inside the traced window, from the tap's own record
    t_a, t_b = run.obs['marks']       # the traced window, host clock
    steps = [s for s in run.obs['tap'].steps if t_a <= s[0] and s[1] <= t_b]
    if not calls or not steps:
        return None
    cached = statistics.mean(s[3] for s in steps)
    need = flops.decode_step_bytes(run.obs['params'], run.obs['slots'],
                                   cached, run.obs['kv_bytes_per_token'])
    return 100.0 * need / run.peaks['hbm_bytes_per_s'] \
        / statistics.median(calls)

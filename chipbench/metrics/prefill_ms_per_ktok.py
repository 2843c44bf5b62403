"""Device milliseconds of the prefill programs (every program of the
configuration's ``device_programs`` but the decode step: prefill and
pack, or the chunks) per 1000 prompt tokens, inside the traced window."""
from ..kinds import serving


def traced_prefills(run):
    """The tap's record of the prefills inside the traced seconds."""
    t_a, t_b = run.obs['marks']       # the traced window, host clock
    return [p for p in run.obs['tap'].prefills if t_a <= p[0] and p[1] <= t_b]


def device_seconds(run, tr):
    return sum(sum(serving.program_seconds(run, tr, k))
               for k in run.config['device_programs'] if k != 'step')


def read(run):
    tr = run.obs.get('trace')
    if tr is None:
        return None
    tokens = sum(p[2] for p in traced_prefills(run))
    return 1e3 * device_seconds(run, tr) / (tokens / 1e3) if tokens else None

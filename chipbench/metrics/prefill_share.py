"""Share of the engine's device time spent in the prefill programs
(all but the decode step), in percent, inside the traced window."""
from ..kinds import serving


def read(run):
    tr = run.obs.get('trace')
    if tr is None:
        return None
    s = {k: sum(serving.program_seconds(run, tr, k))
         for k in run.config['device_programs']}
    total = sum(s.values())
    return 100.0 * (total - s['step']) / total if total else None

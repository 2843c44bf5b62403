"""Prefill's share of its roofline, in percent: the FLOPs the prompts'
own tokens need (padding to the bucket is not useful work) over the
bf16 peak, over the device time of prefill and pack."""
from .. import flops
from . import prefill_ms_per_ktok as pf


def read(run):
    tr = run.obs.get('trace')
    if tr is None or run.peaks is None:
        return None
    need = sum(flops.prefill_flops(run.config, p[2])
               for p in pf.traced_prefills(run))
    spent = pf.device_seconds(run, tr)
    return 100.0 * need / run.peaks['bf16_flops_per_s'] / spent \
        if spent else None

"""The grouped-head decode kernel's share of its roofline over the
decode rows of the traced seconds, in percent.  Least time = the larger
of the live K/V bytes over the HBM peak and of the attention's FLOPs
over the bf16 peak (flops_gqa.py: every position of the layers that
keep everything, ``min(ctx + 1, window)`` of those that read a window),
summed over the plain steps and the chunks that carried a step, over
the device time those calls spend in the operations ``patterns`` match:
the live-pages kernel over the decode rows, by its name (in a chunk the
chunk's own rows run another kernel).  A program whose spans do not
carry the two groups' live positions gives none."""
import numpy as np

from .. import flops_gqa
from . import held_steps


def read(run, patterns):
    got = held_steps.traced_calls(run, patterns)
    if got is None:
        return None
    seconds, spans = got
    full = sum(s.args.get('kv_full_live_positions', 0) for s in spans)
    window = sum(s.args.get('kv_window_live_positions', 0) for s in spans)
    if not (full and window and seconds):
        return None
    need = max(
        flops_gqa.gqa_decode_bytes(
            run.config, full, window,
            np.dtype(run.config['kv_dtype']).itemsize)
        / run.peaks['hbm_bytes_per_s'],
        flops_gqa.gqa_decode_flops(run.config, full, window)
        / run.peaks['bf16_flops_per_s'])
    return 100.0 * need / seconds

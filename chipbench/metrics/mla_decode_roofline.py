"""The latent decode kernel's share of its roofline over the decode
rows of the traced seconds, in percent.  Least time = the larger of the
live latent rows' bytes over the HBM peak and of the absorbed attention's
FLOPs over the bf16 peak (flops_mla.py; at 128 heads over a 576-value
row the two lie within a few percent of each other), summed over the
plain steps and the chunks that carried a step, over the device time
those calls spend in the operations ``patterns`` match: the kernel over
the decode rows, by its name (and, in a chunk, by its shape: the same
kernel also runs the chunk's own rows there)."""
import numpy as np

from .. import flops_mla
from . import held_steps


def read(run, patterns):
    got = held_steps.traced_calls(run, patterns)
    if got is None:
        return None
    seconds, spans = got
    live = sum(s.args.get('kv_latent_live_positions', 0) for s in spans)
    if not live:
        return None
    layers = run.obs['layers']
    need = max(
        flops_mla.mla_decode_bytes(
            run.config, live, layers,
            np.dtype(run.config['kv_dtype']).itemsize)
        / run.peaks['hbm_bytes_per_s'],
        flops_mla.mla_decode_flops(run.config, live, layers)
        / run.peaks['bf16_flops_per_s'])
    return 100.0 * need / seconds

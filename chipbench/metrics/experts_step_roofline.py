"""The expert layers' share of their roofline in the decode step, in
percent.  With at most 32 x 8 routed rows a step the expert layers are
bound by bytes: least time = the bytes of flops_moe.experts_step_bytes
(the touched experts' weights once, the router, the activations) over
the HBM peak; the share is that over the device time a step spends in
the operations ``pattern`` matches (those of ``experts.step_share``)."""
import statistics

from .. import flops_moe
from ..kinds import serving
from . import expert_load, program_op_share


def read(run, pattern):
    if run.obs.get('trace') is None or run.peaks is None:
        return None
    got = program_op_share.matched_and_total(run, 'step', pattern)
    calls = serving.program_seconds(run, run.obs['trace'], 'step')
    steps = expert_load.routed_steps(run, *run.obs['marks'])
    if not got or not got[0] or not calls or not steps:
        return None
    layers, top_k = run.obs['layers'], run.config['num_experts_per_tok']
    need = flops_moe.experts_step_bytes(
        run.obs['params'], layers,
        statistics.mean(s.args['moe_touched'] for s in steps),
        # running rows of a step: its assignments over top_k x layers
        statistics.mean(s.args['moe_assignments'] for s in steps)
        / (top_k * layers), top_k)
    return 100.0 * need / run.peaks['hbm_bytes_per_s'] \
        / (got[0] / len(calls))

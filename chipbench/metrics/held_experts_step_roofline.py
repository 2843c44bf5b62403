"""The expert layers' share of their roofline over the calls that ran
decode rows in the traced seconds (plain steps, and chunks that carried
a step), where the chip holds a share of the routed experts, in percent.
Least time of a call = the larger of its bytes over the HBM peak
(flops_mla.held_experts_step_bytes: the touched held experts, the shared
expert's gate and up, the router, the activations; what binds a step's
64 rows) and of its FLOPs over the bf16 peak
(flops_mla.held_experts_step_flops; what binds a chunk's 320 rows);
the share is the calls' sum of that over the device time they spend in
the operations ``patterns`` match (those of ``experts.held_step_share``
and ``experts.held_chunk_share``)."""
from .. import flops_mla
from . import held_steps


def read(run, patterns):
    got = held_steps.traced_calls(run, patterns)
    if got is None:
        return None
    seconds, spans = got
    c = run.config
    dense = c['first_k_dense_replace']
    layers = c['num_hidden_layers'] - dense
    need = 0.0
    for s in spans:
        # the call's rows (a chunk's own among them): its assignments
        # over top_k x layers
        rows = s.args['moe_all_assignments'] \
            / (c['num_experts_per_tok'] * layers)
        held = s.args['moe_held_assignments'] / layers
        need += max(
            flops_mla.held_experts_step_bytes(
                run.obs['params'], dense, layers,
                s.args['moe_held_touched'], rows, held)
            / run.peaks['hbm_bytes_per_s'],
            flops_mla.held_experts_step_flops(
                run.obs['params'], dense, layers, rows, held)
            / run.peaks['bf16_flops_per_s'])
    return 100.0 * need / seconds

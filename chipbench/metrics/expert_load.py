"""How the decode steps of the untraced window spread their tokens over
the experts, from the ``args`` of the program's ``decode.step`` spans
(``moe_assignments``, ``moe_touched``, ``moe_max_load``).  Counts, not
times.  ``touched_share``: experts with at least one token, mean over
layers and steps, in percent of the experts.  ``max_over_mean``: the most
tokens on one expert in any layer over the mean load of an expert, mean
over steps (1 = perfectly even)."""
import statistics

from .. import program_spans as ps


def routed_steps(run, lo, hi):
    """The ``decode.step`` spans inside [lo, hi) that routed a token."""
    return [s for s in ps.inside(ps.spans(run) or (), lo, hi, ps.STEP)
            if s.args.get('moe_assignments')]


def read(run, what):
    steps = routed_steps(run, *ps.window(run))
    if not steps:
        return None
    experts = run.config['num_experts']
    if what == 'touched_share':
        return 100.0 * statistics.mean(
            s.args['moe_touched'] for s in steps) / experts
    slots_per_layer = experts * run.config['num_hidden_layers']
    return statistics.mean(
        s.args['moe_max_load'] * slots_per_layer
        / s.args['moe_assignments'] for s in steps)

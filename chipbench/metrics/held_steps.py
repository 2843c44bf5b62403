"""The engine calls that ran decode rows over a share of the experts,
from the program's spans: ``decode.step`` and, since most ticks of a
cell with long prompts carry the step in a chunk, ``decode.prefill_chunk``
with ``step_rows`` > 0.  Each carries ``moe_held_assignments``,
``moe_all_assignments``, ``moe_held_touched`` (a step's alone; a carried
chunk's counts include the chunk's rows) and ``kv_latent_live_positions``.
A program without these arguments (the parent commit) gives none."""
import statistics

from .. import program_spans as ps
from ..kinds import serving
from . import program_op_share

CHUNK = 'decode.prefill_chunk'


def steps(run, lo, hi, carried=True):
    """The spans inside [lo, hi) that ran decode rows and say what the
    held experts got: plain steps, and with ``carried`` the chunks that
    carried a step."""
    out = []
    for s in ps.inside(ps.spans(run) or (), lo, hi):
        if 'moe_all_assignments' not in s.args:
            continue
        if s.name == ps.STEP or (carried and s.name == CHUNK
                                 and s.args.get('step_rows')):
            out.append(s)
    return out


# a program of the configuration's ``device_programs`` -> its span
PROGRAM_SPAN = {'step': ps.STEP, 'chunk': CHUNK}


def traced_calls(run, patterns):
    """For a share of a roofline over the calls that ran decode rows in
    the traced seconds: ``patterns`` = {program: pattern of its
    operations}, ``step`` for plain steps, ``chunk`` for chunks that
    carried a step (most ticks of a cell with long prompts).  Returns
    (device seconds those calls spent in the matched operations: a
    program's mean a call times its spans, the spans), or None where
    the trace, the peaks, the operations or the spans' arguments are
    missing."""
    if run.obs.get('trace') is None or run.peaks is None:
        return None
    said = steps(run, *run.obs['marks'])
    seconds, spans = 0.0, []
    for program, pattern in patterns.items():
        got = program_op_share.matched_and_total(run, program, pattern)
        calls = serving.program_seconds(run, run.obs['trace'], program)
        mine = [s for s in said if s.name == PROGRAM_SPAN[program]]
        if got and got[0] and calls and mine:
            seconds += got[0] / len(calls) * len(mine)
            spans += mine
    return (seconds, spans) if spans else None


def read(run, what):
    """``tokens_per_expert``: mean tokens a held expert gets a step
    (plain steps: the held assignments over the held experts and the
    routing layers).  ``local_hit_share``: share of the assignments of
    steps and carried chunks that fall on held experts, in percent
    (6.25 = 1/16 where routing is uniform)."""
    lo, hi = ps.window(run)
    if what == 'local_hit_share':
        got = steps(run, lo, hi)
        total = sum(s.args['moe_all_assignments'] for s in got)
        return 100.0 * sum(s.args['moe_held_assignments']
                           for s in got) / total if total else None
    got = steps(run, lo, hi, carried=False)
    if not got:
        return None
    c = run.config
    layers = c['num_hidden_layers'] - c['first_k_dense_replace']
    return statistics.mean(s.args['moe_held_assignments'] for s in got) \
        / (c['n_routed_experts'] * layers)

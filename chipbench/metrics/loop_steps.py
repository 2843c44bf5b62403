"""The engine calls that ran decode rows through a loop of recurrences,
from the program's spans: ``decode.step`` and ``decode.prefill_chunk``
with ``step_rows`` > 0.  Each carries ``ut_steps``, ``loop_passes``
(passes over a weight layer), ``kv_loop_live_positions`` (the running
slots' positions, times the recurrences) and ``loop_exit_mass`` (the
exit distribution's mean over the running rows, one number a
recurrence).  A program without these arguments gives none.

``read(run, what)``:
``passes_per_weight_layer``: ``loop_passes`` over the layers, mean over
the untraced window's plain steps (4.0 as published: where an early exit
would show).  ``exit_mass_last``: the share of the exit distribution the
LAST recurrence holds, in percent, mean over the same steps (a property
of the seeding: it shows that the gate ran).  ``step_roofline``: the
least time a step's bytes take (flops_loop.py ``loop_step_bytes`` at the
traced plain steps' mean ``kv_loop_live_positions``) at the HBM peak,
over the step program's mean device time, in percent.
``op_share``: the device time ``program`` spends in the operations
``pattern`` matches over the program's own device time (the trace's
module line), in percent: a loop lowered as one ``while`` is an
operation of the trace that encloses its body's, so a share of the SUM
of a program's operations would count the body twice.
``decode_roofline``: the larger of the live K/V bytes at the HBM peak and
of the attention's FLOPs at the bf16 peak, over the plain steps and the
carrying chunks of the traced seconds, over the device time those calls
spend in the operations ``patterns`` match (the live-pages kernel over
the decode rows), in percent."""
import statistics

import numpy as np

from .. import flops_loop
from .. import program_spans as ps
from ..kinds import serving
from . import program_op_share
from .held_steps import CHUNK, PROGRAM_SPAN


def steps(run, lo, hi, carried=True):
    """The spans inside [lo, hi) that ran decode rows through the loop:
    plain steps, and with ``carried`` the chunks that carried a step."""
    return [s for s in ps.inside(ps.spans(run) or (), lo, hi)
            if 'loop_passes' in s.args
            and (s.name == ps.STEP or (carried and s.name == CHUNK
                                       and s.args.get('step_rows')))]


def _itemsizes(run):
    return (np.dtype(run.config['dtype']).itemsize,
            np.dtype(run.config['kv_dtype']).itemsize)


def step_roofline(run):
    tr = run.obs.get('trace')
    if tr is None or run.peaks is None:
        return None
    calls = serving.program_seconds(run, tr, 'step')
    said = steps(run, *run.obs['marks'], carried=False)
    if not (calls and said):
        return None
    need = flops_loop.loop_step_bytes(
        run.config, statistics.mean(s.args['kv_loop_live_positions']
                                    for s in said), *_itemsizes(run))
    return 100.0 * need / run.peaks['hbm_bytes_per_s'] \
        / statistics.mean(calls)


def decode_roofline(run, patterns):
    tr = run.obs.get('trace')
    if tr is None or run.peaks is None:
        return None
    said = steps(run, *run.obs['marks'])
    seconds, positions = 0.0, 0
    for program, pattern in patterns.items():
        got = program_op_share.matched_and_total(run, program, pattern)
        calls = serving.program_seconds(run, tr, program)
        mine = [s for s in said if s.name == PROGRAM_SPAN[program]]
        if got and got[0] and calls and mine:
            # the program's mean matched time a call, times its spans
            seconds += got[0] / len(calls) * len(mine)
            positions += sum(s.args['kv_loop_live_positions']
                             for s in mine)
    if not (seconds and positions):
        return None
    need = max(
        flops_loop.loop_decode_bytes(run.config, positions,
                                     _itemsizes(run)[1])
        / run.peaks['hbm_bytes_per_s'],
        flops_loop.loop_decode_flops(run.config, positions)
        / run.peaks['bf16_flops_per_s'])
    return 100.0 * need / seconds


def op_share(run, program, pattern):
    got = program_op_share.matched_and_total(run, program, pattern)
    calls = got and serving.program_seconds(run, run.obs['trace'], program)
    return 100.0 * got[0] / sum(calls) if calls else None


def read(run, what, patterns=None, program=None, pattern=None):
    if what == 'step_roofline':
        return step_roofline(run)
    if what == 'decode_roofline':
        return decode_roofline(run, patterns)
    if what == 'op_share':
        return op_share(run, program, pattern)
    got = steps(run, *ps.window(run), carried=False)
    if not got:
        return None
    if what == 'passes_per_weight_layer':
        return statistics.mean(s.args['loop_passes'] for s in got) \
            / run.config['num_hidden_layers']
    if what == 'exit_mass_last':
        got = [s for s in got if s.args.get('loop_exit_mass')]
        return 100.0 * statistics.mean(s.args['loop_exit_mass'][-1]
                                       for s in got) if got else None
    raise ValueError('unknown quantity %r' % what)

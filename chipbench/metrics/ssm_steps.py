"""What the state-space layers did, from the program's spans and the
device trace.  ``decode.prefill_chunk`` spans carry ``ssm_scan_tokens``
(the chunk's valid tokens) and ``ssm_from_zero``; ``decode.step`` and a
chunk with ``step_rows`` > 0 carry ``ssm_live_slots`` and
``ssm_state_bytes`` (the running rows' states, every state layer's) and
``kv_live_pages``.  A program without these arguments gives none.

``read(run, what, ...)``:
``op_share``: the device time ``programs`` (keys of the configuration's
``device_programs``) spend in the operations ``pattern`` matches over
those programs' own device time (the trace's module line), in percent,
plain steps AND carrying chunks together where both are named: most
ticks of this cell carry a chunk, and a traced window may hold no plain
step.  The denominator is the module line and not the sum of the
programs' operations: a run of layers is one ``while``, an operation of
the trace that encloses its body's.
``scan_roofline``: the least time the traced chunks' scans' bytes take
at the HBM peak (flops_ssm.py ``scan_bytes`` of the chunk spans' count
and their ``ssm_scan_tokens``) over the device time the chunk program
spends in the operations ``pattern`` matches, in percent.
``step_roofline``: the same for the one-token updates of the decode
rows (``step_bytes`` of the spans' ``ssm_live_slots``), over the plain
steps and the carrying chunks, ``patterns`` a program.
``state_share``: the running rows' live state bytes over live state +
live K/V bytes (``kv_live_pages`` x a page's bytes in the attention
layers), in percent, over the untraced window's decode rows' calls: a
program counter, host arithmetic."""
from .. import flops_ssm
from .. import program_spans as ps
from ..kinds import serving
from . import program_op_share
from .held_steps import CHUNK, PROGRAM_SPAN


def row_calls(run, lo, hi):
    """The spans inside [lo, hi) that ran decode rows over state layers:
    plain steps and carrying chunks."""
    return [s for s in ps.inside(ps.spans(run) or (), lo, hi)
            if 'ssm_live_slots' in s.args
            and (s.name == ps.STEP or s.args.get('step_rows'))]


def matched_seconds(run, program, pattern, spans):
    """Device seconds ``spans`` (calls of ``program``) spent in the
    matched operations: the program's mean a call times the spans."""
    got = program_op_share.matched_and_total(run, program, pattern)
    calls = serving.program_seconds(run, run.obs['trace'], program)
    if not (got and got[0] and calls and spans):
        return 0.0
    return got[0] / len(calls) * len(spans)


def op_share(run, programs, pattern):
    tr = run.obs.get('trace')
    if tr is None:
        return None
    matched = total = 0.0
    for program in programs:
        got = program_op_share.matched_and_total(run, program, pattern)
        if got:
            matched += got[0]
            total += sum(serving.program_seconds(run, tr, program))
    return 100.0 * matched / total if total else None


def scan_roofline(run, pattern):
    if run.obs.get('trace') is None or run.peaks is None:
        return None
    chunks = [s for s in ps.inside(ps.spans(run) or (), *run.obs['marks'],
                                   name=CHUNK)
              if 'ssm_scan_tokens' in s.args]
    seconds = matched_seconds(run, 'chunk', pattern, chunks)
    if not seconds:
        return None
    need = flops_ssm.scan_bytes(
        run.config, len(chunks),
        sum(s.args['ssm_scan_tokens'] for s in chunks))
    return 100.0 * need / run.peaks['hbm_bytes_per_s'] / seconds


def step_roofline(run, patterns):
    if run.obs.get('trace') is None or run.peaks is None:
        return None
    said = row_calls(run, *run.obs['marks'])
    seconds, slots = 0.0, 0
    for program, pattern in patterns.items():
        mine = [s for s in said if s.name == PROGRAM_SPAN[program]]
        got = matched_seconds(run, program, pattern, mine)
        if got:
            seconds += got
            slots += sum(s.args['ssm_live_slots'] for s in mine)
    if not (seconds and slots):
        return None
    return 100.0 * flops_ssm.step_bytes(run.config, slots) \
        / run.peaks['hbm_bytes_per_s'] / seconds


def state_share(run):
    said = row_calls(run, *ps.window(run))
    state = sum(s.args['ssm_state_bytes'] for s in said)
    page = run.traffic['engine']['page_size'] \
        * flops_ssm.kv_position_bytes(run.config)
    kv = page * sum(s.args['kv_live_pages'] for s in said)
    return 100.0 * state / (state + kv) if state + kv else None


def read(run, what, programs=None, pattern=None, patterns=None):
    if what == 'op_share':
        return op_share(run, programs, pattern)
    if what == 'scan_roofline':
        return scan_roofline(run, pattern)
    if what == 'step_roofline':
        return step_roofline(run, patterns)
    if what == 'state_share':
        return state_share(run)
    raise ValueError('unknown quantity %r' % what)

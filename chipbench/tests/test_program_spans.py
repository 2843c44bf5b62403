"""The program's spans through ``program_spans`` and the readers that
use them: a synthetic ring with known durations, laid over the recorded
trace ``trace_small.json`` (whose three ``jit_step_fn`` executions stand
in for decode steps), and the chat cell under ``--rehearse``."""
import json
import os
import types

import pytest

from chipbench import program_spans as ps, xplane
from chipbench.metrics import (prefill_stall_ms, request_span_ms,
                               span_self_ms, step_gap_ms, useful_token_share)
from chipbench.tests.test_rehearse import ROOT, rehearse, tagged
from paddle_tpu.observability import timeline

HERE = os.path.dirname(os.path.abspath(__file__))
T_OPEN, T_HOST_END, T_A = 50.0, 99.0, 100.0
LAUNCH, RETURN = 2.0e-3, 1.5e-3


class Ring(object):
    """Writes spans with chosen stamps into the program's ring."""

    def __init__(self):
        timeline.reset()
        self.ids = iter(range(1, 10 ** 6))

    def add(self, name, t0, t1, parent=None, **args):
        i = next(self.ids)
        timeline.ring().record(name, cat='span', t0=t0, dur=t1 - t0,
                               args=args or None, span_id=i, parent=parent)
        return i

    def tick(self, t0, step, prefill=None):
        """A tick of 95 ms: an optional admit of 30 ms holding a prefill
        of 28, then a step given as (t0, t1) or 60 ms long."""
        step = step or (t0 + 0.032, t0 + 0.092)
        tick = self.add('server.tick', t0, max(t0 + 0.095, step[1] + 1e-3),
                        running=3, admitted=int(bool(prefill)), queued=0)
        if prefill:
            admit = self.add('server.admit', t0 + 1e-3, t0 + 0.031, tick,
                             rid='r1')
            self.add('decode.prefill_into', t0 + 2e-3, t0 + 0.030, admit,
                     tokens=prefill, bucket=128)
        st = self.add('decode.step', step[0], step[1], tick)
        self.add('decode.step.dispatch', step[0], step[0] + 1e-3, st)
        self.add('decode.step.fetch', step[0] + 1e-3, step[1], st)


@pytest.fixture
def trace():
    with open(os.path.join(HERE, 'trace_small.json')) as f:
        tr = json.load(f)
    # the test's own plane only: the recorded host spans are the feed's
    tr['planes'] = [p for p in tr['planes']
                    if not p['name'].startswith('/host:')]
    return tr


@pytest.fixture
def run(trace):
    ring = Ring()
    # the untraced window: 100 ticks, every tenth with a prefill
    for k in range(100):
        ring.tick(T_OPEN + 1.0 + 0.1 * k, None,
                  prefill=70 if k % 10 == 0 else None)
    for k, (q, done) in enumerate([(0.010, 60.0), (0.020, 70.0),
                                   (0.500, 80.0), (9.000, 99.5)]):
        args = dict(rid='r%d' % k, prompt_tokens=70, new_tokens=9)
        ring.add('server.request.queued', 55.0, 55.0 + q, **args)
        ring.add('server.request.prefill', 55.0 + q, 55.1 + q, **args)
        ring.add('server.request.decode', 55.1 + q, done, **args)
    # the traced seconds: a tick around each recorded step program
    win = xplane.window(trace)
    mods = [(s, s + d) for n, s, d in xplane.line_events(
        xplane.device_planes(trace)[0], xplane.MODULES_LINE)
        if n.startswith('jit_step_fn')]
    for s, e in mods:
        step = (T_A + (s - win[0]) / 1e9 - LAUNCH,
                T_A + (e - win[0]) / 1e9 + RETURN)
        ring.tick(step[0] - 0.004, step)
    obs = {'trace': trace, 'marks': (T_A, T_A + (win[1] - win[0]) / 1e9),
           't_open': T_OPEN, 't_host_end': T_HOST_END}
    yield types.SimpleNamespace(
        obs=obs, config={'device_programs': {'step': 'jit_step_fn'}})
    timeline.reset()


def test_lay_and_the_readers(run, trace, capsys):
    events = ps.spans(run)
    assert events and all(isinstance(s, ps.Span) for s in events)
    assert ps.spans(run) is events          # read once
    planes = [p for p in trace['planes'] if p['name'] == ps.PLANE]
    assert len(planes) == 1                 # laid once
    laid = planes[0]['lines'][0]['events']
    # three ticks of four spans, and no request span among them
    assert len(laid) == 12
    assert {n for n, _s, _d in laid} == {
        'server.tick', 'decode.step', 'decode.step.dispatch',
        'decode.step.fetch'}
    line = capsys.readouterr().out.strip().splitlines()
    assert len(line) == 1 and line[0].startswith('PROGRAM_SPANS ')
    said = json.loads(line[0].split(' ', 1)[1])
    assert said['window']['server.tick']['n'] == 100
    assert said['traced_steps']['launch_gap_ms']['n'] == 3

    assert step_gap_ms.read(run, side='launch') == pytest.approx(
        1e3 * LAUNCH, abs=1e-3)
    assert step_gap_ms.read(run, side='return') == pytest.approx(
        1e3 * RETURN, abs=1e-3)
    # 95 ms less a 60 ms step, and less a 30 ms admit in a tenth of them
    assert span_self_ms.read(run, span='server.tick') == pytest.approx(
        0.9 * 35.0 + 0.1 * 5.0)
    # a prefill's own time is what its admit does not hold
    assert span_self_ms.read(run, span='server.admit') == pytest.approx(2.0)
    assert prefill_stall_ms.read(run, q=95) == pytest.approx(28.0)
    assert prefill_stall_ms.read(run, q=50) == 0.0
    assert useful_token_share.read(run) == pytest.approx(100 * 70 / 128.0)
    # of the four requests three completed inside the window
    assert request_span_ms.read(run, span='server.request.queued', q=50) \
        == pytest.approx(20.0)
    assert request_span_ms.read(run, span='server.request.prefill', q=90) \
        == pytest.approx(100.0)

    # the idle gaps of the traced seconds carry the program's names
    gaps = xplane.idle_gaps(trace, xplane.window(trace))
    assert gaps and set(gaps) <= {
        'server.tick', 'decode.step', 'decode.step.dispatch',
        'decode.step.fetch', 'no_span'}
    # before each step program the device idles through the 1 ms of
    # dispatch and the first 1 ms of fetch; after it through RETURN
    assert gaps['decode.step.dispatch'] == pytest.approx(3e-3, rel=1e-3)
    assert gaps['decode.step.fetch'] >= 3 * (1e-3 + RETURN) * 0.999
    assert gaps.get('decode.step', 0.0) < 1e-6      # rounding to ns


def all_readers(run):
    return [step_gap_ms.read(run, side='launch'),
            step_gap_ms.read(run, side='return'),
            span_self_ms.read(run, span='server.tick'),
            prefill_stall_ms.read(run, q=95),
            useful_token_share.read(run),
            request_span_ms.read(run, span='server.request.queued', q=90)]


def test_without_a_trace_only_the_gaps_are_missing(run):
    del run.obs['trace']
    got = all_readers(run)
    assert got[:2] == [None, None]
    assert all(v is not None for v in got[2:])


def test_a_trace_without_a_device_plane(run, trace):
    # a rehearsal's trace: the CPU has no /device:TPU plane, so no marks
    trace['planes'] = []
    assert all_readers(run)[:2] == [None, None]
    assert trace['planes'] == []


def test_no_events_no_metric(run):
    timeline.reset()
    assert ps.spans(run) is None
    assert all_readers(run) == [None] * 6


def test_a_cut_window_gives_nothing(run):
    before = timeline.ring().events()
    # the bound evicted events of the window: the oldest left ended
    # after the window opened
    timeline.reset(cap=len(before) - 50)
    for e in before:
        timeline.ring().record(
            e['name'], cat='span', t0=e['ts'] + timeline.CLOCK_ORIGIN,
            dur=e['dur'], args=e['args'], span_id=e['id'],
            parent=e['parent'])
    assert timeline.ring().dropped == 50
    assert ps.spans(run) is None
    assert all_readers(run) == [None] * 6


def test_evictions_before_the_window_do_no_harm(run):
    # what the ring dropped ended before the window opened: set-up's spans
    timeline.ring().dropped = 7
    oldest = timeline.ring().events()[0]
    assert oldest['ts'] + oldest['dur'] + timeline.CLOCK_ORIGIN > T_OPEN
    run.obs['t_open'] = oldest['ts'] + oldest['dur'] + timeline.CLOCK_ORIGIN
    assert ps.spans(run) is not None


def test_a_program_without_the_spans(run, monkeypatch):
    # the parent commit: no public clock origin, no ``dropped``
    monkeypatch.delattr(timeline, 'CLOCK_ORIGIN')
    assert ps.spans(run) is None
    assert all_readers(run) == [None] * 6


def test_chat_rehearses_with_the_program_spans():
    res, earlier = rehearse(ROOT, 'opt-1.3b_serve_chat', trace=1)
    assert res['correct'] is True and res['failed'] == 0, earlier
    said = tagged(earlier, 'PROGRAM_SPANS')
    names = set(said['window'])
    assert {'server.tick', 'server.admit', 'decode.prefill_into',
            'decode.step', 'decode.step.dispatch', 'decode.step.fetch',
            'server.request.queued'} <= names
    ticks = said['window']['server.tick']
    assert 0 <= ticks['self_mean_ms'] <= ticks['mean_ms']
    # a decode step per tick that ran one, as the tap counts them
    assert said['window']['decode.step']['n'] == \
        tagged(earlier, 'WINDOW')['decode_steps']

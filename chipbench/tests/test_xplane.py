"""The reduction from a trace to numbers, on a small recorded trace.
``trace_small.json`` is cut from a traced run of resnet50_train_fed on
the v5e (my chip run, PR 23): chip 0's programs and its ops of 0.1 ms or
more (95% of the device time) over the first three steps after the
opening mark, some async ops, and the benchmark's host spans laid over
them; the closing mark is the run's own, moved up to just before the
fourth step."""
import json
import os

import pytest

from chipbench import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope='module')
def trace():
    with open(os.path.join(HERE, 'trace_small.json')) as f:
        return json.load(f)


def test_intervals():
    u = xplane.union([[0, 5], [3, 8], [10, 12], [12, 13], [20, 20]])
    assert u == [[0, 8], [10, 13]]
    assert xplane.total(u) == 11
    assert xplane.clip(u, 4, 11) == [[4, 8], [10, 11]]
    assert xplane.subtract([[0, 20]], u) == [[8, 10], [13, 20]]
    assert xplane.subtract(u, [[0, 100]]) == []


def test_busy_idle_and_window(trace):
    win = xplane.window(trace)
    assert win is not None and win[1] > win[0]
    busy_s, window_s, idle = xplane.busy_and_idle(trace, win)
    assert 0 < busy_s < window_s
    # by hand: idle share = 1 - union of ops / window
    ops = xplane.line_events(xplane.device_planes(trace)[0], xplane.OPS_LINE)
    u = xplane.clip(xplane.union([s, s + d] for _n, s, d in ops), *win)
    assert idle == pytest.approx(1 - xplane.total(u) / (win[1] - win[0]))
    assert 0.0 < idle < 1.0


def test_per_op_and_module_time(trace):
    win = xplane.window(trace)
    calls = xplane.module_calls(trace, win, 'jit_step_fn')
    assert len(calls) >= 2 and all(c > 0 for c in calls)
    ops = xplane.op_seconds(trace, win)
    assert ops and all(k.startswith('jit_step_fn/') for k in ops)
    # an op's time is inside its programs' time
    assert sum(ops.values()) <= sum(calls) * 1.0001
    assert xplane.top(ops, 3)[0][1] >= xplane.top(ops, 3)[-1][1]


def test_gap_attribution(trace):
    win = xplane.window(trace)
    gaps = xplane.idle_gaps(trace, win)
    busy_s, window_s, _ = xplane.busy_and_idle(trace, win)
    assert gaps
    # every attributed second is idle time; the short gaps are left out
    assert sum(gaps.values()) <= (window_s - busy_s) * 1.0001
    # the device waits mostly while the host is inside the feed's next()
    assert max(gaps, key=gaps.get) == 'bench.feed_next'
    assert 'bench.window' not in gaps


def test_exposed_collective():
    # two chips; on chip 0 the all-reduce [10, 30) overlaps compute
    # [0, 20): 10 exposed; on chip 1 it is hidden whole
    def plane(n, coll, comp):
        return {'name': '/device:TPU:%d' % n, 'lines': [
            {'name': 'XLA Ops', 'events': [['fusion.1'] + comp]},
            {'name': 'XLA Modules', 'events': [['jit_step_fn(1)', 0, 50]]},
            {'name': 'Async', 'events': [['all-reduce-start.3'] + coll]}]}
    tr = {'planes': [plane(0, [10, 20], [0, 20]),
                     plane(1, [10, 20], [0, 40])]}
    assert xplane.exposed_collective_s(tr, (0, 50)) == \
        pytest.approx(0.5 * 10e-9)


def test_idle_mismatch_line(trace):
    win = xplane.window(trace)
    _b, _w, idle = xplane.busy_and_idle(trace, win)
    agree = xplane.idle_lines(idle, idle + 0.05)
    assert len(agree) == 1 and agree[0].startswith('IDLE_SHARE')
    # PR 22's case: the trace read 79.9% where the host clock says ~30%
    apart = xplane.idle_lines(0.7985, 0.30)
    assert apart[0].startswith('IDLE_SHARE')
    assert apart[1].startswith('IDLE_MISMATCH')

"""``metrics/setup_spans.py`` on a ring built by hand: two programs'
``decode.compile`` with their three stages under one ``decode.warmup``,
a first run each, and a tick after the window opened.  And the six
``setup.*`` metrics' files against ``BENCHMARK.json``."""
import json
import os
import types

import pytest

from chipbench import harness
from chipbench.metrics import setup_spans
from chipbench.tests.test_program_spans import Ring
from chipbench.tests.test_rehearse import BENCH, ROOT
from paddle_tpu.observability import timeline

BASE = harness.T0 + 1.0     # the ring's clock is harness.T0's
T_OPEN = BASE + 100.0
METRICS = {
    'setup.warmup_s': ('s', 'host_clock', {'span': 'decode.warmup'}),
    'setup.trace_s': ('s', 'host_clock', {'span': 'decode.compile.trace'}),
    'setup.lower_s': ('s', 'host_clock', {'span': 'decode.compile.lower'}),
    'setup.backend_s': ('s', 'host_clock',
                        {'span': 'decode.compile.backend'}),
    'setup.warm_run_s': ('s', 'host_clock', {'span': 'decode.warmup.run'}),
    'setup.cache_misses': ('count', 'program_counter',
                           {'span': 'decode.compile.backend',
                            'arg': 'cache', 'equals': 'miss'})}


def fill(ring, late_compile=False):
    """A warm-up of 20 s: ``chunk`` 64 (trace 3, lower 2, backend 1, a
    miss) and ``step`` (2, 1, 0.5, a hit), first runs of 0.25 and 0.125
    s; with ``late_compile`` a third program built after the window
    opened."""
    warm = ring.add('decode.warmup', BASE, BASE + 20.0, programs=2, runs=2)
    t = BASE + 1.0
    for program, bucket, stages, cache in (
            ('chunk', 64, (3.0, 2.0, 1.0), 'miss'),
            ('step', None, (2.0, 1.0, 0.5), 'hit')):
        comp = ring.add('decode.compile', t, t + sum(stages) + 0.5, warm,
                        program=program, bucket=bucket, temp_bytes=0)
        for stage, dur in zip(setup_spans.STAGES, stages):
            args = {'cache': cache} if stage == 'backend' else {}
            ring.add('decode.compile.' + stage, t, t + dur, comp, **args)
            t += dur
        t += 0.5
    ring.add('decode.warmup.run', t, t + 0.25, warm,
             program='chunk', bucket=64)
    ring.add('decode.warmup.run', t + 0.25, t + 0.375, warm,
             program='step', bucket=None)
    tick = ring.add('server.tick', T_OPEN + 1.0, T_OPEN + 1.1)
    if late_compile:
        call = ring.add('decode.prefill_chunk', T_OPEN + 2.0, T_OPEN + 9.0,
                        tick)
        comp = ring.add('decode.compile', T_OPEN + 2.0, T_OPEN + 8.0, call,
                        program='chunk', bucket=128)
        for i, stage in enumerate(setup_spans.STAGES):
            ring.add('decode.compile.' + stage, T_OPEN + 2.0 + i,
                     T_OPEN + 3.0 + i, comp,
                     **({'cache': 'miss'} if stage == 'backend' else {}))


def run_at(t_open=T_OPEN):
    return types.SimpleNamespace(obs={'t_open': t_open})


def read(run, name):
    return setup_spans.read(run, **METRICS[name][2])


WANT = {'setup.warmup_s': 20.0, 'setup.trace_s': 5.0, 'setup.lower_s': 3.0,
        'setup.backend_s': 1.5, 'setup.warm_run_s': 0.375,
        'setup.cache_misses': 1}


@pytest.mark.parametrize('late_compile', [False, True])
def test_sums_by_name_counts_by_cache_and_stops_at_t_open(late_compile):
    fill(Ring(), late_compile)
    run = run_at()
    for name, want in WANT.items():
        assert read(run, name) == pytest.approx(want, abs=1e-9), name
    # the same ring read with the window opening after the late compile
    late = run_at(T_OPEN + 10.0)
    assert read(late, 'setup.cache_misses') == 1 + late_compile
    assert read(late, 'setup.trace_s') == pytest.approx(5.0 + late_compile)
    assert setup_spans.read(run, 'decode.compile.backend', arg='cache',
                            equals='hit') == 1
    assert setup_spans.read(run, 'decode.compile.backend', arg='cache',
                            equals='off') == 0


def test_a_ring_without_the_spans_gives_none_and_not_zero():
    ring = Ring()       # a parent's ring: compiles with no stage, no warmup
    ring.add('decode.compile', BASE, BASE + 5.0, program='step', bucket=None)
    ring.add('server.tick', T_OPEN + 1.0, T_OPEN + 1.1)
    run = run_at()
    for name in METRICS:
        assert read(run, name) is None
    timeline.reset()    # and no ring at all
    assert read(run_at(), 'setup.warmup_s') is None
    # a kind that opens no window has no set-up to bound
    fill(Ring())
    assert read(types.SimpleNamespace(obs={}), 'setup.warmup_s') is None


def test_a_cut_ring_gives_none_never_a_partial_sum(capsys):
    ring = Ring()
    timeline.reset(cap=16)
    fill(ring)
    for i in range(8):      # the window's ticks push set-up's spans out
        ring.add('server.tick', T_OPEN + 2.0 + i, T_OPEN + 2.5 + i)
    assert timeline.ring().dropped > 0
    assert any(e['name'] == 'decode.warmup.run'
               for e in timeline.ring().events())
    run = run_at()
    for name in METRICS:
        assert read(run, name) is None
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith('SETUP_SPANS ')]
    assert [json.loads(line.split(' ', 1)[1]) for line in lines] == [
        {'events': 16, 'dropped': timeline.ring().dropped}]


def test_the_setup_spans_line_has_a_row_a_program_once_a_run(capsys):
    fill(Ring(), late_compile=True)
    run = run_at()
    for name in METRICS:
        read(run, name)
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith('SETUP_SPANS ')]
    assert len(lines) == 1
    said = json.loads(lines[0].split(' ', 1)[1])
    want = [
        {'program': 'chunk', 'bucket': 64, 'trace_s': 3.0, 'lower_s': 2.0,
         'backend_s': 1.0, 'cache': 'miss', 'warm_run_s': 0.25},
        {'program': 'step', 'bucket': None, 'trace_s': 2.0, 'lower_s': 1.0,
         'backend_s': 0.5, 'cache': 'hit', 'warm_run_s': 0.125}]
    assert len(said['programs']) == 2   # the late compile is no set-up
    for got, row in zip(said['programs'], want):
        assert set(got) == set(row)
        for key, value in row.items():
            assert got[key] == (pytest.approx(value) if key.endswith('_s')
                                else value)
    # 20 s less the two compiles (6.5 and 4) and the two runs
    assert said['warmup_self_s'] == pytest.approx(20.0 - 10.5 - 0.375)
    assert said['dropped'] == 0 and said['events'] == 17


def test_the_six_metrics_files_and_entries():
    cells = [w['name'] for w in BENCH['workloads']]
    entries = {m['name']: m for m in BENCH['per_layer']
               if m['layer'] == 'setup'}
    assert list(entries) == list(METRICS)
    for name, (unit, source, params) in METRICS.items():
        with open(os.path.join(ROOT, 'chipbench', 'metrics',
                               name + '.json')) as f:
            assert json.load(f) == {'reader': 'setup_spans',
                                    'params': params}
        assert entries[name] == {
            'name': name, 'unit': unit, 'better': 'lower', 'source': source,
            'layer': 'setup', 'moves': 'setup_s', 'workloads': cells}

"""The decode path's spans on the timeline ring (no arming): names,
nesting by ``parent``, a request's three spans, compiles, the disabled
path, and the ring's own account of what it dropped.  Tiny engine, CPU;
no duration is judged here."""
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.inference.decode import (DecodeEngine, DecodeServer,
                                         extract_params)
from paddle_tpu.models import transformer
from paddle_tpu.observability import timeline

L, D, H, V, T = 2, 32, 4, 64, 64
PAGE, STREAMS = 8, 4


@pytest.fixture(scope='module')
def params():
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 7
        with fluid.program_guard(main, startup):
            transformer.build(vocab_size=V, seq_len=T, n_layers=L,
                              d_model=D, n_heads=H)
        fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
        return extract_params(scope, L)


def make_engine(params, top=16, **kw):
    return DecodeEngine(params, n_layers=L, n_heads=H, page_size=PAGE,
                        max_streams=STREAMS, prefill_bucket=top,
                        prefix_cache=False, **kw)


@pytest.fixture(autouse=True)
def _fresh_ring(monkeypatch):
    # disarmed: what lands on the ring below got there without arming
    monkeypatch.delenv('PADDLE_TPU_TRACE_DIR', raising=False)
    monkeypatch.delenv('PADDLE_TPU_TRACE_DUMP_ON_ERROR', raising=False)
    timeline.reset()
    yield
    timeline.reset()


def spans():
    return [e for e in timeline.ring().events(cat='span') if 'id' in e]


def serve(engine, prompts, n_new):
    server = DecodeServer(engine, warmup=False)
    try:
        streams = [server.submit(p, max_new_tokens=n_new) for p in prompts]
        for st in streams:
            st.result(timeout=120.0)
    finally:
        server.close()
    return streams


@pytest.mark.parametrize('chunked', [False, True])
def test_span_names_and_nesting(params, chunked):
    eng = make_engine(params, prefill_chunk_tokens=PAGE if chunked else 0)
    eng.warmup()
    assert not timeline.armed()
    timeline.ring().clear()
    rng = np.random.default_rng(0)
    serve(eng, [rng.integers(1, V, n) for n in (5, 12, 9)], 4)
    evs = spans()
    by_id = {e['id']: e for e in evs}
    assert len(by_id) == len(evs)

    def parent_name(e):
        return by_id[e['parent']]['name'] if e['parent'] else None

    prefill = 'decode.prefill_chunk' if chunked else 'decode.prefill_into'
    want = {'server.tick': None, 'server.admit': 'server.tick',
            prefill: 'server.admit', 'decode.step': 'server.tick',
            'decode.step.dispatch': 'decode.step',
            'decode.step.fetch': 'decode.step'}
    if chunked:     # a chunk call has the step's two halves
        want.update({prefill + '.dispatch': prefill,
                     prefill + '.fetch': prefill})
    names = {e['name'] for e in evs}
    assert set(want) <= names
    assert 'decode.compile' not in names     # every shape was warmed
    for e in evs:
        if e['name'] in want:
            assert parent_name(e) == want[e['name']], e
            if e['parent']:     # a child lies inside its parent
                p = by_id[e['parent']]
                assert p['ts'] <= e['ts'] and \
                    e['ts'] + e['dur'] <= p['ts'] + p['dur'] + 1e-9
    ticks = [e for e in evs if e['name'] == 'server.tick']
    pf = [e for e in evs if e['name'] == prefill]
    assert [e['step'] for e in ticks] == list(range(len(ticks)))
    assert all(set(e['args']) == {'running', 'admitted', 'queued'}
               for e in ticks)
    assert sum(e['args']['admitted'] for e in ticks) == 3
    # a tick's decode step is a ``step``, or rides the tick's last
    # prefill chunk (``step_rows`` running slots): never both
    steps = [e for e in evs if e['name'] == 'decode.step']
    carrying = [e for e in evs if e['name'] == 'decode.prefill_chunk'
                and e['args']['step_rows']]
    assert len(steps) + len(carrying) \
        == sum(1 for e in ticks if e['args']['running'])
    assert bool(carrying) is chunked
    if chunked:     # a chunk's tick is its ``server.admit``'s parent
        assert not {e['parent'] for e in steps} \
            & {by_id[e['parent']]['parent'] for e in pf}
    # self time is never negative: children do not overlap on a thread
    for t in ticks:
        kids = [e for e in evs if e['parent'] == t['id']]
        assert sum(k['dur'] for k in kids) <= t['dur'] + 1e-9
    assert all(0 < e['args']['tokens'] <= e['args']['bucket'] for e in pf)
    if not chunked:
        assert sorted(e['args']['tokens'] for e in pf) == [5, 9, 12]
        assert {by_id[e['parent']]['args']['rid'] for e in pf} == \
            {'r0', 'r1', 'r2'}


def test_request_spans_share_rid_and_add_up_to_ttft(params):
    eng = make_engine(params)
    eng.warmup()
    rng = np.random.default_rng(1)
    # more requests than slots: the later ones wait for one
    streams = serve(eng, [rng.integers(1, V, 6) for _ in range(7)], 5)
    evs = spans()
    for st in streams:
        mine = {e['name']: e for e in evs if e['name'].startswith(
            'server.request.') and e['args']['rid'] == st.request_id}
        assert set(mine) == {'server.request.queued',
                             'server.request.prefill',
                             'server.request.decode'}
        q, p, d = (mine['server.request.' + k]
                   for k in ('queued', 'prefill', 'decode'))
        assert q['dur'] >= 0 and p['dur'] > 0 and d['dur'] > 0
        assert q['dur'] + p['dur'] == pytest.approx(st.ttft_s, abs=1e-9)
        assert q['ts'] + timeline.CLOCK_ORIGIN == \
            pytest.approx(st.submitted_t, abs=1e-9)
        assert d['ts'] + d['dur'] + timeline.CLOCK_ORIGIN == \
            pytest.approx(st.done_t, abs=1e-9)
        assert q['args'] == {'rid': st.request_id, 'prompt_tokens': 6,
                             'new_tokens': 5}
        assert q['parent'] is None
    waits = sorted(e['dur'] for e in evs
                   if e['name'] == 'server.request.queued')
    assert waits[-1] > waits[0]     # someone waited for a slot


def test_a_new_bucket_compiles_once_under_its_prefill(params):
    eng = make_engine(params, top=32)
    eng.buckets = [8, 16]       # a deployment that never warmed 32
    eng.warmup()
    assert 'decode.compile' in {e['name'] for e in spans()}
    timeline.ring().clear()
    eng.buckets = [8, 16, 32]
    pages = eng.cache.alloc(3)
    prompt = np.arange(1, 21, dtype=np.int32)
    eng.prefill_into(prompt, pages)
    eng.prefill_into(prompt, pages)
    evs = spans()
    comp = [e for e in evs if e['name'] == 'decode.compile']
    assert sorted((e['args']['program'], e['args']['bucket'])
                  for e in comp) == [('pack', 32), ('prefill', 32)]
    calls = [e for e in evs if e['name'] == 'decode.prefill_into']
    assert len(calls) == 2
    first = min(calls, key=lambda e: e['ts'])
    assert {e['parent'] for e in comp} == {first['id']}
    # what came back to the host: the prompt's last row of logits
    assert first['args'] == {'tokens': 20, 'bucket': 32,
                             'fetched_bytes': 4 * V}
    assert eng.compiles_after_warmup == 2


@pytest.mark.parametrize('chunked', [False, True])
def test_compile_spans_say_whether_the_pool_is_in_place(params, chunked):
    eng = make_engine(params, prefill_chunk_tokens=PAGE if chunked else 0)
    eng.warmup()
    comp = [e['args'] for e in spans() if e['name'] == 'decode.compile']
    built = [('chunk', 8)] if chunked else \
        [('pack', 8), ('pack', 16), ('prefill', 8), ('prefill', 16)]
    assert sorted((a['program'], a['bucket']) for a in comp
                  if a['bucket']) == built
    assert [a['program'] for a in comp if a['bucket'] is None] == ['step']
    pool = eng.resident_bytes()
    weights = sum(v.nbytes for v in eng.params.values())
    for a in comp:
        # the weights are an operand of every program but pack, the
        # pools of every program but prefill; the rest is a few rows
        takes = weights * (a['program'] != 'pack') \
            + pool * (a['program'] != 'prefill')
        assert takes <= a['argument_bytes'] < takes + weights
        # the step also says which attention the op's dispatch took:
        # on the CPU, heads of any size, the gathered span
        assert set(a) == {'program', 'bucket', 'temp_bytes', 'alias_bytes',
                          'argument_bytes', 'pool_bytes'} | (
            {'attention'} if a['program'] == 'step' else set())
        if a['program'] == 'step':
            assert a['attention'] == 'xla_gather'
        assert a['pool_bytes'] == pool and a['temp_bytes'] >= 0
        # a prefill takes no pool; every other program aliases all of it
        assert a['alias_bytes'] == (0 if a['program'] == 'prefill'
                                    else pool)


def test_metrics_disabled_leaves_no_ring_record(params):
    eng = make_engine(params)
    eng.warmup()
    obs.set_enabled(False)
    try:
        timeline.ring().clear()
        assert obs.span('x', args={}) is obs.tracing._NULL_SPAN
        obs.record_span('server.request.queued', 0.0, 1.0)
        streams = serve(eng, [np.arange(1, 8)], 3)
        assert len(streams[0].tokens) == 3
        assert timeline.ring().events() == []
    finally:
        obs.reload_enabled()


def test_dropped_counts_evictions_and_clock_origin():
    tl = timeline.Timeline(cap=4)
    for i in range(3):
        tl.record('e%d' % i)
    assert tl.dropped == 0
    for i in range(7):
        tl.record('f%d' % i)
    tl.counter_sample('bytes', 1)
    assert tl.dropped == 7 and len(tl.events()) == 4
    tl.clear()
    assert tl.dropped == 0 and tl.events() == []
    assert timeline.Timeline(cap=None).dropped == 0
    # ts is relative to the public origin, on perf_counter's clock
    t0 = time.perf_counter()
    tl.record('at', t0=t0, dur=0.5)
    assert tl.events()[0]['ts'] + timeline.CLOCK_ORIGIN == \
        pytest.approx(t0, abs=1e-12)


def test_span_ids_parents_and_threads(tmp_path):
    out = {}

    def other():
        with obs.span('t.other', annotate=False):
            pass
        out['done'] = True

    args = {}
    with obs.span('t.outer', annotate=False, step=41, args=args):
        with obs.span('t.inner', annotate=False):
            th = threading.Thread(target=other)
            th.start()
            th.join(timeout=10.0)
        args['filled'] = 'inside'
    assert out.get('done')
    evs = {e['name']: e for e in spans()}
    assert evs['t.inner']['parent'] == evs['t.outer']['id']
    assert evs['t.outer']['parent'] is None
    # another thread's span is no child of this thread's open span
    assert evs['t.other']['parent'] is None
    assert evs['t.outer']['step'] == 41
    assert evs['t.outer']['args'] == {'filled': 'inside'}
    # the exported trace carries id and parent for a viewer
    with open(timeline.export_chrome_trace(str(tmp_path / 't.json'))) as f:
        doc_args = {e['name']: e['args']
                    for e in json.load(f)['traceEvents'] if e['ph'] == 'X'}
    assert doc_args['t.inner']['parent'] == evs['t.outer']['id']


LOOP_ARGS = ('ut_steps', 'loop_passes', 'kv_loop_live_positions',
             'loop_exit_mass')
LOOP_COMPILE_ARGS = ('ut_steps', 'cache_slots', 'weight_layers',
                     'cache_bytes_per_position')


@pytest.mark.parametrize('ut_steps', [None, 1, 3])
def test_a_loops_spans_say_what_it_ran(params, ut_steps):
    """``decode.step``, a carrying ``decode.prefill_chunk`` and
    ``decode.compile`` of a block that runs its layers ``ut_steps`` times;
    a block that runs them once (None: ``OptBlock``) says none of it."""
    import test_ouro_decode as ouro     # its tiny engine: D, H, PAGE as here
    eng = make_engine(params, prefill_chunk_tokens=PAGE) \
        if ut_steps is None else ouro.engine(ut_steps, L, chunk=PAGE)
    eng.warmup()
    rng = np.random.default_rng(0)
    serve(eng, [rng.integers(1, ouro.V, n) for n in (5, 20, 9)], 6)
    evs = spans()
    steps = [e['args'] for e in evs if e['name'] == 'decode.step']
    chunks = [e['args'] for e in evs if e['name'] == 'decode.prefill_chunk']
    compiles = [e['args'] for e in evs if e['name'] == 'decode.compile']
    carrying = [a for a in chunks if a['step_rows']]
    assert steps and carrying and compiles
    alone = [a for a in chunks if not a['step_rows']]
    if ut_steps is None:
        for a in steps + chunks:
            assert not set(LOOP_ARGS) & set(a)
        for a in compiles:
            assert not set(LOOP_COMPILE_ARGS) & set(a)
        return
    for a in steps + carrying:
        assert a['ut_steps'] == ut_steps
        assert a['loop_passes'] == ut_steps * L
        assert a['kv_loop_live_positions'] > 0 \
            and a['kv_loop_live_positions'] % ut_steps == 0
        assert len(a['loop_exit_mass']) == ut_steps
        assert abs(sum(a['loop_exit_mass']) - 1.0) < 1e-5
    for a in alone:     # no decode rows ran: nothing left the loop
        assert not set(LOOP_ARGS) & set(a)
    for a in compiles:
        assert a['ut_steps'] == ut_steps and a['weight_layers'] == L
        assert a['cache_slots'] == ut_steps * L
        assert a['cache_bytes_per_position'] == ut_steps * L * 2 * D * 4


STATE_ARGS = ('ssm_live_slots', 'ssm_state_bytes', 'ssm_scan_tokens',
              'ssm_from_zero')
STATE_COMPILE_ARGS = ('layer_kinds', 'state_rows', 'state_bytes_per_stream',
                      'state_pool_bytes', 'ssm')


@pytest.mark.parametrize('block', ['opt', 'ouro', 'jamba'])
def test_only_a_block_with_state_layers_says_what_its_states_did(params,
                                                                 block):
    """The state layers' arguments on ``decode.step``,
    ``decode.prefill_chunk`` and ``decode.compile`` under a server: a
    block without such layers (``OptBlock``, a looped ``OuroBlock``)
    says none of them, and its ``stats()`` counts no state."""
    import test_jamba_decode as jam
    import test_ouro_decode as ouro
    eng = {'opt': lambda: make_engine(params, prefill_chunk_tokens=PAGE),
           'ouro': lambda: ouro.engine(2, L, chunk=PAGE),
           'jamba': lambda: jam.engine(PAGE)}[block]()
    eng.warmup()
    rng = np.random.default_rng(0)
    server = DecodeServer(eng, warmup=False)
    try:
        for st in [server.submit(rng.integers(1, jam.V, n), max_new_tokens=6)
                   for n in (5, 20, 9)]:
            st.result(timeout=120.0)
        stats = server.stats()
    finally:
        server.close()
    evs = spans()
    steps = [e['args'] for e in evs if e['name'] == 'decode.step']
    chunks = [e['args'] for e in evs if e['name'] == 'decode.prefill_chunk']
    compiles = [e['args'] for e in evs if e['name'] == 'decode.compile']
    assert steps and chunks and compiles
    assert stats['state_slots_live'] == stats['state_recomputed'] == 0
    if block != 'jamba':
        for a in steps + chunks:
            assert not set(STATE_ARGS) & set(a)
        for a in compiles:
            assert not set(STATE_COMPILE_ARGS) & set(a)
        return
    per_stream = eng.cache.state_bytes_per_stream()
    for a in steps + [c for c in chunks if c['step_rows']]:
        assert 1 <= a['ssm_live_slots'] <= 3
        assert a['ssm_state_bytes'] == a['ssm_live_slots'] * per_stream
    for a in chunks:
        assert a['ssm_scan_tokens'] == a['tokens']
        assert isinstance(a['ssm_from_zero'], bool)
        assert ('ssm_live_slots' in a) == bool(a['step_rows'])
    assert sum(a['ssm_from_zero'] for a in chunks) == 3     # a prompt each
    for a in compiles:
        assert set(STATE_COMPILE_ARGS) <= set(a)
        assert a['state_bytes_per_stream'] == per_stream


# -- set-up: decode.warmup, the three stages of a compile, the first runs ----

STAGES = ['decode.compile.trace', 'decode.compile.lower',
          'decode.compile.backend']
SETUP_NAMES = set(STAGES) | {'decode.warmup', 'decode.warmup.run'}


def family(evs):
    """(by id, {id: its children in the order they started}, a function
    from an event to the names of its ancestors, nearest first)."""
    by_id = {e['id']: e for e in evs}
    kids = {}
    for e in sorted(evs, key=lambda e: e['ts']):
        kids.setdefault(e['parent'], []).append(e)

    def ancestors(e):
        out = []
        while e['parent'] is not None:
            e = by_id[e['parent']]
            out.append(e['name'])
        return out
    return by_id, kids, ancestors


def inside(child, parent):
    return parent['ts'] <= child['ts'] and \
        child['ts'] + child['dur'] <= parent['ts'] + parent['dur'] + 1e-9


def built_by_warmup(chunked):
    return [('chunk', 8), ('step', None)] if chunked else \
        [('prefill', 8), ('pack', 8), ('prefill', 16), ('pack', 16),
         ('step', None)]


@pytest.mark.parametrize('chunked', [False, True])
def test_a_warmups_compiles_have_their_three_stages_in_order(params,
                                                             chunked):
    eng = make_engine(params, prefill_chunk_tokens=PAGE if chunked else 0)
    eng.warmup()
    evs = spans()
    by_id, kids, ancestors = family(evs)
    warm, = [e for e in evs if e['name'] == 'decode.warmup']
    assert warm['parent'] is None
    comp = [e for e in evs if e['name'] == 'decode.compile']
    assert [(e['args']['program'], e['args']['bucket']) for e in comp] \
        == built_by_warmup(chunked)
    assert warm['args'] == {'programs': len(comp), 'runs': len(comp)}
    for c in comp:
        below = kids[c['id']]
        assert [k['name'] for k in below] == STAGES
        assert all(inside(k, c) for k in below)
        # one after the other: a stage starts where the last one ended
        for a, b in zip(below, below[1:]):
            assert a['ts'] + a['dur'] <= b['ts']
        assert [k['args'] for k in below] == [None, None, {'cache': 'off'}]
    for e in evs:
        if e['name'] in ('decode.compile', 'decode.warmup.run'):
            assert ancestors(e) == ['decode.warmup'] and inside(e, warm)
        if e['name'] in STAGES:
            assert ancestors(e) == ['decode.compile', 'decode.warmup']
    # (the engine placed its weights when it was made, before warm-up)
    assert {e['name'] for e in evs} == SETUP_NAMES | {'decode.compile',
                                                      'decode.weights'}
    # what no child names is the span's own, and never negative
    assert sum(k['dur'] for k in kids[warm['id']]) <= warm['dur'] + 1e-9


@pytest.mark.parametrize('chunked', [False, True])
def test_one_warm_run_for_each_executable_warmup_runs(params, chunked):
    eng = make_engine(params, prefill_chunk_tokens=PAGE if chunked else 0)
    eng.warmup()
    runs = [e for e in spans() if e['name'] == 'decode.warmup.run']
    assert [(e['args']['program'], e['args']['bucket']) for e in runs] \
        == sorted(built_by_warmup(chunked), key=lambda p: p[0] == 'step')
    assert all(set(e['args']) == {'program', 'bucket'} and e['dur'] > 0
               for e in runs)
    # the runs come after every compile, and one at a time
    last = max(e['ts'] + e['dur'] for e in spans()
               if e['name'] == 'decode.compile')
    assert all(last <= e['ts'] for e in runs)
    for a, b in zip(runs, runs[1:]):
        assert a['ts'] + a['dur'] <= b['ts']


def test_a_bucket_met_after_warmup_has_its_stages_under_its_prefill(params):
    eng = make_engine(params, top=32)
    eng.buckets = [8, 16]
    eng.warmup()
    timeline.ring().clear()
    eng.buckets = [8, 16, 32]
    eng.prefill_into(np.arange(1, 21, dtype=np.int32), eng.cache.alloc(3))
    evs = spans()
    _by_id, kids, ancestors = family(evs)
    comp = [e for e in evs if e['name'] == 'decode.compile']
    assert len(comp) == 2
    for c in comp:
        assert [k['name'] for k in kids[c['id']]] == STAGES
        assert ancestors(c) == ['decode.prefill_into']
    assert sorted(e['name'] for e in evs) == sorted(
        STAGES * 2 + ['decode.compile'] * 2 + ['decode.prefill_into'])
    # the re-warm builds nothing, and runs everything once more
    timeline.ring().clear()
    eng.warmup()
    warm, = [e for e in spans() if e['name'] == 'decode.warmup']
    assert warm['args'] == {'programs': 0, 'runs': 7}
    assert {e['name'] for e in spans()} == {'decode.warmup',
                                            'decode.warmup.run'}


@pytest.mark.parametrize('chunked', [False, True])
def test_a_second_warmup_with_nothing_new_records_no_span(params, chunked):
    eng = make_engine(params, prefill_chunk_tokens=PAGE if chunked else 0)
    eng.warmup()
    assert spans()
    timeline.ring().clear()
    eng.warmup()
    assert timeline.ring().events() == []
    assert eng.compiles_after_warmup == 0


def test_metrics_disabled_leaves_none_of_the_setup_spans(params):
    obs.set_enabled(False)
    try:
        eng = make_engine(params, top=32)
        eng.buckets = [8, 16]
        eng.warmup()
        eng.buckets = [8, 16, 32]
        eng.prefill_into(np.arange(1, 21, dtype=np.int32),
                         eng.cache.alloc(3))
        assert eng.compiles_total == 7 and eng.compiles_after_warmup == 2
        assert timeline.ring().events() == []
    finally:
        obs.reload_enabled()
    timeline.ring().clear()
    eng.warmup()        # and with them on again, the re-warm is seen
    assert {e['name'] for e in spans()} == {'decode.warmup',
                                            'decode.warmup.run'}


def test_the_backend_span_says_what_the_cache_did(params, compile_cache):
    """With a persistent cache, a fresh directory: every program of the
    first engine is a miss (XLA compiled it and the entry was written),
    every program of a second engine over the same weights a hit; the
    other cases of this file, with no cache, read ``'off'``."""
    said = []
    for _ in range(2):
        timeline.ring().clear()
        make_engine(params, prefill_chunk_tokens=PAGE).warmup()
        said.append([e['args']['cache'] for e in spans()
                     if e['name'] == 'decode.compile.backend'])
    assert said == [['miss', 'miss'], ['hit', 'hit']]
    assert any(compile_cache.iterdir())


# chipbench's reader of these spans, on a ring built by hand: its cases
# live with the benchmark (chipbench/tests/test_setup_spans.py, which tier
# 1 does not collect) and are collected here by name
from chipbench.tests.test_setup_spans import (  # noqa: E402,F401
    test_a_cut_ring_gives_none_never_a_partial_sum,
    test_a_ring_without_the_spans_gives_none_and_not_zero,
    test_sums_by_name_counts_by_cache_and_stops_at_t_open,
    test_the_setup_spans_line_has_a_row_a_program_once_a_run)

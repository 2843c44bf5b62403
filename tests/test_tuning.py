"""ISSUE 16 autotuner: registry / search / cache / executor-apply.

The tier-1 contract this file pins (ISSUE.md acceptance):

- deterministic search: fixed fake measurements give an identical
  winner and trace, twice;
- cost-model pruning: HBM-budget blowouts and modeled-much-worse
  candidates are never measured;
- persistence: winners round-trip through the on-disk cache keyed by
  (plan key, device kind, mesh) — a second build does zero search, a
  changed plan key or mesh misses, a corrupted file is counted and
  falls back safely;
- CPU dry-run smoke on a real program: the chosen config is modeled at
  least as fast as the defaults;
- PADDLE_TPU_TUNE=off (the default) leaves executor behavior bitwise
  identical.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.tuning import cache as tcache
from paddle_tpu.tuning import registry, roofline
from paddle_tpu.tuning import runtime as trt
from paddle_tpu.tuning import search as tsearch


@pytest.fixture(autouse=True)
def _clean_tuner_env(monkeypatch):
    """Every test starts untuned: no tuner-applied env, no memo."""
    saved = set(registry._TUNER_APPLIED)
    for env in saved:
        monkeypatch.delenv(env, raising=False)
    registry._TUNER_APPLIED.clear()
    trt.reset()
    yield
    for t in registry.registered_tunables():
        if t.env in registry._TUNER_APPLIED:
            os.environ.pop(t.env, None)
    registry._TUNER_APPLIED.clear()
    registry._TUNER_APPLIED.update(saved)
    trt.reset()


def _fake_tunables():
    """A private two-knob registry slice for search unit tests."""
    return [
        registry.Tunable('tile', (1, 2, 4), 2, 'test',
                         env='PADDLE_TPU_DEVICE_PREFETCH_CHUNK'),
        registry.Tunable('mode', ('a', 'b'), 'a', 'test',
                         env='PADDLE_TPU_AMP'),
    ]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_covers_the_hand_set_tunables():
    names = [t.name for t in registry.registered_tunables()]
    for expected in ('device_prefetch_chunk', 'amp',
                     'mesh', 'embed_bucket_tile', 'embed_cache_rows',
                     'serving_max_wait_ms', 'serving_max_batch',
                     'train_batch', 'run_steps_k'):
        assert expected in names, names
    for t in registry.registered_tunables():
        assert isinstance(t.domain, tuple) and 1 < len(t.domain) <= 64
        assert t.default in t.domain, t
        assert t.env.startswith('PADDLE_TPU_'), t
        for v in t.domain:
            assert t.coerce(t.encode(v)) == v, (t.name, v)


def test_pinning_and_applied_restore(monkeypatch):
    t = registry.tunable('device_prefetch_chunk')
    assert not registry.is_pinned(t)
    monkeypatch.setenv(t.env, '4')
    assert registry.is_pinned(t)  # user-set env pins
    assert registry.current_config([t])[t.name] == 4
    monkeypatch.delenv(t.env)
    with registry.applied({t.name: 8}):
        assert os.environ[t.env] == '8'
    assert t.env not in os.environ  # restored


def test_apply_persistent_masks_in_base_env_and_never_repins(
        monkeypatch):
    t = registry.tunable('device_prefetch_chunk')
    done = registry.apply_persistent({t.name: 4})
    assert done == {t.name: 4}
    assert os.environ[t.env] == '4'
    # the tuner set it, so it does NOT pin and base_env masks it
    assert not registry.is_pinned(t)
    with registry.base_env():
        assert t.env not in os.environ
    assert os.environ[t.env] == '4'
    # a user-pinned tunable is never overwritten
    p = registry.tunable('amp')
    monkeypatch.setenv(p.env, 'bf16')
    assert registry.apply_persistent({p.name: 'f16'}) == {}
    assert os.environ[p.env] == 'bf16'


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def test_search_deterministic_fixed_measurements():
    model = {(1, 'a'): 1.0, (2, 'a'): 0.8, (4, 'a'): 0.7,
             (1, 'b'): 0.9, (2, 'b'): 0.5, (4, 'b'): 0.4}

    def run_once():
        tun = _fake_tunables()
        tuner = tsearch.Autotuner(
            model_fn=lambda c: {'score': model[(c['tile'], c['mode'])],
                                'peak_bytes': 0},
            measure_fn=lambda c: model[(c['tile'], c['mode'])],
            tunables=tun, hbm_budget_bytes=0, measure_budget=100)
        return tuner.search()

    r1, r2 = run_once(), run_once()
    assert r1.winners == r2.winners == {'tile': 4, 'mode': 'b'}
    assert r1.trace == r2.trace
    assert r1.best_score == pytest.approx(0.4)
    assert 'winner' in r1.format_trace()


def test_ties_keep_the_incumbent():
    tun = [registry.Tunable('tile', (1, 2), 1, 'test',
                            env='PADDLE_TPU_DEVICE_PREFETCH_CHUNK')]
    tuner = tsearch.Autotuner(
        model_fn=lambda c: {'score': 1.0, 'peak_bytes': 0},
        measure_fn=lambda c: 1.0, tunables=tun, hbm_budget_bytes=0,
        measure_budget=10)
    assert tuner.search().winners == {}


def test_hbm_budget_prunes_without_measuring():
    measured = []

    def measure(c):
        measured.append(dict(c))
        return 1.0

    tun = [registry.Tunable('tile', (1, 2, 4), 1, 'test',
                            env='PADDLE_TPU_DEVICE_PREFETCH_CHUNK')]
    tuner = tsearch.Autotuner(
        model_fn=lambda c: {'score': 1.0,
                            'peak_bytes': c['tile'] * 10 ** 9},
        measure_fn=measure, tunables=tun,
        hbm_budget_bytes=2 * 10 ** 9, measure_budget=100)
    r = tuner.search()
    # tile=4 models at 4GB > 2GB budget: pruned, never measured
    assert not any(c['tile'] == 4 for c in measured)
    pruned = [e for e in r.trace if e['action'] == 'pruned']
    assert any('HBM budget' in (e['reason'] or '') for e in pruned)


def test_modeled_worse_prunes_and_budget_bounds_measurements():
    measured = []
    tun = [registry.Tunable('tile', (1, 2, 4, 8), 1, 'test',
                            env='PADDLE_TPU_DEVICE_PREFETCH_CHUNK')]
    tuner = tsearch.Autotuner(
        model_fn=lambda c: {'score': float(c['tile']), 'peak_bytes': 0},
        measure_fn=lambda c: measured.append(dict(c)) or 1.0,
        tunables=tun, hbm_budget_bytes=0, prune_slack=0.15,
        measure_budget=100)
    r = tuner.search()
    # every candidate models worse than the incumbent (score=tile):
    # all pruned, only the baseline measured
    assert len(measured) == 1
    assert r.winners == {}
    reasons = [e['reason'] for e in r.trace
               if e['action'] == 'pruned']
    assert any('worse than incumbent' in (x or '') for x in reasons)
    # measure budget: with pruning disabled, the cap binds
    measured.clear()
    tuner = tsearch.Autotuner(
        model_fn=None,
        measure_fn=lambda c: measured.append(dict(c)) or 1.0,
        tunables=tun, hbm_budget_bytes=0, measure_budget=2)
    r = tuner.search()
    assert len(measured) == 2
    assert any('budget exhausted' in (e['reason'] or '')
               for e in r.trace)


def test_pinned_tunable_skipped_by_search(monkeypatch):
    t = registry.tunable('amp')
    monkeypatch.setenv(t.env, 'bf16')
    tuner = tsearch.Autotuner(
        model_fn=lambda c: {'score': 1.0, 'peak_bytes': 0},
        tunables=[t, registry.tunable('device_prefetch_chunk')])
    r = tuner.search()
    assert all(e['tunable'] != 'amp' for e in r.trace[1:])
    assert r.config.get('amp') == 'bf16'  # pinned value rides along


def test_infeasible_mesh_candidates_never_measured():
    t = registry.tunable('mesh')
    import jax
    ndev = len(jax.devices())
    tuner = tsearch.Autotuner(
        model_fn=lambda c: {'score': 1.0, 'peak_bytes': 0},
        tunables=[t])
    r = tuner.search()
    bad = [e for e in r.trace
           if e.get('reason') == 'infeasible on this backend']
    # conftest forces 8 virtual devices: every candidate needing more
    # than ndev is pruned as infeasible
    needs = {'dp=2': 2, 'dp=4': 4, 'dp=8': 8, 'fsdp=8': 8,
             'dp=4,fsdp=2': 8, 'dp=2,tp=2': 4}
    for spec, n in needs.items():
        if n > ndev:
            assert any(e['value'] == spec for e in bad)


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def test_cache_roundtrip_and_keying(tmp_path):
    c = tcache.TuneCache(str(tmp_path))
    k1 = c.key(('pm', 2, 'bf16'), 'cpu', None)
    k2 = c.key(('pm', 2, None), 'cpu', None)      # different plan key
    k3 = c.key(('pm', 2, 'bf16'), 'cpu', (('dp', 2),))  # different mesh
    k4 = c.key(('pm', 2, 'bf16'), 'TPU v5e', None)  # different device
    assert len({k1, k2, k3, k4}) == 4
    assert c.load(k1) is None  # miss
    assert c.store(k1, {'amp': 'bf16'}, meta={'base_score': 1.0})
    assert c.load(k1) == {'amp': 'bf16'}
    assert c.load(k2) is None
    assert c.load(k3) is None


def test_corrupted_cache_file_counts_and_falls_back(tmp_path):
    c = tcache.TuneCache(str(tmp_path))
    k = c.key(('pm',), 'cpu', None)
    assert c.store(k, {'amp': 'bf16'})
    before = tcache.TuneCache.stats()['corrupt']
    with open(c.path(k), 'w') as f:
        f.write('{not json')
    assert c.load(k) is None  # no crash
    # wrong schema is corruption too, not a silent hit
    with open(c.path(k), 'w') as f:
        json.dump({'schema': 999, 'winners': {'amp': 'bf16'}}, f)
    assert c.load(k) is None
    assert tcache.TuneCache.stats()['corrupt'] == before + 2


def test_cache_dir_resolution(monkeypatch, tmp_path):
    """PADDLE_TPU_TUNE_CACHE_DIR wins; unset, the winners live beside
    the compiled executables in the compile-cache directory; an explicit
    empty root disables persistence."""
    from paddle_tpu.compile_cache import compile_cache_dir
    monkeypatch.setenv('PADDLE_TPU_TUNE_CACHE_DIR', str(tmp_path))
    assert tcache.TuneCache().root == str(tmp_path / 'paddle_tpu_tuning')
    monkeypatch.delenv('PADDLE_TPU_TUNE_CACHE_DIR')
    assert tcache.TuneCache().root == os.path.join(
        compile_cache_dir(), 'paddle_tpu_tuning')
    c = tcache.TuneCache('')
    assert not c.enabled()
    assert c.load('deadbeef') is None
    assert not c.store('deadbeef', {'amp': 'bf16'})


def test_autotune_cached_mode_zero_search(tmp_path):
    tun = _fake_tunables()
    cache = tcache.TuneCache(str(tmp_path))
    key = cache.key(('pm', 2), 'cpu', None)
    model = lambda c: {'score': 1.0 / c['tile'], 'peak_bytes': 0}  # noqa: E731
    r = tsearch.autotune(model, tunables=tun, cache=cache,
                         cache_key=key, mode='search')
    assert not r.cached and r.winners == {'tile': 4}

    def boom(c):
        raise AssertionError('cached mode must not search or measure')

    r2 = tsearch.autotune(boom, measure_fn=boom, tunables=tun,
                          cache=cache, cache_key=key, mode='cached')
    assert r2.cached and r2.winners == {'tile': 4}
    assert 'cache hit' in r2.format_trace()
    # a cold key in cached mode returns defaults untouched, no search
    r3 = tsearch.autotune(boom, measure_fn=boom, tunables=tun,
                          cache=cache,
                          cache_key=cache.key(('pm', 3), 'cpu', None),
                          mode='cached')
    assert not r3.cached and r3.winners == {}
    assert tsearch.autotune(boom, mode='off') is None


# ---------------------------------------------------------------------------
# CPU dry-run smoke on a real program (the tier-1 acceptance check)
# ---------------------------------------------------------------------------

def _small_program():
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data(name='x', shape=[32], dtype='float32')
        label = fluid.layers.data(name='label', shape=[1],
                                  dtype='int64')
        h = fluid.layers.fc(input=x, size=64, act='relu')
        pred = fluid.layers.fc(input=h, size=10, act='softmax')
        cost = fluid.layers.mean(x=fluid.layers.cross_entropy(
            input=pred, label=label))
        fluid.optimizer.SGDOptimizer(0.1).minimize(cost)
    return main_p, startup, cost


def test_dryrun_smoke_chosen_config_modeled_no_worse(tmp_path):
    prog, _startup, cost = _small_program()
    feed_specs = {'x': ((8, 32), 'float32'), 'label': ((8, 1), 'int32')}
    tun = [registry.tunable('amp'),
           registry.tunable('device_prefetch_chunk')]

    def model_fn(cfg):
        with registry.applied(cfg):
            return trt.model_program(prog, fetch_names=(cost.name,),
                                     feed_specs=feed_specs)

    base_model = model_fn(registry.current_config(tun))
    assert base_model is not None and base_model['score'] > 0
    assert base_model['peak_bytes'] > 0

    cache = tcache.TuneCache(str(tmp_path))
    key = trt.cache_key_for(prog)
    r = tsearch.autotune(model_fn, tunables=tun, cache=cache,
                         cache_key=key, mode='search')
    assert not r.cached
    # dry-run contract: the chosen config is modeled at least as fast
    # as the defaults (strict < to adopt, ties keep the incumbent)
    assert r.best_score <= base_model['score']
    # winners round-trip: the second build is a cache hit, zero search
    def boom(c):
        raise AssertionError('second build must not search')
    r2 = tsearch.autotune(boom, tunables=tun, cache=cache,
                          cache_key=key, mode='cached')
    assert r2.cached and r2.winners == r.winners


def test_cache_key_separates_programs_but_not_rebuilds():
    prog_a, _s, _c = _small_program()
    prog_a2, _s2, _c2 = _small_program()  # same model, later build
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        loss = fluid.layers.mean(x=fluid.layers.square_error_cost(
            input=fluid.layers.fc(input=x, size=1), label=y))
        fluid.optimizer.SGDOptimizer(0.01).minimize(loss)
    # distinct models never share winners; in-process rebuilds (whose
    # var-name counters differ) and fresh-process builds do — the
    # fingerprint is the op-type multiset, not names
    assert trt.cache_key_for(prog_a) == trt.cache_key_for(prog_a2)
    assert trt.cache_key_for(prog_a) != trt.cache_key_for(main_p)


def test_cache_key_stable_under_tuner_applied_env(tmp_path):
    prog, _startup, _cost = _small_program()
    key_fresh = trt.cache_key_for(prog)
    # after the tuner applies a plan-affecting winner, the key must not
    # move (base_env masks it) — the zero-search-restart contract
    registry.apply_persistent({'amp': 'bf16'})
    assert trt.cache_key_for(prog) == key_fresh
    # but a USER-pinned plan-affecting env legitimately changes the key
    os.environ.pop('PADDLE_TPU_AMP', None)
    registry._TUNER_APPLIED.discard('PADDLE_TPU_AMP')
    os.environ['PADDLE_TPU_AMP'] = 'bf16'
    try:
        assert trt.cache_key_for(prog) != key_fresh
    finally:
        os.environ.pop('PADDLE_TPU_AMP', None)


# ---------------------------------------------------------------------------
# executor integration
# ---------------------------------------------------------------------------

def _feed():
    rng = np.random.default_rng(0)
    return {'x': rng.normal(size=(8, 32)).astype(np.float32),
            'label': rng.integers(0, 10, (8, 1)).astype(np.int32)}


def test_executor_applies_cached_winners(tmp_path, monkeypatch):
    prog, startup, cost = _small_program()
    monkeypatch.setenv('PADDLE_TPU_TUNE_CACHE_DIR', str(tmp_path))
    cache = tcache.TuneCache(str(tmp_path))
    cache.store(trt.cache_key_for(prog), {'device_prefetch_chunk': 4})
    monkeypatch.setenv('PADDLE_TPU_TUNE', 'cached')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    out = exe.run(prog, feed=_feed(), fetch_list=[cost])
    assert np.isfinite(np.asarray(out[0])).all()
    # the winner was applied to the process env by the executor hook
    assert os.environ.get('PADDLE_TPU_DEVICE_PREFETCH_CHUNK') == '4'
    assert 'PADDLE_TPU_DEVICE_PREFETCH_CHUNK' in \
        registry.tuner_applied_env()


def test_tune_off_is_bitwise_identical(tmp_path, monkeypatch):
    prog, startup, cost = _small_program()
    prog.random_seed = startup.random_seed = 7  # deterministic init
    # a poisoned cache that would change behavior if it were consulted
    cache = tcache.TuneCache(str(tmp_path))
    cache.store(trt.cache_key_for(prog), {'device_prefetch_chunk': 4})
    monkeypatch.setenv('PADDLE_TPU_TUNE_CACHE_DIR', str(tmp_path))
    monkeypatch.delenv('PADDLE_TPU_TUNE', raising=False)
    env_before = dict(os.environ)

    def run_twice():  # two SGD steps in a fresh scope
        scope = fluid.core.scope.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            a = np.asarray(exe.run(prog, feed=_feed(),
                                   fetch_list=[cost])[0])
            b = np.asarray(exe.run(prog, feed=_feed(),
                                   fetch_list=[cost])[0])
        return a, b

    a1, b1 = run_twice()
    assert trt.maybe_apply_cached(prog) is None  # off: no-op
    a2, b2 = run_twice()
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(b1, b2)
    assert dict(os.environ) == env_before  # nothing applied


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

def _fake_cost():
    return {
        'total': {'flops': 4.0e9, 'bytes': 1.0e8},
        'memory': {'peak_bytes': 123},
        'per_op': [
            {'index': 0, 'type': 'mul', 'role': 'forward',
             'flops': 3.8e11, 'bytes': 1.0e6},     # mxu-bound
            {'index': 1, 'type': 'relu', 'role': 'forward',
             'flops': 1.0e6, 'bytes': 9.0e7},      # hbm-bound
            {'index': 2, 'type': 'add', 'role': 'forward',
             'flops': 1.0e5, 'bytes': 9.0e6},
        ],
    }


def test_roofline_report_names_top_ops_and_limiting_resource():
    rep = roofline.report(_fake_cost(), measured_step_s=1e-2, top=2)
    assert rep['floor_s'] > 0 and rep['gap'] > 1
    assert len(rep['top']) == 2
    # ordered by modeled floor: the big matmul first, mxu-bound;
    # the relu second, hbm-bound
    assert rep['top'][0]['type'] == 'mul'
    assert rep['top'][0]['bound'] == 'mxu'
    assert rep['top'][1]['type'] == 'relu'
    assert rep['top'][1]['bound'] == 'hbm'
    assert rep['top'][0]['lost_s'] > rep['top'][1]['lost_s']
    text = roofline.format_report(rep)
    assert 'off roofline' in text and 'mxu-bound' in text


def test_roofline_flag_overrides(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_PEAK_TFLOPS', '100')
    monkeypatch.setenv('PADDLE_TPU_HBM_GBPS', '400')
    assert roofline.resolved_peak_tflops() == 100.0
    assert roofline.resolved_hbm_gbps() == 400.0
    rep = roofline.report(_fake_cost())
    assert rep['peak_tflops'] == 100.0


# ---------------------------------------------------------------------------
# lint wiring
# ---------------------------------------------------------------------------

def test_check_tunables_green():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), 'tools', 'check_tunables.py')
    spec = importlib.util.spec_from_file_location('check_tunables', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.check() == []

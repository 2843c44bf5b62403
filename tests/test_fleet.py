"""ServingFleet: multi-replica dispatch, lifecycle, and hot-swap.

Covers the fleet contract end to end on the CPU smoke config: version
resolution, queue-depth routing, drain-vs-close on the batching server,
dispatch-failure containment (retry + unroutable + health restore),
versioned deploy/rollback under live traffic with zero dropped
requests, warm-cache cold start, and metric labeling/retirement.
"""
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import io, observability
from paddle_tpu.inference import (BatchingInferenceServer,
                                  InferenceServer, ServingFleet,
                                  export_bucketed)

MAX_BATCH = 4


def _build_mlp(seed):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[6], dtype='float32')
        h = fluid.layers.fc(input=x, size=16, act='relu')
        pred = fluid.layers.fc(input=h, size=4)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    return main, scope, exe, pred


@pytest.fixture(scope='module')
def versions(tmp_path_factory):
    """A TF-Serving-style base dir with two numbered model versions
    (different init seeds, so their outputs differ measurably)."""
    base = tmp_path_factory.mktemp('model_versions')
    for ver, seed in (('1', 11), ('2', 42)):
        main, scope, exe, pred = _build_mlp(seed)
        export_bucketed(str(base / ver), {'x': (6,)}, [pred],
                        executor=exe, main_program=main, scope=scope,
                        max_batch=MAX_BATCH)
    return str(base)


def _feed(rng, rows=1):
    return {'x': rng.randn(rows, 6).astype('float32')}


def _mk_fleet(versions, **kw):
    kw.setdefault('replicas', 2)
    kw.setdefault('max_wait_ms', 20.0)
    kw.setdefault('linger_ms', 0.5)
    kw.setdefault('health_interval_ms', 0)  # off unless a test needs it
    return ServingFleet(versions, **kw)


# -- io.py version resolution -----------------------------------------
def test_resolve_version_dir(versions, tmp_path):
    d, name = io.resolve_version_dir(versions)
    assert name == '2' and d.endswith('2')  # highest number wins
    d1, n1 = io.resolve_version_dir(versions, version='1')
    assert n1 == '1' and io.bucket_artifacts(d1)
    # a bare artifact dir resolves to itself
    d2, n2 = io.resolve_version_dir(os.path.join(versions, '1'))
    assert d2 == os.path.join(versions, '1') and n2 == '1'
    assert sorted(io.bucket_artifacts(d2)) == [1, 2, 4]
    with pytest.raises(ValueError):
        io.resolve_version_dir(versions, version='99')
    # a dir holding neither artifacts nor version subdirs with them
    (tmp_path / 'not_a_version').mkdir()
    with pytest.raises(ValueError):
        io.resolve_version_dir(str(tmp_path))


# -- batching drain / post-close submit hooks --------------------------
def test_drain_flushes_then_rejects(versions):
    paths = io.bucket_artifacts(os.path.join(versions, '1'))
    srv = BatchingInferenceServer(paths, max_wait_ms=40.0,
                                  linger_ms=1.0)
    try:
        rng = np.random.RandomState(0)
        futs = [srv.submit(_feed(rng)) for _ in range(10)]
        assert srv.drain(timeout=30.0) is True
        # everything queued before the drain completed
        for f in futs:
            out, = f.result(timeout=5.0)
            assert out.shape == (1, 4)
        # the server is retired for new work but alive for stats()
        with pytest.raises(RuntimeError, match='draining'):
            srv.submit(_feed(rng))
        st = srv.stats()
        assert st['requests_completed'] == 10
        assert st['queue_depth'] == 0 and st['in_flight_batches'] == 0
        assert srv.queue_state()['accepting'] is False
    finally:
        srv.close()
    with pytest.raises(RuntimeError, match='closed'):
        srv.submit(_feed(np.random.RandomState(1)))


def test_submit_after_close_raises_even_under_backpressure(versions):
    """A submit blocked on queue backpressure must observe close() and
    raise — not enqueue into the dead dispatcher and hang."""
    paths = io.bucket_artifacts(os.path.join(versions, '1'))
    srv = BatchingInferenceServer(paths, warmup=False, max_queue=1,
                                  max_wait_ms=10000.0,
                                  linger_ms=10000.0)
    rng = np.random.RandomState(2)
    srv.submit(_feed(rng))  # fills the queue (dispatcher lingers)
    errors = []

    def blocked_submit():
        try:
            srv.submit(_feed(rng))
        except RuntimeError as e:
            errors.append(e)

    t = threading.Thread(target=blocked_submit)
    t.start()
    time.sleep(0.1)  # let it block on backpressure
    srv.close()
    t.join(10.0)
    assert not t.is_alive(), "submit hung past close()"
    assert len(errors) == 1 and 'closed' in str(errors[0])


def test_queue_wait_compute_split_in_stats(versions):
    paths = io.bucket_artifacts(os.path.join(versions, '1'))
    srv = BatchingInferenceServer(paths, max_wait_ms=20.0,
                                  linger_ms=0.5)
    try:
        rng = np.random.RandomState(3)
        for rows in (1, 2, 4, 1, 3):
            srv.predict(_feed(rng, rows), timeout=30.0)
        st = srv.stats()
        for key in ('queue_wait_p50_ms', 'queue_wait_p99_ms',
                    'compute_p50_ms', 'compute_p99_ms'):
            assert key in st and st[key] >= 0.0
        assert st['per_bucket'], "no per-bucket split recorded"
        for b, row in st['per_bucket'].items():
            assert b in st['buckets']
            assert row['batches'] >= 1
            assert row['compute_p99_ms'] > 0.0
        # the split is consistent with the end-to-end latency: a
        # request waits then computes, so neither span can exceed the
        # p99 of the whole by more than measurement slop
        assert st['queue_wait_p50_ms'] <= st['p99_latency_ms'] + 1.0
        # the same histograms are what /metrics exports
        text = observability.prometheus_text()
        assert 'paddle_tpu_serving_queue_wait_seconds_bucket' in text
        assert 'paddle_tpu_serving_compute_seconds_bucket' in text
    finally:
        srv.close()


# -- fleet routing -----------------------------------------------------
def test_fleet_serves_and_matches_reference(versions):
    fleet = _mk_fleet(versions)
    try:
        assert fleet.version == '2'
        ref = InferenceServer(
            io.bucket_artifacts(os.path.join(versions, '2'))[1])
        rng = np.random.RandomState(4)
        for _ in range(8):
            f = _feed(rng)
            got, = fleet.predict(f, timeout=30.0)
            want, = ref.predict(f)
            np.testing.assert_allclose(got, np.asarray(want),
                                       rtol=1e-5, atol=1e-6)
        st = fleet.stats()
        assert st['failed'] == 0 and st['completed'] == 8
        # round-robin tie-breaking spread the idle-fleet requests over
        # both replicas instead of piling on replica 0
        done = [p['server']['requests_completed']
                for p in st['replicas']]
        assert all(d > 0 for d in done), done
    finally:
        fleet.close()


def test_fleet_routes_to_less_loaded_replica(versions):
    fleet = _mk_fleet(versions)
    try:
        rep_busy, rep_idle = fleet._replicas
        # pile synthetic queue depth onto one replica
        with rep_busy.server._cv:
            rep_busy.server._pending_rows += 1000
        try:
            picked = {fleet._pick(frozenset()).rid for _ in range(6)}
            assert picked == {rep_idle.rid}
        finally:
            with rep_busy.server._cv:
                rep_busy.server._pending_rows -= 1000
    finally:
        fleet.close()


def test_fleet_default_replicas_flag(versions, monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_FLEET_REPLICAS', '1')
    fleet = ServingFleet(versions, health_interval_ms=0)
    try:
        assert len(fleet.replica_ids) == 1
    finally:
        fleet.close()


# -- failure containment ----------------------------------------------
def _break(rep):
    """Make a replica's dispatch path fail (simulated dead process)."""
    def boom(feed, **kw):  # accepts request_id= like the real submit
        raise OSError("replica %s: injected dispatch failure" % rep.rid)
    rep.server.submit = boom


def test_dispatch_failure_is_retried_and_marks_unroutable(versions):
    fleet = _mk_fleet(versions, unroutable_after=1, retry_limit=2)
    try:
        bad = fleet._replicas[0]
        _break(bad)
        rng = np.random.RandomState(5)
        # clients still get results: rerouted to the healthy replica
        for _ in range(4):
            out, = fleet.predict(_feed(rng), timeout=30.0)
            assert out.shape == (1, 4)
        st = fleet.stats()
        assert st['failed'] == 0
        assert st['unroutable'] == 1
        bad_stat, = [p for p in st['replicas'] if p['id'] == bad.rid]
        assert bad_stat['state'] == 'unroutable'
        # once unroutable it is out of routing: no more retries needed
        before = st['retries']
        fleet.predict(_feed(rng), timeout=30.0)
        assert fleet.stats()['retries'] == before
    finally:
        fleet.close()


def test_health_loop_restores_recovered_replica(versions):
    fleet = _mk_fleet(versions, unroutable_after=1, retry_limit=2,
                      health_interval_ms=30.0)
    try:
        bad = fleet._replicas[0]
        orig_submit = bad.server.submit
        _break(bad)
        rng = np.random.RandomState(6)
        fleet.predict(_feed(rng), timeout=30.0)  # strikes the replica
        deadline = time.time() + 5.0
        while bad.state != 'unroutable' and time.time() < deadline:
            time.sleep(0.01)
        assert bad.state == 'unroutable'
        # replica recovers: the next health probe restores it
        del bad.server.submit  # back to the class method
        assert bad.server.submit == orig_submit.__func__.__get__(
            bad.server)
        deadline = time.time() + 10.0
        while bad.state != 'ready' and time.time() < deadline:
            time.sleep(0.02)
        assert bad.state == 'ready', "health loop never restored it"
        assert fleet.stats()['health_probes'] >= 1
        assert fleet.stats()['failed'] == 0
    finally:
        fleet.close()


def test_all_replicas_dead_yields_clear_error(versions):
    fleet = _mk_fleet(versions, replicas=2, unroutable_after=1,
                      retry_limit=3)
    try:
        for rep in list(fleet._replicas):
            _break(rep)
        rng = np.random.RandomState(7)
        fut = fleet.submit(_feed(rng))
        with pytest.raises(Exception) as ei:
            fut.result(timeout=30.0)
        assert 'injected dispatch failure' in str(ei.value) \
            or 'no routable replica' in str(ei.value)
        assert fleet.stats()['failed'] == 1
    finally:
        fleet.close()


def test_invalid_feed_fails_fast_without_striking_replicas(versions):
    fleet = _mk_fleet(versions)
    try:
        fut = fleet.submit({'x': np.zeros((1, 7), np.float32)})
        with pytest.raises(ValueError):
            fut.result(timeout=10.0)
        st = fleet.stats()
        assert st['unroutable'] == 0 and st['retries'] == 0
    finally:
        fleet.close()


# -- lifecycle under traffic ------------------------------------------
class _Traffic(object):
    """Background closed-loop client recording per-request outcomes."""

    def __init__(self, fleet, rng, period_s=0.002):
        self.fleet = fleet
        self.rng = rng
        self.period = period_s
        self.errors = []
        self.ok = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            try:
                out, = self.fleet.predict(_feed(self.rng), timeout=30.0)
                assert out.shape == (1, 4)
                self.ok += 1
            except Exception as e:  # pragma: no cover - the assertion
                self.errors.append(e)
            time.sleep(self.period)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(30.0)


def test_remove_add_replica_under_traffic(versions):
    fleet = _mk_fleet(versions, replicas=2)
    try:
        rng = np.random.RandomState(8)
        with _Traffic(fleet, rng) as traffic:
            time.sleep(0.2)
            rid = fleet.remove_replica()
            assert rid not in fleet.replica_ids
            assert len(fleet.replica_ids) == 1
            time.sleep(0.2)
            new_rid = fleet.add_replica()
            assert new_rid in fleet.replica_ids
            time.sleep(0.2)
        assert traffic.errors == []
        assert traffic.ok > 0
        assert fleet.stats()['failed'] == 0
        with pytest.raises(ValueError):
            fleet.remove_replica('nonexistent')
    finally:
        fleet.close()


def test_deploy_hot_swap_and_rollback_under_traffic(versions):
    fleet = ServingFleet(os.path.join(versions, '1'), replicas=2,
                         max_wait_ms=20.0, linger_ms=0.5,
                         health_interval_ms=0)
    try:
        ref1 = InferenceServer(
            io.bucket_artifacts(os.path.join(versions, '1'))[1])
        ref2 = InferenceServer(
            io.bucket_artifacts(os.path.join(versions, '2'))[1])
        rng = np.random.RandomState(9)
        probe = _feed(rng)
        w1 = np.asarray(ref1.predict(probe)[0])
        w2 = np.asarray(ref2.predict(probe)[0])
        assert not np.allclose(w1, w2)  # versions are distinguishable

        np.testing.assert_allclose(fleet.predict(probe, 30.0)[0], w1,
                                   rtol=1e-5, atol=1e-6)
        with _Traffic(fleet, np.random.RandomState(10)) as traffic:
            time.sleep(0.1)
            name = fleet.deploy(os.path.join(versions, '2'))
            assert name == '2' and fleet.version == '2'
            # post-flip requests answer with the NEW version
            np.testing.assert_allclose(
                fleet.predict(probe, 30.0)[0], w2,
                rtol=1e-5, atol=1e-6)
            time.sleep(0.1)
            back = fleet.rollback()
            assert back == '1' and fleet.version == '1'
            np.testing.assert_allclose(
                fleet.predict(probe, 30.0)[0], w1,
                rtol=1e-5, atol=1e-6)
        assert traffic.errors == []  # zero dropped/failed mid-swap
        st = fleet.stats()
        assert st['failed'] == 0
        assert st['deploys'] == 3 and st['rollbacks'] == 1
        # every live replica serves the rolled-back version
        assert {p['version'] for p in st['replicas']} == {'1'}
    finally:
        fleet.close()


def test_deploy_record_prev_protocol(versions, tmp_path):
    """The deploy record rides io.write_rollback_json: the .prev
    archive always holds the superseded deployment."""
    state = str(tmp_path / 'state')
    fleet = ServingFleet(os.path.join(versions, '1'), replicas=1,
                         state_dir=state, health_interval_ms=0)
    try:
        rec = io.read_rollback_json(os.path.join(state, 'DEPLOY.json'))
        assert rec['version'] == '1'
        assert io.read_rollback_json(
            os.path.join(state, 'DEPLOY.json'), prev=True) is None
        fleet.deploy(os.path.join(versions, '2'))
        rec = io.read_rollback_json(os.path.join(state, 'DEPLOY.json'))
        prev = io.read_rollback_json(
            os.path.join(state, 'DEPLOY.json'), prev=True)
        assert rec['version'] == '2' and prev['version'] == '1'
    finally:
        fleet.close()
    assert os.path.isdir(state)  # caller-owned state dir survives


# -- AOT-warmed cold start --------------------------------------------
def test_cold_replica_with_warm_cache_reports_zero_compiles(
        versions, compile_cache):
    """Acceptance: with a pre-populated persistent compile cache, a
    cold replica joining the fleet reports 0 post-warmup compiles
    before its first routed request — and its warmup is pure cache
    hits (the cache directory gains no new entries)."""
    cache = str(compile_cache)
    fleet = _mk_fleet(versions, replicas=1)
    try:
        assert os.path.isdir(cache) and os.listdir(cache), \
            "warmup did not populate the persistent cache"
        n_entries = len(os.listdir(cache))
        first, = fleet._replicas
        n_buckets = len(io.bucket_artifacts(
            os.path.join(versions, '2')))
        assert fleet.stats()['replicas'][0]['compiles'] == n_buckets
        rid = fleet.add_replica()  # the cold replica joining
        st = fleet.stats()
        cold, = [p for p in st['replicas'] if p['id'] == rid]
        # the joiner shares the live sibling's compiled servable:
        # serving-ready with ZERO compiles of its own, and the
        # persistent cache gains nothing (no recompile anywhere)
        assert cold['compiles'] == 0
        assert cold['compiles_after_warmup'] == 0
        added, = [r for r in fleet._replicas if r.rid == rid]
        assert added.server._compiled is first.server._compiled
        assert len(os.listdir(cache)) == n_entries, \
            "cold replica warmup recompiled instead of cache-hitting"
        # and after serving real traffic it STAYS zero
        rng = np.random.RandomState(11)
        for rows in (1, 2, 4):
            fleet.predict(_feed(rng, rows), timeout=30.0)
        st = fleet.stats()
        assert all(p['compiles_after_warmup'] == 0
                   for p in st['replicas'])
    finally:
        fleet.close()


# -- telemetry ---------------------------------------------------------
def test_fleet_metrics_labels_and_retirement(versions):
    fleet = _mk_fleet(versions)
    fid = fleet._fid
    try:
        rng = np.random.RandomState(12)
        fleet.predict(_feed(rng), timeout=30.0)
        text = observability.prometheus_text()
        assert ('paddle_tpu_fleet_requests_total{fleet="%s"} 1'
                % fid) in text
        assert ('paddle_tpu_fleet_replicas{fleet="%s",state="ready"} 2'
                % fid) in text
        # per-replica series carry replica AND version labels
        assert 'version="2"' in text and 'replica="r' in text
        # callback gauges read live state at scrape time
        snap = observability.snapshot()
        g = snap['paddle_tpu_fleet_replicas']['samples']
        ready = [s for s in g if s['labels'].get('fleet') == fid
                 and s['labels']['state'] == 'ready']
        assert ready and ready[0]['value'] == 2
    finally:
        fleet.close()
    text = observability.prometheus_text()
    assert ('fleet="%s"' % fid) not in text, \
        "closed fleet's series were not retired"


def test_callback_gauge_primitive():
    """Gauge.set_function: pulled at read time, exception falls back to
    the last pushed value, set_function(None) reverts to push mode."""
    from paddle_tpu.observability import MetricsRegistry
    reg = MetricsRegistry()
    g = reg.gauge('paddle_tpu_test_cb_gauge', 'x', ('k',))
    child = g.labels(k='a')
    child.set(7.0)
    live = {'v': 1.0}
    child.set_function(lambda: live['v'])
    assert child.value == 1.0
    live['v'] = 3.5
    assert child.value == 3.5

    def broken():
        raise RuntimeError("scrape-time failure")
    child.set_function(broken)
    assert child.value == 7.0  # falls back to the pushed value
    child.set_function(None)
    assert child.value == 7.0
    snap = reg.snapshot()
    assert snap['paddle_tpu_test_cb_gauge']['samples'][0]['value'] == 7.0


# -- HBM observability PR: resident-bytes gauges + budget precheck --------

def test_resident_bytes_gauges_and_shared_dedupe(versions):
    fleet = _mk_fleet(versions, replicas=2)
    try:
        st = fleet.stats()
        per = st['replicas']
        assert all(p['resident_bytes'] > 0 for p in per)
        # replicas of one version share ONE compiled servable: the
        # aggregate counts it once, not once per dispatch lane
        assert st['resident_bytes'] == per[0]['resident_bytes']
        assert st['resident_bytes_watermark'] >= st['resident_bytes']
        # per-replica gauge series exist, labeled fleet/replica/version
        fam = fleet._m._resident
        for rep in fleet._replicas:
            assert rep.m_resident.value == \
                rep.resident['total_bytes'] > 0
        # the aggregate callback gauge reads the deduped total live
        agg = fleet._m._g_resident.labels(fleet=fleet._fid)
        assert agg.value == st['resident_bytes']
    finally:
        fleet.close()


def test_deploy_overlap_raises_resident_watermark(versions):
    fleet = _mk_fleet(versions, replicas=2, version='1')
    try:
        v1 = fleet.stats()['resident_bytes']
        fleet.deploy(versions, version='2')
        st = fleet.stats()
        # at the rollout overlap both versions were live: the
        # watermark saw more than either steady state alone
        assert st['resident_bytes_watermark'] > st['resident_bytes']
        assert st['resident_bytes_watermark'] > v1
    finally:
        fleet.close()


def test_hbm_budget_precheck_is_warn_only(versions, caplog):
    import logging
    fleet = _mk_fleet(versions, replicas=1, version='1')
    try:
        before = fleet.stats()
        assert before['hbm_budget_precheck_failures'] == 0
        with caplog.at_level(logging.WARNING,
                             logger='paddle_tpu.inference.fleet'):
            vname = fleet.deploy(versions, version='2',
                                 hbm_budget_bytes=1)
        assert vname == '2'  # warn-only: the deploy went through
        st = fleet.stats()
        assert st['hbm_budget_precheck_failures'] == 1
        assert any('would exceed the HBM budget' in r.message
                   for r in caplog.records)
        # and the fleet still serves the new version
        rng = np.random.RandomState(1)
        out, = fleet.predict(_feed(rng), timeout=30.0)
        assert out.shape == (1, 4)
        # a roomy budget passes silently
        fleet.deploy(versions, version='1',
                     hbm_budget_bytes=1 << 40)
        assert fleet.stats()['hbm_budget_precheck_failures'] == 1
    finally:
        fleet.close()


def test_fleet_budget_defaults_to_peak_hbm_flag(versions, monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_PEAK_HBM_BYTES', '1')
    fleet = _mk_fleet(versions, replicas=1)  # ctor deploy prechecks
    try:
        st = fleet.stats()
        assert st['hbm_budget_bytes'] == 1
        assert st['hbm_budget_precheck_failures'] == 1
    finally:
        fleet.close()


def test_fleet_routing_span_carries_request_id(versions, monkeypatch,
                                               tmp_path):
    from paddle_tpu.observability import timeline
    monkeypatch.setenv('PADDLE_TPU_TRACE_DIR', str(tmp_path))
    timeline.reset()
    fleet = _mk_fleet(versions, replicas=2)
    try:
        rng = np.random.RandomState(2)
        out, = fleet.predict(_feed(rng), timeout=30.0)
        deadline = time.time() + 10.0
        disp = qw = None
        while time.time() < deadline and not (disp and qw):
            evs = timeline.ring().events()
            disp = [e for e in evs
                    if e['name'] == 'fleet.dispatch'] or None
            qw = [e for e in evs
                  if e['name'] == 'serving.queue_wait'] or None
            time.sleep(0.01)
        assert disp, 'fleet routing span missing'
        assert qw, 'replica queue-wait span missing'
        rid = disp[0]['args']['request_id']
        assert disp[0]['args']['replica'] in fleet.replica_ids
        assert disp[0]['args']['version'] == fleet.version
        # ONE id names the request across routing and replica spans
        assert any(e['args'].get('request_id') == rid for e in qw)
    finally:
        fleet.close()
        timeline.reset()

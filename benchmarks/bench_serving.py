"""Serving benchmark: latency + throughput of the saved StableHLO
ResNet-50 inference artifact — the capi deployment use case (reference
paddle/capi: load once, predict many).

Batch-1 latency is a per-call round trip; throughput chains calls
through a data dependency and syncs once.

The `dynamic` scenario exercises the BatchingInferenceServer on a
CTR-style many-field tower (the "millions of users" traffic shape):
closed-loop concurrency-8 clients vs sequential unbatched predict, and
Poisson open-loop arrivals at several offered loads, reporting p50/p99
latency, throughput, and mean batch occupancy next to the fixed-batch
lines.
"""
import json
import os
import sys
import tempfile
import threading
import time
from collections import deque

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from common import on_tpu  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.inference import serving
    from paddle_tpu.models import resnet

    tpu = on_tpu()
    if tpu:
        hw, depth, classes = 224, 50, 1000
        lat_calls, thr_chain = 30, 30
    else:  # CPU smoke: same path, tiny shapes
        hw, depth, classes = 32, 18, 10
        lat_calls, thr_chain = 3, 3

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        img, label, prediction, avg_cost, acc = resnet.build_imagenet(
            depth=depth, num_classes=classes, image_shape=(hw, hw, 3),
            dtype='bfloat16', layout='NHWC')
    place = fluid.TPUPlace(0) if tpu else fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(startup)

    rng = np.random.default_rng(0)
    results = []
    servers, xs = {}, {}
    for batch, mode in ((1, 'latency'), (8, 'latency'),
                        (8, 'throughput'), (64, 'throughput'),
                        (64, 'pipelined')):
        server = servers.get(batch)
        if server is None:
            path = os.path.join(tempfile.mkdtemp(),
                                'resnet_b%d.hlo' % batch)
            serving.export_inference(path, {'img': (batch, hw, hw, 3)},
                                     [prediction], executor=exe,
                                     main_program=main_prog)
            server = servers[batch] = serving.InferenceServer(path)
            xs[batch] = rng.normal(
                size=(batch, hw, hw, 3)).astype(np.float32)
            np.asarray(server.predict({'img': xs[batch]})[0])  # warm
        x = xs[batch]
        # pipelined mode re-uploads per call; cap it for big batches,
        # chained mode stages once
        thr_chain_b = thr_chain if (batch <= 8 or mode == 'throughput') \
            else min(thr_chain, 10)

        if mode == 'latency':
            times = []
            for _ in range(lat_calls):
                t0 = time.perf_counter()
                np.asarray(server.predict({'img': x})[0])  # full sync
                times.append(time.perf_counter() - t0)
            r = {"metric": "resnet%d_serving_latency_ms_b%d"
                           % (depth, batch),
                 "value": round(float(np.median(times)) * 1e3, 2),
                 "unit": "ms", "dtype": "bfloat16"}
        elif mode == 'throughput':
            # predict_stacked: K requests as one device scan, one sync —
            # the serve-path counterpart of Executor.run_steps.  The
            # stacked inputs stage onto the device ONCE and the upload
            # is timed separately: a production server overlaps staging
            # with compute (double buffering).
            stacked_np = {'img': np.stack([x] * thr_chain_b)}
            t0 = time.perf_counter()
            stacked = {kk: jax.device_put(v, place.jax_device())
                       for kk, v in stacked_np.items()}
            jax.block_until_ready(stacked['img'])
            t_upload = time.perf_counter() - t0
            ys = server.predict_stacked(stacked, thr_chain_b)  # compile
            [np.asarray(y) for y in ys]
            samples, totals = [], []
            for _ in range(3):
                t0 = time.perf_counter()
                ys = server.predict_stacked(stacked, thr_chain_b)
                [np.asarray(y) for y in ys]
                totals.append(time.perf_counter() - t0)
                samples.append(batch * thr_chain_b / totals[-1])
            # split the wall into device vs dispatch: the chained call
            # pays ONE dispatch for K batches, so per-batch device time
            # is the chained wall / K; a single predict() pays the full
            # round trip, and the difference is dispatch cost.  Median
            # sample, so the breakdown describes the same run as the
            # reported value.
            t_chain_batch = float(np.median(totals)) / thr_chain_b * 1e3
            # single call on a DEVICE-resident batch: its wall is
            # RTT + device, so the difference below is pure per-call
            # dispatch overhead, not upload (stage_mb_s carries that)
            xd = jax.device_put(x, place.jax_device())
            np.asarray(server.predict({'img': xd})[0])  # warm path
            t0 = time.perf_counter()
            np.asarray(server.predict({'img': xd})[0])
            t_single = (time.perf_counter() - t0) * 1e3
            r = {"metric": "resnet%d_serving_throughput_img_s_b%d"
                           % (depth, batch),
                 "value": round(float(np.median(samples)), 2),
                 "samples": [round(s, 1) for s in samples],
                 "unit": "img/s", "dtype": "bfloat16",
                 "device_ms_per_batch": round(t_chain_batch, 2),
                 "dispatch_ms_per_call": round(
                     max(t_single - t_chain_batch, 0.0), 2),
                 "stage_mb_s": round(
                     stacked_np['img'].nbytes / 1e6 / t_upload, 1),
                 "chain": thr_chain_b}
        else:
            # pipelined async dispatch: K independent predict_async
            # calls in flight, one sync at the end — no stacking, no
            # special chain program, just not blocking per call
            futures = [server.predict_async({'img': x})
                       for _ in range(thr_chain_b)]
            [np.asarray(o) for o in futures[-1]]
            samples = []
            for _ in range(3):
                t0 = time.perf_counter()
                futures = [server.predict_async({'img': x})
                           for _ in range(thr_chain_b)]
                for outs in futures:
                    for o in outs:
                        np.asarray(o)
                samples.append(batch * thr_chain_b /
                               (time.perf_counter() - t0))
            # split the pipelined wall like the chained line above so a
            # predict_async regression shows where it is: device compute
            # from a short stacked chain on a
            # device-resident batch, upload from one timed device_put,
            # dispatch = residual wall per call
            dev_chain = 10
            stacked = {'img': jax.device_put(
                np.stack([x] * dev_chain), place.jax_device())}
            ys = server.predict_stacked(stacked, dev_chain)  # compile
            [np.asarray(y) for y in ys]
            t0 = time.perf_counter()
            ys = server.predict_stacked(stacked, dev_chain)
            [np.asarray(y) for y in ys]
            dev_ms = (time.perf_counter() - t0) / dev_chain * 1e3
            t0 = time.perf_counter()
            np.asarray(jax.device_put(x, place.jax_device())[0, 0, 0])
            up_ms = (time.perf_counter() - t0) * 1e3
            wall_ms = batch / float(np.median(samples)) * 1e3
            r = {"metric": "resnet%d_serving_pipelined_img_s_b%d"
                           % (depth, batch),
                 "value": round(float(np.median(samples)), 2),
                 "samples": [round(s, 1) for s in samples],
                 "unit": "img/s", "dtype": "bfloat16",
                 "device_ms_per_batch": round(dev_ms, 2),
                 "stage_mb_s": round(x.nbytes / 1e6 / max(up_ms / 1e3,
                                                          1e-9), 1),
                 "dispatch_ms_per_call": round(
                     max(wall_ms - dev_ms - up_ms, 0.0), 2)}
        print(json.dumps(r))
        results.append(r)
    results.extend(dynamic_scenario(tpu))
    results.extend(amp_scenario(tpu))
    results.extend(fleet_scenario(tpu))
    results.extend(multitenant_scenario(tpu))
    results.extend(online_scenario(tpu))
    results.extend(decode_scenario(tpu))
    results.extend(decode_prefix_scenario(tpu))
    results.extend(decode_chunked_scenario(tpu))
    # attach the observability snapshot so BENCH_*.json runs carry the
    # queue/occupancy/latency telemetry behind the headline numbers
    # (empty when PADDLE_TPU_METRICS_ENABLED=0 — servers then report to
    # private registries)
    from paddle_tpu import observability
    snap = {"metric": "serving_metrics_snapshot",
            "snapshot": observability.snapshot()}
    print(json.dumps(snap))
    results.append(snap)
    return results


def _build_ctr_tower(n_sparse, seed=17):
    """A CTR-style tower (sparse id embeddings + dense stats -> small
    MLP): per-request compute is tiny, so serving cost is dominated by
    per-call dispatch of the many-field feed — exactly what dynamic
    batching amortizes."""
    import paddle_tpu as fluid

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = seed
    with fluid.program_guard(main_prog, startup):
        embs = []
        for i in range(n_sparse):
            c = fluid.layers.data(name='C%d' % i, shape=[1],
                                  dtype='int64')
            embs.append(fluid.layers.embedding(input=c,
                                               size=[10000, 16]))
        dense = fluid.layers.data(name='I', shape=[13],
                                  dtype='float32')
        feat = fluid.layers.concat(embs + [dense], axis=1)
        h = fluid.layers.fc(input=feat, size=256, act='relu')
        h = fluid.layers.fc(input=h, size=128, act='relu')
        pred = fluid.layers.fc(input=h, size=1, act='sigmoid')
    return main_prog, startup, pred


def amp_scenario(tpu):
    """Inference-side AMP: the CTR tower exported bucketed at f32 vs
    PADDLE_TPU_AMP=bf16 (export_bucketed amp='bf16' — the artifact
    embeds the AMP-rewritten program: fc towers in bf16, weights cast
    once at the graph edge), served at one bucket size side by side."""
    import paddle_tpu as fluid
    from paddle_tpu.inference import export_bucketed
    from paddle_tpu.inference import serving

    n_sparse = 26
    bucket = 8
    n_chain = 30 if tpu else 5
    main_prog, startup, pred = _build_ctr_tower(n_sparse)
    place = fluid.TPUPlace(0) if tpu else fluid.CPUPlace()
    exe = fluid.Executor(place)
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    specs = {('C%d' % i): (1,) for i in range(n_sparse)}
    specs['I'] = (13,)
    rng = np.random.default_rng(0)
    feed = {('C%d' % i):
            rng.integers(0, 10000, size=(bucket, 1)).astype('int32')
            for i in range(n_sparse)}
    feed['I'] = rng.normal(size=(bucket, 13)).astype('float32')

    results = []
    for amp_label, amp_mode in (('off', '0'), ('bf16', 'bf16')):
        paths = export_bucketed(
            tempfile.mkdtemp(), specs, [pred], executor=exe,
            main_program=main_prog, scope=scope, max_batch=bucket,
            amp=amp_mode)
        srv = serving.InferenceServer(paths[bucket])
        np.asarray(srv.predict(feed)[0])  # compile + warm
        samples = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n_chain):
                np.asarray(srv.predict(feed)[0])
            samples.append(bucket * n_chain /
                           (time.perf_counter() - t0))
        r = {"metric": "ctr_serving_bucketed_preds_per_sec",
             "value": round(float(np.median(samples)), 2),
             "samples": [round(s, 1) for s in samples],
             "amp": amp_label,
             "note": "b%d export_bucketed CTR tower" % bucket}
        print(json.dumps(r))
        results.append(r)
    return results


def fleet_scenario(tpu):
    """The serving-fleet rollout drill: Poisson open-loop traffic
    against a 3-replica ServingFleet while the fleet goes through a
    full operational sequence mid-load —

      steady0 -> kill (drain-remove one replica) -> add (a cold replica
      joins after AOT warmup) -> swap (hot-deploy a new model version,
      old set drains) -> steady1

    — reporting p50/p99 latency per phase, the p99 ratio of every phase
    against the steady baseline, and the failed-request count (the
    acceptance bar is ZERO: every operation either drains queued work
    or retries dispatches on healthy replicas, so clients only ever see
    results).

    The production cold-start story is compile-cache-backed: replica
    warmup (fleet start, add_replica, deploy) is disk reads, not XLA
    compiles, once the persistent compile cache (paddle_tpu/
    compile_cache.py: JAX_COMPILATION_CACHE_DIR, else
    <checkout>/.jax_cache) holds both versions."""
    import paddle_tpu as fluid
    from paddle_tpu.inference import (BatchingInferenceServer,
                                      ServingFleet, export_bucketed)
    from paddle_tpu import io as pio

    n_sparse = 26
    max_batch = 16
    per_phase = 320 if tpu else 240
    replicas = 3
    base_dir = tempfile.mkdtemp()

    specs = {('C%d' % i): (1,) for i in range(n_sparse)}
    specs['I'] = (13,)
    place = fluid.TPUPlace(0) if tpu else fluid.CPUPlace()
    for ver, seed in (('1', 17), ('2', 23)):
        main_prog, startup, pred = _build_ctr_tower(n_sparse, seed=seed)
        exe = fluid.Executor(place)
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        export_bucketed(os.path.join(base_dir, ver), specs, [pred],
                        executor=exe, main_program=main_prog,
                        scope=scope, max_batch=max_batch)
        # one warmup pass per version populates the persistent cache
        BatchingInferenceServer(
            pio.bucket_artifacts(os.path.join(base_dir, ver))).close()

    rng = np.random.default_rng(0)

    def mk():
        f = {('C%d' % i):
             rng.integers(0, 10000, size=(1, 1)).astype('int32')
             for i in range(n_sparse)}
        f['I'] = rng.normal(size=(1, 13)).astype('float32')
        return f

    t0 = time.perf_counter()
    fleet = ServingFleet(os.path.join(base_dir, '1'),
                         replicas=replicas, max_wait_ms=10.0,
                         linger_ms=0.3, health_interval_ms=100.0)
    t_warm = time.perf_counter() - t0

    f1 = mk()
    for _ in range(32):
        fleet.submit(f1)
    fleet.predict(f1)  # drain + warm every replica's serving loop

    # offered load: the fleet's sequential (latency-bound) predict rate
    # — pressure enough that batching and routing matter, while the
    # open loop stays stable on the smoke box
    t0 = time.perf_counter()
    for _ in range(30):
        fleet.predict(f1)
    lam = 30 / (time.perf_counter() - t0)

    # each phase submits Poisson-paced requests for AT LEAST per_phase
    # requests AND the full window of its fleet action (kill/add/swap
    # run in a worker thread; the submission loop never pauses), so
    # the latency sample actually covers the operation
    phases = [
        ('steady0', None),
        ('kill', lambda: fleet.remove_replica()),
        ('add', lambda: fleet.add_replica()),
        ('swap', lambda: fleet.deploy(os.path.join(base_dir, '2'))),
        ('steady1', None),
    ]
    sub_at, done_at, errors = [], [], []
    phase_of = []
    action_wall = {}
    futs = []

    def make_cb(i):
        def cb(fut):
            done_at[i] = time.perf_counter()
            if fut.exception() is not None:
                errors.append((i, fut.exception()))
        return cb

    def run_action(name, fn):
        t0 = time.perf_counter()
        fn()
        action_wall[name] = time.perf_counter() - t0

    cap_per_phase = per_phase * 30  # safety bound if an action stalls
    for phase, action in phases:
        th = None
        if action is not None:
            th = threading.Thread(target=run_action,
                                  args=(phase, action))
            th.start()
        count = 0
        while count < per_phase or (th is not None and th.is_alive()):
            if count >= cap_per_phase:
                break
            time.sleep(float(rng.exponential(1.0 / lam)))
            i = len(futs)
            sub_at.append(time.perf_counter())
            done_at.append(None)
            phase_of.append(phase)
            fut = fleet.submit(mk())
            fut.add_done_callback(make_cb(i))
            futs.append(fut)
            count += 1
        if th is not None:
            th.join(300.0)
    for fut in futs:
        try:
            fut.result(timeout=120.0)
        except Exception:
            pass  # already recorded via the callback
    deadline = time.perf_counter() + 5.0
    while any(d is None for d in done_at) and \
            time.perf_counter() < deadline:
        time.sleep(0.001)

    results = []
    p99_by_phase = {}
    for phase, _action in phases:
        lat = np.array([d - s for d, s, p in
                        zip(done_at, sub_at, phase_of)
                        if p == phase and d is not None]) * 1e3
        p99_by_phase[phase] = float(np.percentile(lat, 99))
        r = {"metric": "ctr_fleet_poisson_%s" % phase,
             "value": round(float(np.percentile(lat, 99)), 2),
             "unit": "ms p99",
             "p50_latency_ms": round(float(np.percentile(lat, 50)), 2),
             "p95_latency_ms": round(float(np.percentile(lat, 95)), 2),
             "offered_req_s": round(lam, 1),
             "n_requests": int(lat.size)}
        if phase in action_wall:
            r["action_wall_s"] = round(action_wall[phase], 2)
        print(json.dumps(r))
        results.append(r)
    st = fleet.stats()
    steady = p99_by_phase['steady0']
    summary = {
        "metric": "ctr_fleet_rollout_summary",
        "value": len(errors), "unit": "failed requests",
        "replicas": replicas, "warmup_s": round(t_warm, 1),
        "offered_req_s": round(lam, 1),
        "final_version": st['version'],
        "deploys": st['deploys'],
        "dispatch_retries": st['retries'],
        "compiles_after_warmup": sum(
            p['compiles_after_warmup'] for p in st['replicas']),
        "p99_steady_ms": round(steady, 2),
        "p99_worst_over_steady": round(
            max(p99_by_phase.values()) / max(steady, 1e-9), 2),
        "queue_wait_p99_ms": round(max(
            p['server']['queue_wait_p99_ms']
            for p in st['replicas']), 2),
        "compute_p99_ms": round(max(
            p['server']['compute_p99_ms']
            for p in st['replicas']), 2),
    }
    if not tpu:
        summary["note"] = (
            "2-core CPU smoke box: the swap-phase p99 tail is the new "
            "version's ~3s of (cache-hit) compile loads contending "
            "with the only two serving cores; kill/add are invisible "
            "(shared servable, zero builds).  On a TPU host the "
            "compile threads don't contend with serving.")
    print(json.dumps(summary))
    results.append(summary)
    fleet.close()
    return results


def multitenant_scenario(tpu):
    """The multi-tenant serving drill (ISSUE 17): 3 CTR models under
    one fleet — tenants gold/silver/bronze with SLO classes to match —
    taking skewed Poisson traffic (~70/25/5) while the fleet goes
    through the tenancy operational sequence mid-load:

      steady0 -> evict (an enforcing over-budget deploy LRU-evicts the
      cold bronze tenant's buckets; a second, unsatisfiable deploy is
      REJECTED before any build cost) -> coldjoin (a simulated fresh
      process — cleared in-process jax caches — builds a whole new
      fleet off the warm AOT executable cache, zero compiles) ->
      steady1 (bronze traffic resumes, re-warming its evicted buckets
      through the counted compile path)

    Reports per-tenant p50/p99 (the acceptance bar: p99s ordered by
    SLO class — gold's deadline flush is half the base max_wait,
    bronze's 4x), the eviction/admission counters, and the dropped-
    request count (bar: ZERO across eviction + cold join)."""
    from paddle_tpu.compile_cache import compile_cache_dir
    aot_was = os.environ.get('PADDLE_TPU_AOT_CACHE_DIR')
    if not aot_was:
        # the serialized executables live beside the compile cache
        os.environ['PADDLE_TPU_AOT_CACHE_DIR'] = compile_cache_dir()
    try:
        return _multitenant_scenario_impl(tpu)
    finally:
        if aot_was is None:
            os.environ.pop('PADDLE_TPU_AOT_CACHE_DIR', None)
        elif aot_was == '':
            os.environ['PADDLE_TPU_AOT_CACHE_DIR'] = ''


def _multitenant_scenario_impl(tpu):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.inference import (AdmissionError, AotCache,
                                      ServingFleet, export_bucketed)
    from paddle_tpu import io as pio

    n_sparse = 26
    max_batch = 16
    per_phase = 240 if tpu else 160
    base_dir = tempfile.mkdtemp()

    specs = {('C%d' % i): (1,) for i in range(n_sparse)}
    specs['I'] = (13,)
    place = fluid.TPUPlace(0) if tpu else fluid.CPUPlace()
    tenants = [('gold', 'gold', 'a', 17), ('silver', 'silver', 'b', 23),
               ('bronze', 'bronze', 'c', 31)]
    for _t, _slo, model, seed in tenants:
        main_prog, startup, pred = _build_ctr_tower(n_sparse, seed=seed)
        exe = fluid.Executor(place)
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        export_bucketed(os.path.join(base_dir, model), specs, [pred],
                        executor=exe, main_program=main_prog,
                        scope=scope, max_batch=max_batch)

    rng = np.random.default_rng(0)

    def mk():
        f = {('C%d' % i):
             rng.integers(0, 10000, size=(1, 1)).astype('int32')
             for i in range(n_sparse)}
        f['I'] = rng.normal(size=(1, 13)).astype('float32')
        return f

    t0 = time.perf_counter()
    fleet = ServingFleet(os.path.join(base_dir, 'a'), replicas=1,
                         max_wait_ms=10.0, linger_ms=0.3,
                         health_interval_ms=100.0,
                         tenant='gold', slo_class='gold',
                         hbm_admission='enforce')
    for tname, slo, model, _seed in tenants[1:]:
        fleet.deploy(os.path.join(base_dir, model), replicas=1,
                     tenant=tname, slo_class=slo)
    t_warm = time.perf_counter() - t0

    for tname, _slo, _m, _s in tenants:
        fleet.predict(mk(), tenant=tname)  # warm every serving loop

    t0 = time.perf_counter()
    for _ in range(20):
        fleet.predict(mk(), tenant='gold')
    # The SLO deadline flush (max_wait) only governs a request's wait
    # while its replica has a batch in flight: target busy-but-stable
    # load, not overload (where queueing drowns the per-class
    # deadlines) and shed to a trickle while the operational actions
    # hold the cores, as a real admission front-end would.
    lam = min(0.45 * 20 / (time.perf_counter() - t0), 400.0)
    lam_action = lam * 0.25

    sub_at, done_at, errors = [], [], []
    tenant_of, phase_of = [], []
    futs = []
    action_wall = {}
    action_out = {}

    def make_cb(i):
        def cb(fut):
            done_at[i] = time.perf_counter()
            if fut.exception() is not None:
                errors.append((i, fut.exception()))
        return cb

    def pick_tenant(skew):
        r = rng.random()
        acc = 0.0
        for name, p in skew:
            acc += p
            if r < acc:
                return name
        return skew[-1][0]

    def do_evict():
        """Mid-load: LRU-evict the (paused, coldest) bronze tenant to
        fit a new servable, then prove an unsatisfiable deploy is
        rejected with no build cost."""
        st = fleet.stats()
        bronze_rep, = [r for r in fleet._replicas
                       if r.tenant == 'bronze']
        bronze_bytes = \
            bronze_rep.server.resident_bytes()['total_bytes']
        incoming = sum(
            os.path.getsize(p) for p in
            pio.bucket_artifacts(os.path.join(base_dir, 'a')).values())
        budget = (st['resident_bytes'] + incoming
                  - bronze_bytes + 1024)
        fleet.deploy(os.path.join(base_dir, 'a'), replicas=1,
                     tenant='probe', slo_class='silver',
                     hbm_budget_bytes=budget)
        t0 = time.perf_counter()
        try:
            fleet.deploy(os.path.join(base_dir, 'b'), replicas=1,
                         tenant='rejected', hbm_budget_bytes=1)
            action_out['rejected'] = False
        except AdmissionError:
            action_out['rejected'] = True
        action_out['reject_wall_s'] = time.perf_counter() - t0

    def do_coldjoin():
        """A simulated fresh process joins mid-load: in-process jax
        caches cleared, fleet built entirely off the warm AOT disk
        cache — serving-ready with zero compiles."""
        jax.clear_caches()
        f2 = ServingFleet(os.path.join(base_dir, 'a'), replicas=1,
                          max_wait_ms=10.0, linger_ms=0.3,
                          health_interval_ms=0)
        st2 = f2.stats()
        action_out['coldjoin_compiles'] = sum(
            p['compiles'] + p['compiles_after_warmup']
            for p in st2['replicas'])
        f2.predict(mk())
        action_out['coldjoin_served'] = True
        f2.close()

    # bronze pauses after steady0 so it is unambiguously the coldest
    # tenant when the evict-phase deploy needs room
    skew_full = [('gold', 0.65), ('silver', 0.25), ('bronze', 0.10)]
    skew_nobronze = [('gold', 0.75), ('silver', 0.25)]
    phases = [
        ('steady0', None, skew_full),
        ('evict', do_evict, skew_nobronze),
        ('coldjoin', do_coldjoin, skew_nobronze),
        ('steady1', None, skew_full),
    ]

    def run_action(name, fn):
        t0 = time.perf_counter()
        fn()
        action_wall[name] = time.perf_counter() - t0

    cap_per_phase = per_phase * 30
    for phase, action, skew in phases:
        th = None
        if action is not None:
            th = threading.Thread(target=run_action,
                                  args=(phase, action))
            th.start()
        count = 0
        rate = lam if action is None else lam_action
        while count < per_phase or (th is not None and th.is_alive()):
            if count >= cap_per_phase:
                break
            time.sleep(float(rng.exponential(1.0 / rate)))
            i = len(futs)
            tname = pick_tenant(skew)
            sub_at.append(time.perf_counter())
            done_at.append(None)
            tenant_of.append(tname)
            phase_of.append(phase)
            fut = fleet.submit(mk(), tenant=tname)
            fut.add_done_callback(make_cb(i))
            futs.append(fut)
            count += 1
        if th is not None:
            th.join(300.0)
    for fut in futs:
        try:
            fut.result(timeout=120.0)
        except Exception:
            pass  # already recorded via the callback
    deadline = time.perf_counter() + 5.0
    while any(d is None for d in done_at) and \
            time.perf_counter() < deadline:
        time.sleep(0.001)

    results = []
    p99_by_tenant = {}
    for tname, _slo, _m, _s in tenants:
        # per-tenant SLO rows over the steady phases only: the action
        # phases measure the operational walls, not class latency
        lat = np.array([d - s for d, s, t, ph in
                        zip(done_at, sub_at, tenant_of, phase_of)
                        if t == tname and d is not None
                        and ph.startswith('steady')]) * 1e3
        p99_by_tenant[tname] = float(np.percentile(lat, 99))
        r = {"metric": "ctr_multitenant_%s" % tname,
             "value": round(float(np.percentile(lat, 99)), 2),
             "unit": "ms p99 (steady phases)",
             "slo_class": tname,
             "p50_latency_ms": round(float(np.percentile(lat, 50)), 2),
             "p95_latency_ms": round(float(np.percentile(lat, 95)), 2),
             "n_requests": int(lat.size)}
        print(json.dumps(r))
        results.append(r)
    st = fleet.stats()
    aot = AotCache.stats()
    summary = {
        "metric": "ctr_multitenant_summary",
        "value": len(errors), "unit": "dropped requests",
        "offered_req_s": round(lam, 1),
        "warmup_s": round(t_warm, 1),
        "tenants": sorted(fleet.tenants()),
        "p99_ordered_by_slo": bool(
            p99_by_tenant['gold'] <= p99_by_tenant['silver']
            <= p99_by_tenant['bronze']),
        "evictions": st['evictions'],
        "evicted_tenant_buckets":
            st['tenants']['bronze']['evicted_buckets'],
        "admission_rejections": st['admission_rejections'],
        "overbudget_deploy_rejected": action_out.get('rejected'),
        "reject_wall_s": round(
            action_out.get('reject_wall_s', 0.0), 3),
        "coldjoin_compiles": action_out.get('coldjoin_compiles'),
        "aot_hits": aot['hits'], "aot_stores": aot['stores'],
        "rewarm_compiles_after_warmup": sum(
            p['compiles_after_warmup'] for p in st['replicas']),
        "action_wall_s": {k: round(v, 2)
                          for k, v in action_wall.items()},
    }
    if not tpu:
        summary["note"] = (
            "2-core CPU smoke box: three tenant groups contend for "
            "two cores, so absolute p99s are queueing-dominated; the "
            "SLO ordering comes from the per-class deadline flush "
            "(gold 5ms / silver 10ms / bronze 40ms max_wait).")
    print(json.dumps(summary))
    results.append(summary)
    fleet.close()
    return results


def online_scenario(tpu):
    """The continuous-learning drill (ROADMAP item 4): Poisson traffic
    against a fleet while the online pipeline retrains it in the SAME
    process —

      steady -> concept drift (label coupling rotates mid-run; the
      serving model goes stale and background fine-tune rounds win it
      back through the eval gate) -> one injected bad round (a
      poisoned, label-flipped log segment force-promoted past the
      gate, simulating a corrupted upstream joiner) -> automatic
      rollback on the live-AUC regression -> recovery

    — recording per-phase serving p99, the live-AUC-over-time and
    model-age series, the freshness-SLO violation count, and the
    failed-request count (the bar is ZERO: deploys drain, rollbacks
    drain, training steals no request).

    On the CPU smoke box the mid-phase p99 tail includes each promote's
    export + warmup compiles contending with the two serving cores;
    on a TPU host the compile threads don't contend with serving.
    """
    import paddle_tpu as fluid
    from paddle_tpu.core.program import reset_unique_name_guard
    from paddle_tpu.inference import ServingFleet, export_bucketed
    from paddle_tpu.online import (ClickstreamTail, ClickstreamWriter,
                                   OnlineController, OnlineTrainer)
    from paddle_tpu import io as pio

    n_dense, n_slots, id_space = 13, 4, 5000
    batch, steps, holdout = 16, 6, 2       # 96 train + 32 gate rows
    poison_steps = 24                      # the bad round trains 4x
    max_batch, replicas = 4, 2
    live_window = 96
    slo_s = 8.0
    base = tempfile.mkdtemp(prefix='paddle_tpu_online_')
    log = os.path.join(base, 'click.log')

    with reset_unique_name_guard():
        main_prog, startup = fluid.Program(), fluid.Program()
        main_prog.random_seed = startup.random_seed = 11
        with fluid.program_guard(main_prog, startup):
            dense = fluid.layers.data(name='dense', shape=[n_dense],
                                      dtype='float32')
            slots = [fluid.layers.data(name='C%d' % i, shape=[1],
                                       dtype='int64')
                     for i in range(n_slots)]
            label = fluid.layers.data(name='label', shape=[1],
                                      dtype='int64')
            embs = [fluid.layers.embedding(input=s,
                                           size=[id_space, 8])
                    for s in slots]
            feat = fluid.layers.concat(embs + [dense], axis=1)
            h = fluid.layers.fc(input=feat, size=32, act='relu')
            predict = fluid.layers.fc(input=h, size=2, act='softmax')
            cost = fluid.layers.cross_entropy(input=predict,
                                              label=label)
            loss = fluid.layers.mean(x=cost)
            fluid.optimizer.AdamOptimizer(
                learning_rate=0.01).minimize(loss)
        infer_prog = pio.get_inference_program([predict], main_prog)
    place = fluid.TPUPlace(0) if tpu else fluid.CPUPlace()
    exe = fluid.Executor(place)
    scope = fluid.Scope()
    exe.run(startup, scope=scope)

    def batch_fn(rows):
        f = {'dense': np.stack([r[0] for r in rows]),
             'label': np.array([[r[2]] for r in rows],
                               dtype=np.int64)}
        for i in range(n_slots):
            f['C%d' % i] = np.array([[r[1][i]] for r in rows],
                                    dtype=np.int64)
        return f

    def request_feed(row):
        f = {'dense': row[0][None, :]}
        for i in range(n_slots):
            f['C%d' % i] = np.array([[row[1][i]]], dtype=np.int64)
        return f

    writer = ClickstreamWriter(log, n_dense=n_dense, n_slots=n_slots,
                               id_space=id_space, seed=0)
    world = {'drift': 0.0}            # shared by log AND traffic
    writer.append(batch * (steps + holdout) * 4)  # pretrain backlog
    tail = ClickstreamTail(log)
    trainer = OnlineTrainer(
        exe, main_prog, tail, batch_fn, batch_size=batch,
        checkpoint_dir=os.path.join(base, 'ckpt'),
        steps_per_round=steps, holdout_batches=holdout,
        fetch_list=[loss], scope=scope)
    for _ in range(4):                # pretrain off the backlog
        trainer.run_round(max_wait_s=5.0)

    specs = {'dense': (n_dense,)}
    specs.update({('C%d' % i): (1,) for i in range(n_slots)})
    export_base = os.path.join(base, 'versions')

    def export_fn(vdir):
        export_bucketed(vdir, specs, [predict], executor=exe,
                        main_program=main_prog, scope=scope,
                        max_batch=max_batch)

    os.makedirs(export_base)
    export_fn(os.path.join(export_base, '1'))
    t0_fleet = time.perf_counter()
    fleet = ServingFleet(export_base, replicas=replicas,
                         max_wait_ms=10.0, linger_ms=0.3,
                         health_interval_ms=100.0)
    warmup_s = time.perf_counter() - t0_fleet

    def eval_fn(rows):
        feed = batch_fn(rows)
        feed.pop('label')
        out = exe.run(infer_prog, feed=feed, fetch_list=[predict],
                      scope=scope)[0]
        return np.asarray(out)[:, 1], np.array([r[2] for r in rows])

    def serving_eval_fn(rows):
        futs = [fleet.submit(request_feed(r)) for r in rows]
        scores = [float(np.asarray(f.result(timeout=60.0)[0])[0, 1])
                  for f in futs]
        return np.array(scores), np.array([r[2] for r in rows])

    ctl = OnlineController(
        trainer, fleet, export_base, export_fn, eval_fn,
        serving_eval_fn=serving_eval_fn, live_window=live_window,
        freshness_slo_s=slo_s, auc_delta=0.05)

    # offered load: a fraction of the sequential predict rate, like
    # fleet_scenario — enough pressure that batching matters, stable
    # on the smoke box while compiles contend
    probe = request_feed(writer.make_row())
    for _ in range(16):
        fleet.submit(probe)
    fleet.predict(probe)
    t0 = time.perf_counter()
    for _ in range(30):
        fleet.predict(probe)
    lam = 0.7 * 30 / (time.perf_counter() - t0)

    # background feedback traffic: Poisson arrivals scored by the
    # fleet; each outcome (score, true label) feeds the live monitor
    lat, errors = [], []            # (t_done, phase, latency_s)
    phase = ['steady']
    stop = threading.Event()
    pause_writer = threading.Event()
    rng = np.random.default_rng(1)

    def traffic():
        while not stop.is_set():
            time.sleep(float(rng.exponential(1.0 / lam)))
            row = writer.make_row(world['drift'])
            t_sub = time.perf_counter()
            ph = phase[0]
            try:
                fut = fleet.submit(request_feed(row))
            except Exception as e:
                errors.append(e)
                continue

            def done(f, t_sub=t_sub, ph=ph, y=row[2]):
                t_done = time.perf_counter()
                if f.exception() is not None:
                    errors.append(f.exception())
                    return
                s = float(np.asarray(f.result()[0])[0, 1])
                lat.append((t_done, ph, t_done - t_sub))
                ctl.record_live([s], [y])
            fut.add_done_callback(done)

    def feed_log():
        # ~160 rows/s: roughly the loop's consumption rate, so the
        # trainer stays near the tail (run_rounds also drops any
        # backlog before each round — freshness first)
        while not stop.is_set():
            if not pause_writer.is_set():
                writer.append(16, drift=world['drift'])
            time.sleep(0.1)

    def p99_ms(ph=None, window_s=None):
        now = time.perf_counter()
        xs = [l * 1e3 for t, p, l in lat
              if (ph is None or p == ph)
              and (window_s is None or now - t <= window_s)]
        return float(np.percentile(xs, 99)) if len(xs) >= 20 else None

    series, round_log = [], []

    def sample(tag=''):
        st = ctl.stats()
        series.append({
            't': round(time.perf_counter() - t_start, 2),
            'phase': phase[0], 'tag': tag,
            'version': st['version'],
            'live_auc': None if st['live_auc'] is None
            else round(st['live_auc'], 4),
            'model_age_s': round(st['model_age_s'], 2),
            'in_violation': st['in_violation'],
            'p99_ms_30s': None if p99_ms(window_s=30.0) is None
            else round(p99_ms(window_s=30.0), 2)})

    def run_rounds(n, force=False):
        for _ in range(n):
            # freshness first: a loop that fell behind trains on the
            # newest window, not the stale backlog (skipped rows are
            # accounted exactly like gate-rejected ones)
            tail.skip_to_latest(keep_bytes=64_000)
            # let the live window fill with the CURRENT version's
            # outcomes so check() judges it, not its predecessor
            time.sleep(0.3)
            sample('pre')  # the SERVING model's live AUC, pre-swap
            rep = ctl.run_round(max_wait_s=30.0, force_promote=force)
            gate = rep.get('gate') or {}
            round_log.append({
                'phase': phase[0], 'outcome': rep['outcome'],
                'step': rep['step'],
                'gate_auc': None if 'auc' not in gate
                else round(gate['auc'], 4),
                'serving_auc': None if gate.get('serving_auc') is None
                else round(gate['serving_auc'], 4),
                'version': rep.get('version'),
                'round_s': round(rep['round_s'], 2)})
            ctl.check(p99_ms=p99_ms(window_s=30.0))
            sample('round')

    threads = [threading.Thread(target=traffic, daemon=True),
               threading.Thread(target=feed_log, daemon=True)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    results = []
    try:
        # -- steady: the loop promotes fresh models under load -------
        run_rounds(2)

        # -- drift: the label coupling rotates; the serving model is
        # now stale and retraining wins it back through the gate -----
        phase[0] = 'drift'
        world['drift'] = 0.45
        run_rounds(4)

        # -- poison: a corrupted upstream segment (labels flipped),
        # force-promoted past the gate — the injected bad round ------
        phase[0] = 'poison'
        pause_writer.set()
        tail.skip_to_latest()  # the poisoned segment is what's next
        trainer.steps_per_round = poison_steps  # one big bad round
        writer.append(batch * (poison_steps + holdout),
                      drift=world['drift'], flip_labels=True)
        run_rounds(1, force=True)
        trainer.steps_per_round = steps
        pause_writer.clear()
        # the live window fills with the bad model's outcomes; the
        # watchdog rolls back automatically
        deadline = time.perf_counter() + 60.0
        fired = None
        while fired is None and time.perf_counter() < deadline:
            time.sleep(0.3)
            fired = ctl.check(p99_ms=p99_ms(window_s=30.0))
        sample('rollback' if fired else 'rollback_timeout')

        # -- recovery: clean rounds promote again --------------------
        phase[0] = 'recovery'
        run_rounds(2)

        # -- stall: an upstream log outage — no fresh rows, so no
        # promotes, and the serving model ages past the freshness SLO
        # (the counted, alertable violation window); the next promote
        # after the log recovers clears it ---------------------------
        phase[0] = 'stall'
        pause_writer.set()
        t_stall = time.perf_counter()
        while time.perf_counter() - t_stall < slo_s * 1.3:
            time.sleep(0.5)
            ctl.check(p99_ms=p99_ms(window_s=30.0))
        sample('stalled')
        pause_writer.clear()
        run_rounds(1)
    finally:
        stop.set()
        for t in threads:
            t.join(10.0)

    st = ctl.stats()
    fst = fleet.stats()
    p99_steady = p99_ms('steady')
    per_phase = {ph: p99_ms(ph) for ph in
                 ('steady', 'drift', 'poison', 'recovery', 'stall')}
    summary = {
        'metric': 'ctr_online_loop_summary',
        'value': len(errors), 'unit': 'failed requests',
        'offered_req_s': round(lam, 1),
        'replicas': replicas, 'fleet_warmup_s': round(warmup_s, 1),
        'rounds': [r for r in round_log],
        'rounds_promoted': sum(1 for r in round_log
                               if r['outcome'] == 'promoted'),
        'rounds_gate_failed': sum(1 for r in round_log
                                  if r['outcome'] == 'gate_failed'),
        'auto_rollback_reason': st['last_rollback_reason'],
        'rollbacks_by_reason': fst['rollbacks_by_reason'],
        'freshness_slo_s': slo_s,
        'slo_violations': st['slo_violations'],
        'final_version': st['version'],
        'final_live_auc': None if st['live_auc'] is None
        else round(st['live_auc'], 4),
        'p99_ms_by_phase': {k: (None if v is None else round(v, 2))
                            for k, v in per_phase.items()},
        'p99_worst_over_steady': None if not p99_steady else round(
            max(v for v in per_phase.values() if v is not None)
            / p99_steady, 2),
        'requests': fst['requests'], 'failed': fst['failed'],
        'series': series,
    }
    if not tpu:
        summary['note'] = (
            '2-core CPU smoke box: promote-phase p99 tails include '
            'each export + deploy warmup compiling on the serving '
            'cores (same structural contention as the fleet swap '
            'phase); on a TPU host compiles do not contend with '
            'serving.')
    print(json.dumps(summary))
    results.append(summary)
    ctl.close()
    fleet.close()
    return results


def dynamic_scenario(tpu):
    """Adaptive batching under request-at-a-time traffic."""
    import paddle_tpu as fluid
    from paddle_tpu.inference import BatchingInferenceServer

    n_sparse = 26
    max_batch = 64
    n_req = 480 if not tpu else 960
    main_prog, startup, pred = _build_ctr_tower(n_sparse)
    place = fluid.TPUPlace(0) if tpu else fluid.CPUPlace()
    exe = fluid.Executor(place)
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    specs = {('C%d' % i): (1,) for i in range(n_sparse)}
    specs['I'] = (13,)
    t0 = time.perf_counter()
    srv = BatchingInferenceServer.from_program(
        specs, [pred], executor=exe, main_program=main_prog,
        scope=scope, max_batch=max_batch, max_wait_ms=10.0,
        linger_ms=0.3)
    t_warm = time.perf_counter() - t0
    ref = srv._servers[1]  # the unbatched single-row artifact
    rng = np.random.default_rng(0)

    def mk():
        f = {('C%d' % i):
             rng.integers(0, 10000, size=(1, 1)).astype('int32')
             for i in range(n_sparse)}
        f['I'] = rng.normal(size=(1, 13)).astype('float32')
        return f

    f1 = mk()
    ref.predict(f1)
    for _ in range(64):
        srv.submit(f1)
    srv.predict(f1)  # drain + warm the serving loop

    def base_rate(n=150):
        t0 = time.perf_counter()
        for _ in range(n):
            ref.predict(f1)
        return n / (time.perf_counter() - t0)

    def closed_loop(n_threads=8, depth=8):
        per = n_req // n_threads
        feeds = [[mk() for _ in range(per)] for _ in range(n_threads)]

        def client(i):
            q = deque()
            for j in range(per):
                q.append(srv.submit(feeds[i][j]))
                while len(q) >= depth:
                    q.popleft().result()
            while q:
                q.popleft().result()

        ths = [threading.Thread(target=client, args=(i,))
               for i in range(n_threads)]
        s0 = srv.stats()
        t0 = time.perf_counter()
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        dt = time.perf_counter() - t0
        s1 = srv.stats()
        occ = ((s1['requests_completed'] - s0['requests_completed'])
               / max(s1['batches'] - s0['batches'], 1))
        return n_threads * per / dt, occ

    results = []
    # -- closed loop: concurrency 8, paired with adjacent baselines ----
    bases, rates, occs = [], [], []
    for _ in range(3):
        bases.append(base_rate())
        r, occ = closed_loop()
        rates.append(r)
        occs.append(occ)
    base = float(np.median(bases))
    rate = float(np.median(rates))
    st = srv.stats()
    r = {"metric": "ctr_serving_dynamic_closed_loop_conc8",
         "value": round(rate, 1), "unit": "req/s",
         "single_predict_req_s": round(base, 1),
         "speedup_vs_single": round(rate / base, 2),
         "mean_batch_occupancy": round(float(np.median(occs)), 2),
         "compiles_warmup": st['compiles'],
         "compiles_after_warmup": st['compiles_after_warmup'],
         "warmup_s": round(t_warm, 1),
         "buckets": st['buckets'], "n_requests": n_req,
         "pipeline_depth": 8}
    print(json.dumps(r))
    results.append(r)

    # -- open loop: Poisson arrivals at several offered loads ----------
    for load_frac in (0.5, 1.0, 2.0):
        lam = base * load_frac  # offered req/s
        n = min(n_req, int(max(lam, 50) * 2) + 50)
        feeds = [mk() for _ in range(n)]
        gaps = rng.exponential(1.0 / lam, size=n)
        done_at = [None] * n
        sub_at = [None] * n

        def make_cb(i):
            def cb(_fut):
                done_at[i] = time.perf_counter()
            return cb

        s0 = srv.stats()
        futs = []
        t0 = time.perf_counter()
        for i in range(n):
            target = t0 + float(np.sum(gaps[:i + 1]))
            delay = target - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            sub_at[i] = time.perf_counter()
            fut = srv.submit(feeds[i])
            fut.add_done_callback(make_cb(i))
            futs.append(fut)
        for fut in futs:
            fut.result()
        dt = time.perf_counter() - t0
        # set_result unblocks result() BEFORE running done-callbacks:
        # give stragglers a beat so every done_at slot is stamped
        deadline = time.perf_counter() + 5.0
        while any(d is None for d in done_at) and \
                time.perf_counter() < deadline:
            time.sleep(0.001)
        s1 = srv.stats()
        lat = np.array([d - s for d, s in zip(done_at, sub_at)
                        if d is not None]) * 1e3
        occ = ((s1['requests_completed'] - s0['requests_completed'])
               / max(s1['batches'] - s0['batches'], 1))
        r = {"metric": "ctr_serving_dynamic_poisson_load%g" % load_frac,
             "value": round(n / dt, 1), "unit": "req/s",
             "offered_req_s": round(lam, 1),
             "p50_latency_ms": round(float(np.percentile(lat, 50)), 2),
             "p99_latency_ms": round(float(np.percentile(lat, 99)), 2),
             "mean_batch_occupancy": round(occ, 2),
             "compiles_after_warmup": s1['compiles_after_warmup'],
             "n_requests": n}
        print(json.dumps(r))
        results.append(r)
    srv.close()
    return results


def decode_scenario(tpu):
    """Autoregressive decode under open-loop Poisson traffic (ISSUE 19):
    streams of MIXED prompt/generation lengths arrive at random times
    against the paged-KV DecodeEngine, served two ways over the same
    arrival schedule —

      continuous: streams join mid-decode at step granularity the
        moment a slot + pages free up (work-conserving), vs
      static: generation-batch baseline — a new group is admitted only
        when every slot drained (the barrier continuous batching
        removes)

    — reporting p50/p99 time-to-first-token, p50/p99 per-token latency,
    and generated tokens/s via common.generated_tokens_per_sec (the
    same accounting bench_decode.py's headline uses).  The bar: ZERO
    dropped streams, ZERO post-warmup compiles, and continuous
    throughput strictly above the static baseline at mixed lengths.
    The continuous row also carries the on-chip roofline prediction
    from cost_model.decode_step_cost — the modeled TPU tokens/s next
    to the measured CPU-smoke number, per the PERF.md convention."""
    import paddle_tpu as fluid
    from paddle_tpu.inference.decode import DecodeEngine, DecodeServer, \
        extract_params
    from paddle_tpu.models import transformer
    from paddle_tpu.transpiler.cost_model import decode_step_cost
    from common import generated_tokens_per_sec

    if tpu:
        L, D, H, V, T = 6, 512, 8, 30000, 512
        page, streams, bucket = 16, 16, 256
        n_req, mean_gap_s = 64, 0.001
    else:
        L, D, H, V, T = 2, 64, 4, 200, 64
        page, streams, bucket = 8, 4, 32
        n_req, mean_gap_s = 24, 0.001

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        main_p, startup = fluid.Program(), fluid.Program()
        main_p.random_seed = startup.random_seed = 19
        with fluid.program_guard(main_p, startup):
            transformer.build(vocab_size=V, seq_len=T, n_layers=L,
                              d_model=D, n_heads=H)
        exe = fluid.Executor(fluid.TPUPlace(0) if tpu
                             else fluid.CPUPlace())
        exe.run(startup, scope=scope)
        params = extract_params(scope, L)
    eng = DecodeEngine(params, n_layers=L, n_heads=H, page_size=page,
                       max_streams=streams, prefill_bucket=bucket)
    eng.warmup()

    # ONE arrival schedule + workload for both treatments: Poisson
    # gaps, prompts mixed across the bucket ladder, mixed generation
    # lengths — the shape continuous batching exists for
    rng = np.random.default_rng(7)
    gaps = rng.exponential(mean_gap_s, n_req)
    plens = rng.choice([4, 7, 11, 15, 22, 30], n_req).astype(int)
    if not tpu:
        plens = np.minimum(plens, bucket - 2)
    nnews = rng.choice([6, 10, 16, 24], n_req).astype(int)
    prompts = [rng.integers(1, V, int(p)).astype(np.int64)
               for p in plens]

    results = []
    throughput = {}
    for label, static in (('continuous', False), ('static', True)):
        srv = DecodeServer(eng, static_batching=static)
        t_start = time.perf_counter()
        streams_out = []
        for gap, prompt, nn in zip(gaps, prompts, nnews):
            time.sleep(float(gap))
            streams_out.append(srv.submit(prompt,
                                          max_new_tokens=int(nn)))
        assert srv.drain(timeout=600.0), "decode drain timed out"
        wall = time.perf_counter() - t_start
        stats = srv.stats()
        srv.close()
        assert stats['dropped'] == 0, stats
        assert stats['compiles_after_warmup'] == 0, stats
        assert stats['completed'] == n_req, stats
        ttfts = np.asarray([st.ttft_s for st in streams_out])
        per_tok = np.concatenate([st.per_token_s()
                                  for st in streams_out
                                  if len(st.per_token_s())])
        n_generated = int(sum(len(st.tokens) for st in streams_out))
        thr = generated_tokens_per_sec(n_generated, wall)
        throughput[label] = thr
        r = {"metric": "decode_generated_tokens_per_sec",
             "value": round(thr, 2),
             "batching": label,
             "streams": n_req,
             "p50_ttft_ms": round(float(np.percentile(ttfts, 50))
                                  * 1e3, 2),
             "p99_ttft_ms": round(float(np.percentile(ttfts, 99))
                                  * 1e3, 2),
             "p50_tok_ms": round(float(np.percentile(per_tok, 50))
                                 * 1e3, 2),
             "p99_tok_ms": round(float(np.percentile(per_tok, 99))
                                 * 1e3, 2),
             "dropped": stats['dropped'],
             "compiles_after_warmup": stats['compiles_after_warmup'],
             "note": "L=%d D=%d V=%d page=%d slots=%d; mixed prompts "
                     "%d-%d + mixed gen %d-%d, Poisson mean gap %.0fms"
                     % (L, D, V, page, streams, plens.min(),
                        plens.max(), nnews.min(), nnews.max(),
                        mean_gap_s * 1e3)}
        if not static:
            # on-chip prediction: one full-width decode step priced by
            # the closed-form model against the calibrated roofline —
            # tokens/s = S / max(compute floor, bandwidth floor)
            c = decode_step_cost(L, D, H, 4 * D, V, streams,
                                 ctx_len=int(plens.mean()
                                             + nnews.mean() // 2))
            peak = float(os.environ.get('PADDLE_TPU_PEAK_TFLOPS')
                         or 0) or 192.0
            gbps = float(os.environ.get('PADDLE_TPU_HBM_GBPS')
                         or 0) or 819.0
            step_floor = max(c['flops'] / (peak * 1e12),
                             c['bytes'] / (gbps * 1e9))
            r['modeled_tpu_tokens_per_sec'] = round(
                streams / step_floor, 1)
            r['modeled_step_bound'] = (
                'mxu' if c['flops'] / (peak * 1e12)
                >= c['bytes'] / (gbps * 1e9) else 'hbm')
        print(json.dumps(r))
        results.append(r)
    assert throughput['continuous'] > throughput['static'], (
        "continuous batching must beat the generation-batch baseline: "
        "%r" % throughput)
    return results


def _decode_model(tpu, seed=19, **over):
    """The decode-bench transformer (same shapes as decode_scenario),
    built once per scenario: returns (params, cfg).  Keyword overrides
    replace cfg entries before the build."""
    import paddle_tpu as fluid
    from paddle_tpu.inference.decode import extract_params
    from paddle_tpu.models import transformer

    if tpu:
        cfg = dict(L=6, D=512, H=8, V=30000, T=512,
                   page=16, streams=16, bucket=256)
    else:
        cfg = dict(L=2, D=64, H=4, V=200, T=64,
                   page=8, streams=4, bucket=32)
    cfg.update(over)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        main_p, startup = fluid.Program(), fluid.Program()
        main_p.random_seed = startup.random_seed = seed
        with fluid.program_guard(main_p, startup):
            transformer.build(vocab_size=cfg['V'], seq_len=cfg['T'],
                              n_layers=cfg['L'], d_model=cfg['D'],
                              n_heads=cfg['H'])
        exe = fluid.Executor(fluid.TPUPlace(0) if tpu
                             else fluid.CPUPlace())
        exe.run(startup, scope=scope)
        return extract_params(scope, cfg['L']), cfg


def decode_prefix_scenario(tpu):
    """Prefix-cached KV page reuse (ISSUE 20): the agent/few-shot
    traffic shape — every request shares a common preamble (system
    prompt + exemplars) and differs only in a short suffix — served
    prefix-on vs prefix-off over the SAME seed-pinned Poisson arrival
    schedule.  Reports TTFT p50/p99 for both treatments, the prefix
    hit rate, and the closed-form prefill MACs split cached vs
    computed (cost_model.prefill_cost — the cached share is work the
    reuse path never issues).  The bar: hit rate >= 0.5, prefix-on
    TTFT p99 strictly below prefix-off, zero post-warmup compiles."""
    from paddle_tpu.inference.decode import DecodeEngine, DecodeServer
    from paddle_tpu.transpiler.cost_model import prefill_cost

    # CPU smoke needs a prefill-heavy shape: at the default D=64 a
    # monolithic bucket call and a single tail chunk both cost the
    # same ~0.8ms XLA dispatch floor, so the cached-span skip has no
    # wall-clock signal to show — widen until prefill math dominates
    params, cfg = _decode_model(tpu) if tpu else \
        _decode_model(False, D=256, V=8000)
    page = cfg['page']
    bucket = cfg['bucket'] if tpu else cfg['T']
    n_req = 48 if tpu else 24
    pre_len = 4 * page         # page-aligned few-shot preamble
    suf_len = 8 if tpu else 6
    max_new = 8 if tpu else 6
    rng = np.random.default_rng(11)
    preamble = rng.integers(1, cfg['V'], pre_len).astype(np.int64)
    prompts = [np.concatenate([
        preamble, rng.integers(1, cfg['V'], suf_len).astype(np.int64)])
        for _ in range(n_req)]
    gaps = rng.exponential(0.001, n_req)

    engines = {}
    for label, on in (('on', True), ('off', False)):
        eng = DecodeEngine(params, n_layers=cfg['L'],
                           n_heads=cfg['H'], page_size=page,
                           max_streams=cfg['streams'],
                           prefill_bucket=bucket,
                           prefix_cache=on)
        eng.warmup()
        engines[label] = eng

    def run(label):
        srv = DecodeServer(engines[label])
        h0 = srv.stats()['prefix_hit_tokens']
        if label == 'on':
            # seed the trie: the one cold miss is this treatment's
            # warmup, not a sample of its steady state (repeat runs
            # hit the already-populated trie, which IS the steady
            # state the cache converges to under this traffic)
            srv.submit(prompts[0],
                       max_new_tokens=1).result(timeout=120.0)
            h0 = srv.stats()['prefix_hit_tokens']
        streams = []
        for gap, p in zip(gaps, prompts):
            time.sleep(float(gap))
            streams.append(srv.submit(p, max_new_tokens=max_new))
        assert srv.drain(timeout=600.0), "prefix drain timed out"
        stats = srv.stats()
        srv.close()
        assert stats['dropped'] == 0, stats
        assert stats['compiles_after_warmup'] == 0, stats
        ttfts = np.asarray([st.ttft_s for st in streams]) * 1e3
        hit = stats['prefix_hit_tokens'] - h0
        miss = sum(len(p) for p in prompts) - hit
        return (float(np.percentile(ttfts, 99)),
                float(np.percentile(ttfts, 50)),
                hit / max(hit + miss, 1), hit, stats)

    # interleaved repeats, median p99 per treatment: a single
    # p99-vs-p99 comparison between two runs seconds apart measures
    # 2-core box weather, not the scheduler
    repeats = 3
    samples = {'on': [], 'off': []}
    for _ in range(repeats):
        for label in ('on', 'off'):
            samples[label].append(run(label))

    results = []
    p99 = {}
    for label in ('on', 'off'):
        runs = samples[label]
        p99[label] = float(np.median([r[0] for r in runs]))
        p50 = float(np.median([r[1] for r in runs]))
        hit_rate = runs[-1][2]
        stats = runs[-1][4]
        flops_computed = flops_cached = 0
        for p in prompts:
            c = prefill_cost(cfg['L'], cfg['D'], cfg['H'],
                             4 * cfg['D'], cfg['V'], len(p),
                             cached_len=pre_len if label == 'on'
                             else 0)
            flops_computed += c['flops']
            flops_cached += c['flops_cached']
        r = {"metric": "decode_prefix_ttft_ms",
             "value": round(p99[label], 2), "unit": "ms p99",
             "prefix_cache": label,
             "p50_ttft_ms": round(p50, 2),
             "p99_ttft_ms": round(p99[label], 2),
             "p99_samples": [round(x[0], 2) for x in runs],
             "prefix_hit_rate": round(hit_rate, 3),
             "prefix_hit_tokens": runs[-1][3],
             "prefill_gflops_computed": round(flops_computed / 1e9, 4),
             "prefill_gflops_cached": round(flops_cached / 1e9, 4),
             "cached_pages": stats['cached_pages'],
             "compiles_after_warmup": stats['compiles_after_warmup'],
             "note": "%d streams sharing a %d-token preamble + %d-token"
                     " unique suffix, Poisson mean gap 1ms, median of "
                     "%d interleaved runs"
                     % (n_req, pre_len, suf_len, repeats)}
        print(json.dumps(r))
        results.append(r)
        if label == 'on':
            assert hit_rate >= 0.5, (
                "prefix hit rate %.3f below the 0.5 bar" % hit_rate)
    assert p99['on'] < p99['off'], (
        "prefix-on TTFT p99 must beat prefix-off: %r" % p99)
    return results


def decode_chunked_scenario(tpu):
    """Chunked prefill bounds head-of-line blocking (ISSUE 20): three
    short-prompt streams decode continuously while long-prompt streams
    inject mid-run; the victims' inter-token latency p99 is compared
    against the same streams with NO injection.  The chunked engine
    (per-tick prefill budget of one page) must hold the ratio at
    <= 1.5x; the monolithic engine — which prefills each long prompt
    in one tick-blocking call — runs the same schedule as the
    recorded contrast."""
    from paddle_tpu.inference.decode import DecodeEngine, DecodeServer

    if tpu:
        params, cfg = _decode_model(True)
    else:
        # step-heavy smoke shape: the 1.5x bound is about a page-sized
        # chunk hiding inside a decode step that dominates the tick.
        # At the default smoke width a sub-ms step would be swamped by
        # the ~0.8ms XLA dispatch floor of the EXTRA per-tick chunk
        # call — measuring the host, not the scheduler — so widen the
        # model and the slot count until the step carries the tick
        params, cfg = _decode_model(False, D=256, V=8000,
                                    page=4, streams=16)
    page = cfg['page']
    n_short = cfg['streams'] - 1
    short_new = 64 if tpu else 44
    # injected prompts span (nearly) the full context with the prefill
    # ladder opened up to match: the monolithic treatment prefills
    # each one in a single tick-blocking top-bucket call, which is the
    # head-of-line block chunking exists to break up
    bucket = cfg['T']
    long_len, long_new = cfg['T'] - 8, 4
    n_long = 6
    rng = np.random.default_rng(13)
    short_prompts = [rng.integers(1, cfg['V'], 4).astype(np.int64)
                     for _ in range(n_short)]
    long_prompts = [rng.integers(1, cfg['V'], long_len).astype(np.int64)
                    for _ in range(n_long)]

    def run(eng, inject):
        srv = DecodeServer(eng)
        shorts = [srv.submit(p, max_new_tokens=short_new)
                  for p in short_prompts]
        deadline = time.perf_counter() + 120.0
        while not all(st.tokens for st in shorts) and \
                time.perf_counter() < deadline:
            time.sleep(0.001)   # all victims decoding before injection
        if inject:
            for p in long_prompts:
                srv.submit(p, max_new_tokens=long_new)
        assert srv.drain(timeout=600.0), "chunked drain timed out"
        stats = srv.stats()
        srv.close()
        assert stats['dropped'] == 0, stats
        assert stats['compiles_after_warmup'] == 0, stats
        # steady-state ITL: drop each victim's first few intervals —
        # they straddle admission and the first post-warmup dispatches,
        # cold-start jitter common to both treatments
        itl = np.concatenate([st.per_token_s()[5:]
                              for st in shorts]) * 1e3
        return float(np.percentile(itl, 99)), stats

    results = []
    ratios = {}
    repeats = 3   # interleaved repeats, median p99 per treatment:
    #               a single p99-vs-p99 comparison between two runs
    #               half a second apart measures 2-core box weather
    for label, chunk in (('chunked', page), ('monolithic', 0)):
        eng = DecodeEngine(params, n_layers=cfg['L'],
                           n_heads=cfg['H'], page_size=page,
                           max_streams=cfg['streams'],
                           prefill_bucket=bucket,
                           prefill_chunk_tokens=chunk)
        eng.warmup()
        base_p99s, inj_p99s = [], []
        for _ in range(repeats):
            base_p99s.append(run(eng, inject=False)[0])
            inj_p99, stats = run(eng, inject=True)
            inj_p99s.append(inj_p99)
        base_p99 = float(np.median(base_p99s))
        inj_p99 = float(np.median(inj_p99s))
        ratios[label] = inj_p99 / max(base_p99, 1e-9)
        r = {"metric": "decode_itl_injection_ratio",
             "value": round(ratios[label], 2),
             "unit": "x no-injection p99",
             "prefill": label,
             "itl_p99_ms_baseline": round(base_p99, 2),
             "itl_p99_ms_injected": round(inj_p99, 2),
             "baseline_samples": [round(x, 2) for x in base_p99s],
             "injected_samples": [round(x, 2) for x in inj_p99s],
             "prefill_chunks": stats['prefill_chunks'],
             "compiles_after_warmup": stats['compiles_after_warmup'],
             "note": "%d victims decoding %d tokens; %d injected "
                     "%d-token prompts" % (n_short, short_new,
                                           n_long, long_len)}
        print(json.dumps(r))
        results.append(r)
    assert ratios['chunked'] <= 1.5, (
        "chunked prefill must bound victim ITL p99 at 1.5x the "
        "no-injection baseline: %r" % ratios)
    return results


if __name__ == '__main__':
    main()

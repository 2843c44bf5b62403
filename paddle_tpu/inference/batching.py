"""N4+ — dynamic request batching over shape-bucketed precompiled artifacts.

The exported-artifact serving path (serving.py) answers the benchmark use
case: pre-formed fixed batches, one shape, one compile.  Production traffic
is the opposite — requests arrive one at a time at variable rates, and
every novel batch shape costs a multi-second XLA compile.  The fix here is
the Clipper / TF-Serving adaptive-batching design, TPU-native:

- a request queue + background dispatcher coalesces concurrent ``submit``
  calls into batches, so the chip runs near-full batches under load;
- batches land on a power-of-two **bucket ladder** (1, 2, 4, ..,
  ``max_batch``): requests pad up to the next bucket and un-pad on the way
  out, so only ~log2(max_batch) shapes ever compile;
- the dispatch policy is **work-conserving**: a full bucket launches
  immediately (while fewer than two batches are in flight), a partial
  batch launches once the device is idle and a short ``linger_ms`` has
  passed (letting the just-woken clients of the previous batch pile on),
  and the **deadline flush** ``max_wait_ms`` — counted from the oldest
  queued request — bounds the latency a lone request can ever pay;
- **double-buffered async dispatch**: jax dispatch is asynchronous, so the
  dispatcher stages batch N+1 (``jax.device_put``) and launches it while
  the collector still syncs batch N — the ``predict_stacked`` staging note
  made real — with at most two batches in flight so memory stays bounded;
- **startup warmup** AOT-compiles every bucket before serving begins, and
  the serving loop only ever calls those precompiled executables — a shape
  that somehow misses the ladder is a counted event
  (``stats()['compiles_after_warmup']``), not a silent multi-second stall.

Correctness contract: the inference graph must be row-independent along
the batch axis (true for inference_optimize'd programs — batch-norm runs
on frozen statistics), so padded rows cannot perturb real rows: a real
row's output is computed from that row's data alone and is bitwise
independent of what sits in the padding.  Padding replicates the last
real row rather than feeding zeros: an all-zeros row can generate NaN/Inf
(division, log) which a non-row-wise op could propagate.

Precision note: rows routed through DIFFERENT bucket programs can differ
from each other in the last ulp — XLA picks different kernels for
different shapes (GEMV vs GEMM, vector vs scalar ``exp``).  Within one
bucket program results are deterministic, and a request that exactly
fills its bucket is bit-identical to an unbatched ``predict`` on that
bucket's artifact.
"""
import itertools
import os
import queue
import tempfile
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

import jax

from .. import observability as _obs
from ..analysis import lockdebug as _lkd
from ..compile_cache import enable_compile_cache
from ..observability import timeline as _tlm
from .aot_cache import AotCache, artifact_digest
from .serving import InferenceServer, export_inference

__all__ = ['BatchingInferenceServer', 'export_bucketed', 'bucket_sizes']

_STOP = object()

_server_seq = itertools.count()


class _ServingMetrics(object):
    """Per-server handles into a metrics registry, labeled
    ``server="b<N>"`` so concurrent servers in one process stay
    distinguishable on /metrics while ``stats()`` reads back exactly
    this server's children.

    When observability is disabled the server still needs its counters —
    ``stats()`` is part of the serving contract — so it reports into a
    private registry instead of the global one: same code path, nothing
    exported, nothing shared.
    """

    def __init__(self, reg, sid):
        L = ('server',)
        self._sid = sid
        self._families = []

        def child(metric):
            self._families.append(metric)
            return metric.labels(server=sid)

        self.submitted = child(reg.counter(
            'paddle_tpu_serving_requests_submitted_total',
            'requests accepted by submit()', L))
        self.completed = child(reg.counter(
            'paddle_tpu_serving_requests_completed_total',
            'requests whose results were delivered', L))
        self.batches = child(reg.counter(
            'paddle_tpu_serving_batches_total',
            'device batches dispatched', L))
        self.batch_rows = child(reg.counter(
            'paddle_tpu_serving_batch_rows_total',
            'real (non-padding) rows dispatched in batches', L))
        self.batch_capacity = child(reg.counter(
            'paddle_tpu_serving_batch_capacity_total',
            'bucket capacity dispatched (rows incl. padding)', L))
        self.compiles = child(reg.counter(
            'paddle_tpu_serving_compiles_total',
            'bucket AOT compiles (warmup + on-demand)', L))
        self.compiles_after_warmup = child(reg.counter(
            'paddle_tpu_serving_compiles_after_warmup_total',
            'compiles after warmup finished — nonzero means the bucket '
            'ladder missed a shape and the loop stalled', L))
        self.queue_depth = child(reg.gauge(
            'paddle_tpu_serving_queue_depth',
            'requests waiting to be batched', L))
        self.in_flight = child(reg.gauge(
            'paddle_tpu_serving_in_flight_batches',
            'batches dispatched but not yet synced', L))
        self.latency = child(reg.histogram(
            'paddle_tpu_serving_request_latency_seconds',
            'submit-to-result latency per request', L,
            buckets=_obs.DEFAULT_LATENCY_BUCKETS))
        self.occupancy = child(reg.histogram(
            'paddle_tpu_serving_batch_occupancy',
            'real rows per dispatched batch', L,
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)))
        # queue-wait vs compute: the end-to-end request latency above
        # splits into the time a request sat waiting to be batched and
        # the time its batch spent on the device — labeled by the bucket
        # it dispatched in (plus a bucket="all" rollup), so the fleet
        # dispatcher's routing signal and the bench read the SAME
        # numbers stats() reports
        L2 = ('server', 'bucket')
        self._queue_wait_family = reg.histogram(
            'paddle_tpu_serving_queue_wait_seconds',
            'submit-to-dispatch wait per request, by dispatched bucket '
            '(bucket="all" aggregates)', L2,
            buckets=_obs.DEFAULT_LATENCY_BUCKETS)
        self._compute_family = reg.histogram(
            'paddle_tpu_serving_compute_seconds',
            'dispatch-to-sync device time per batch, by bucket '
            '(bucket="all" aggregates)', L2,
            buckets=_obs.DEFAULT_LATENCY_BUCKETS)
        self._bucket_children = {}  # (family, bucket_label) -> child

    def _bucket_child(self, family, bucket):
        key = (family.name, str(bucket))
        child = self._bucket_children.get(key)
        if child is None:
            child = family.labels(server=self._sid, bucket=str(bucket))
            self._bucket_children[key] = child
        return child

    def queue_wait(self, bucket):
        return self._bucket_child(self._queue_wait_family, bucket)

    def compute(self, bucket):
        return self._bucket_child(self._compute_family, bucket)

    def observed_buckets(self):
        """Bucket sizes that have dispatched at least one batch so far
        (the stats() per-bucket iteration set)."""
        return sorted({int(b) for (_, b) in self._bucket_children
                       if b != 'all'})

    def close(self):
        """Retire this server's label series so a process cycling
        servers (rolling reloads, test suites) doesn't grow the
        registry and /metrics output without bound.  The server's own
        handles stay usable for a final stats() read."""
        for m in self._families:
            m.remove(server=self._sid)
        for fam_name, b in list(self._bucket_children):
            fam = (self._queue_wait_family
                   if fam_name == self._queue_wait_family.name
                   else self._compute_family)
            fam.remove(server=self._sid, bucket=b)


def bucket_sizes(max_batch):
    """The power-of-two bucket ladder [1, 2, 4, ...] whose top is
    ``max_batch`` rounded up to a power of two."""
    if max_batch < 1:
        raise ValueError("max_batch must be >= 1, got %r" % (max_batch,))
    sizes = [1]
    while sizes[-1] < max_batch:
        sizes.append(sizes[-1] * 2)
    return sizes


def export_bucketed(dir_path, feed_specs, target_vars, executor=None,
                    main_program=None, scope=None, max_batch=None,
                    amp=None):
    """Export one shape-specialized StableHLO artifact per bucket size.

    :param feed_specs: {feed_name: per-request example shape WITHOUT the
        batch axis} — bucket b exports at shape (b,) + example_shape.
    :param amp: scoped PADDLE_TPU_AMP override for these exports:
        'bf16'/'f16' bakes the AMP-rewritten program (white-listed ops
        in low precision, f32 weights cast once at the graph edge) into
        every bucket's artifact; '0' forces full precision; None
        (default) honours the ambient flag.  The override is
        PROCESS-GLOBAL for the duration of the export (amp_guard
        mutates os.environ, which every concurrent plan build reads) —
        export before serving/training threads start, the way
        from_program's warmup already sequences it.
    :returns: {bucket_size: artifact path}, ready for
        :class:`BatchingInferenceServer`.
    """
    from ..transpiler.amp import amp_guard
    if max_batch is None:
        # registered tunable: flag default 8 keeps the historical
        # ladder when the env is unset; explicit max_batch= still wins
        from ..flags import FLAGS
        max_batch = int(FLAGS.serving_max_batch)
    paths = {}
    with amp_guard(amp):
        for b in bucket_sizes(max_batch):
            shapes = {n: (b,) + tuple(s) for n, s in feed_specs.items()}
            p = os.path.join(dir_path, 'bucket_%d.stablehlo' % b)
            export_inference(p, shapes, target_vars, executor=executor,
                             main_program=main_program, scope=scope)
            paths[b] = p
    return paths


class _Request(object):
    __slots__ = ('feed', 'rows', 'future', 't_submit', 'rid')

    def __init__(self, feed, rows, t_submit, rid):
        self.feed = feed
        self.rows = rows
        self.future = Future()
        self.t_submit = t_submit
        self.rid = rid


class BatchingInferenceServer(object):
    """Adaptive-batching front end over a ladder of bucket-sized
    :class:`InferenceServer` artifacts (load once, predict *concurrently*).

    - ``submit(feed)`` -> Future of [outputs] (thread-safe; blocks only
      on queue backpressure); ``predict(feed)`` is submit + wait.
    - A request carries one example (feed values at the exported example
      shape) or a leading batch axis of k <= max_batch rows; outputs keep
      the request's leading axis.
    - ``stats()`` exposes queue depth, batch occupancy, latency
      percentiles, and compile counters.

    Construction: ``BatchingInferenceServer({bucket: path})`` over
    artifacts from :func:`export_bucketed`, or the one-call
    :meth:`from_program`.

    Knobs: ``max_wait_ms`` caps how long any request waits to be batched
    (the deadline flush); ``linger_ms`` is the much shorter grace period
    a partial batch waits while the device is idle, trading a hair of
    latency for occupancy under closed-loop load; ``max_queue`` bounds
    the submission queue (submit blocks past it — backpressure, not
    unbounded memory).
    """

    def __init__(self, bucket_paths, max_wait_ms=None, linger_ms=0.5,
                 max_queue=4096, warmup=True, latency_window=4096,
                 share_artifacts_with=None, warmup_throttle_ms=0.0):
        if max_wait_ms is None:
            # registered tunable (tuning/registry.py): the flag default
            # is the historical 5.0 ms, so an unset env is bitwise the
            # old constructor default; explicit max_wait_ms= still wins
            from ..flags import FLAGS
            max_wait_ms = float(FLAGS.serving_max_wait_ms)
        enable_compile_cache()
        if share_artifacts_with is not None:
            # a sibling server over the SAME model version: reuse its
            # deserialized artifacts and AOT-compiled executables
            # instead of re-deserializing + re-tracing every bucket.
            # In-process replicas (ServingFleet) are dispatch lanes
            # over one servable — compiled executables are thread-safe
            # and immutable, so sharing them is free, and a fleet
            # deploy pays ONE warmup per version instead of one per
            # replica.  The queues, worker threads, metrics, and
            # lifecycle below stay fully per-server.
            src = share_artifacts_with
            if not isinstance(src, BatchingInferenceServer):
                raise TypeError(
                    "share_artifacts_with must be a "
                    "BatchingInferenceServer, got %r" % (src,))
            if bucket_paths and \
                    sorted(int(b) for b in bucket_paths) != src._buckets:
                raise ValueError(
                    "share_artifacts_with: bucket_paths ladder %s does "
                    "not match the source server's %s — sharing is only "
                    "valid between replicas of ONE exported version"
                    % (sorted(int(b) for b in bucket_paths),
                       src._buckets))
            self._servers = src._servers
            # the same dict object, deliberately: a bucket lazily
            # compiled by either sibling is visible to both
            self._compiled = src._compiled
            self._bucket_paths = dict(src._bucket_paths)
            self._buckets = src._buckets
            self.max_batch = src.max_batch
            self._feed_names = src._feed_names
            self._example_shapes = src._example_shapes
            self._dtypes = src._dtypes
            # eviction/AOT state is part of the shared servable: a
            # bucket evicted or re-warmed through either sibling is
            # evicted/re-warmed for both, and the last-use map feeds
            # the budget manager's LRU with dispatches from all lanes
            self._aot = src._aot
            self._aot_digests = src._aot_digests
            self._bucket_used = src._bucket_used
            self._res_gen = src._res_gen
        else:
            if not bucket_paths:
                raise ValueError("bucket_paths is empty")
            self._servers = {int(b): InferenceServer(p)
                             for b, p in bucket_paths.items()}
            self._compiled = {}
            self._bucket_paths = {int(b): p
                                  for b, p in bucket_paths.items()}
            self._buckets = sorted(self._servers)
            self.max_batch = self._buckets[-1]
            avals = self._servers[self.max_batch].feed_avals()
            self._feed_names = sorted(avals)
            self._example_shapes = {
                n: tuple(a.shape[1:]) for n, a in avals.items()}
            self._dtypes = {n: np.dtype(a.dtype)
                            for n, a in avals.items()}
            for b in self._buckets:
                av = self._servers[b].feed_avals()
                want = {n: (b,) + self._example_shapes[n]
                        for n in self._feed_names}
                got = {n: tuple(a.shape) for n, a in av.items()}
                if got != want:
                    raise ValueError(
                        "bucket %d artifact feeds %s do not match the "
                        "ladder (expected %s): every bucket must "
                        "export the same example shapes with only the "
                        "batch axis varying" % (b, got, want))
            # AOT executable cache (PADDLE_TPU_AOT_CACHE_DIR): warmup
            # deserializes stored executables instead of compiling —
            # zero warmup compiles on a warm disk cache.  Disabled
            # (the default) this is one flag read and None forever.
            aot = AotCache()
            self._aot = aot if aot.enabled() else None
            self._aot_digests = {}  # bucket -> artifact sha1
            # per-bucket last-dispatch stamps (time.monotonic), the
            # budget manager's LRU signal.  Written by the dispatcher
            # thread only; readers (the fleet's eviction planner)
            # tolerate a stale read — like _compiled, the dict itself
            # is GIL-atomic and never locked.
            self._bucket_used = {}
            # residency generation, bumped on evict and on post-warmup
            # (re)compiles so fleet replicas know their cached
            # resident_bytes() snapshot went stale.  One shared
            # mutable cell: siblings sharing this servable must see
            # the same generation.
            self._res_gen = [0]
        self.max_wait = float(max_wait_ms) / 1e3
        self.linger = float(linger_ms) / 1e3
        self.max_queue = int(max_queue)

        # one lock, two wait-sets: the dispatcher sleeps on _cv, clients
        # blocked on backpressure sleep on _cv_space — so a submit wakes
        # exactly the dispatcher, not a herd of queued clients.  Both
        # conditions carry ONE watchdog name: they are one lock in the
        # acquisition-order graph (PADDLE_TPU_LOCK_DEBUG)
        lock = threading.Lock()
        self._cv = _lkd.make_condition(
            'BatchingInferenceServer._cv', lock)
        self._cv_space = _lkd.make_condition(
            'BatchingInferenceServer._cv', lock)
        self._pending = deque()   # guarded by _cv
        self._pending_rows = 0    # running row total of _pending
        self._in_flight = 0       # batches dispatched, not yet synced
        self._stopping = False
        self._draining = False    # drain(): stop accepting, keep flushing
        # collector handoff; capacity 2 == the double-buffer window
        self._inflight_q = queue.Queue(maxsize=2)

        # staging a batch onto the device (jax.device_put, one call for
        # the whole feed pytree) only pays where host and device memory
        # differ; on the CPU backend the AOT executable ingests numpy
        # directly and an explicit put is pure overhead (measured 1.5ms
        # per 27-field batch)
        self._stage_to_device = jax.default_backend() != 'cpu'

        # stats live in the observability registry (the global one when
        # metrics are enabled — labeled server="b<N>" and exported on
        # /metrics — else a private registry so stats() keeps working);
        # latency_window is retained for signature compatibility but the
        # bounded-bucket histogram replaced the latency deque
        del latency_window
        sid = 'b%d' % next(_server_seq)
        reg = _obs.registry() if _obs.enabled() \
            else _obs.MetricsRegistry()
        self._m = _ServingMetrics(reg, sid)
        # monotonic per-server request ids for the timeline dispatch
        # spans (a fleet passes its own fleet-level id through submit)
        self._req_seq = itertools.count()
        self._warmup_done = False
        self._closed = False
        self._owned_dir = None  # set by from_program when it mkdtemp'd
        # the serving runtime is the natural home of the opt-in scrape
        # endpoint: first server construction starts it when
        # PADDLE_TPU_METRICS_PORT is set (idempotent, daemon thread)
        if _obs.enabled():
            _obs.maybe_serve_from_env()

        if warmup:
            # warmup_throttle_ms: pause between bucket compiles so
            # OTHER servers' dispatch threads in this process get the
            # cores/GIL back between bursts — a fleet building a new
            # version next to live traffic warms gently; standalone
            # startup (nothing else serving) keeps the default 0
            throttle = float(warmup_throttle_ms) / 1e3
            for i, b in enumerate(self._buckets):
                if throttle and i and b not in self._compiled:
                    time.sleep(throttle)
                self._ensure_compiled(b)
        self._warmup_done = True

        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name='paddle-tpu-batch-dispatch',
            daemon=True)
        self._collector = threading.Thread(
            target=self._collect_loop, name='paddle-tpu-batch-collect',
            daemon=True)
        self._dispatcher.start()
        self._collector.start()

    @classmethod
    def from_program(cls, feed_specs, target_vars, executor=None,
                     main_program=None, scope=None, max_batch=None,
                     path_dir=None, **kw):
        """Export the bucket ladder for a program and serve it, in one
        call.  ``feed_specs`` are per-request example shapes (no batch
        axis); remaining kwargs pass through to the constructor."""
        owned = path_dir is None
        path_dir = path_dir or tempfile.mkdtemp(
            prefix='paddle_tpu_buckets_')
        paths = export_bucketed(path_dir, feed_specs, target_vars,
                                executor=executor,
                                main_program=main_program, scope=scope,
                                max_batch=max_batch)
        srv = cls(paths, **kw)
        if owned:
            srv._owned_dir = path_dir  # removed by close()
        return srv

    # -- client surface ------------------------------------------------
    def submit(self, feed, request_id=None):
        """Enqueue one request; returns a Future of [output arrays],
        each keeping the request's leading row count.  Blocks only when
        the request queue is full (backpressure).  After :meth:`drain`
        or :meth:`close` this raises ``RuntimeError`` immediately — a
        request must never enqueue behind a dispatcher that is retiring
        (its Future would hang the caller forever).

        ``request_id`` threads an upstream id (the fleet dispatcher's)
        through the dispatch spans in the flight-recorder timeline; by
        default each request gets this server's next monotonic id."""
        norm, rows = self._normalize(feed)
        rid = (next(self._req_seq) if request_id is None
               else request_id)
        req = _Request(norm, rows, time.perf_counter(), rid)
        with self._cv:
            self._check_accepting()
            while (len(self._pending) >= self.max_queue
                   and not self._closed and not self._draining):
                self._cv_space.wait(0.1)
            self._check_accepting()
            self._pending.append(req)
            self._pending_rows += rows
            self._m.submitted.inc()
            self._m.queue_depth.set(len(self._pending))
            # wake the dispatcher only on the transitions it can act on:
            # first work after idle, or a bucket's worth accumulated.
            # In between it sleeps on its own linger/deadline timer —
            # per-submit wakeups were the dominant GIL cost under load
            if len(self._pending) == 1 or \
                    self._pending_rows >= self.max_batch:
                self._cv.notify()
        return req.future

    def _check_accepting(self):
        """Raise the clear post-retirement error.  Caller holds _cv."""
        if self._closed:
            raise RuntimeError(
                "BatchingInferenceServer is closed; submit() after "
                "close() is rejected (the dispatcher is gone and the "
                "request's Future would never complete)")
        if self._draining:
            raise RuntimeError(
                "BatchingInferenceServer is draining; it no longer "
                "accepts new requests (queued and in-flight work is "
                "being flushed before retirement)")

    def predict(self, feed, timeout=None):
        """submit + wait: returns [output arrays] for this request."""
        return self.submit(feed).result(timeout)

    def queue_state(self):
        """Cheap live snapshot of the dispatch queue — the routing
        signal a fleet dispatcher polls per submit: requests and rows
        waiting to be batched, batches in flight on the device, and
        whether this server is still accepting work.  One lock
        acquisition, no registry reads."""
        with self._cv:
            return {
                'queued_requests': len(self._pending),
                'queued_rows': self._pending_rows,
                'in_flight_batches': self._in_flight,
                'accepting': not (self._closed or self._draining),
            }

    def drain(self, timeout=30.0):
        """Stop accepting new requests and flush what is already here:
        every queued and in-flight request still completes (partial
        batches launch immediately — no linger / deadline wait), but
        any further ``submit()`` raises.  Unlike :meth:`close` the
        worker threads, compiled buckets, and metrics stay alive, so a
        fleet can retire a replica without dropping queued requests and
        still read its final ``stats()``.  Returns True when the queue
        fully drained within ``timeout`` seconds (False means work was
        still in flight — the caller may retry or close() anyway,
        which keeps flushing).  Idempotent; drain-then-close is the
        graceful retirement sequence."""
        with self._cv:
            self._draining = True
            self._cv.notify()           # wake the dispatcher to flush
            self._cv_space.notify_all()  # unblock backpressured submits
        deadline = time.perf_counter() + timeout
        while True:
            with self._cv:
                if not self._pending and self._in_flight == 0:
                    return True
            if time.perf_counter() >= deadline:
                return False
            time.sleep(0.002)

    def stats(self):
        """The same dict shape as before the observability rebase; the
        values now read back from registry metrics (p50/p99 are
        bucket-interpolated histogram quantiles rather than exact
        order statistics over a sliding window).

        The end-to-end latency additionally splits into its two spans —
        ``queue_wait_*`` (submit to dispatch) and ``compute_*``
        (dispatch to host sync, per batch) — overall and under
        ``per_bucket`` keyed by dispatched bucket size.  These read the
        same histograms the fleet dispatcher's routing signal and
        bench_serving report, so all three agree by construction."""
        with self._cv:
            depth = len(self._pending)
            in_flight = self._in_flight
        m = self._m
        batches = m.batches.value
        rows_sum = m.batch_rows.value
        capacity_sum = m.batch_capacity.value
        qw, comp = m.queue_wait('all'), m.compute('all')
        per_bucket = {}
        for b in m.observed_buckets():
            bq, bc = m.queue_wait(b), m.compute(b)
            per_bucket[b] = {
                'queue_wait_p50_ms': bq.quantile(0.5) * 1e3,
                'queue_wait_p99_ms': bq.quantile(0.99) * 1e3,
                'compute_p50_ms': bc.quantile(0.5) * 1e3,
                'compute_p99_ms': bc.quantile(0.99) * 1e3,
                'batches': int(bc.count),
            }
        return {
            'queue_depth': depth,
            'in_flight_batches': in_flight,
            'requests_submitted': int(m.submitted.value),
            'requests_completed': int(m.completed.value),
            'batches': int(batches),
            'mean_batch_occupancy':
                rows_sum / batches if batches else 0.0,
            'mean_bucket_fill':
                rows_sum / capacity_sum if capacity_sum else 0.0,
            'compiles': int(m.compiles.value),
            'compiles_after_warmup':
                int(m.compiles_after_warmup.value),
            'p50_latency_ms': m.latency.quantile(0.5) * 1e3,
            'p99_latency_ms': m.latency.quantile(0.99) * 1e3,
            'queue_wait_p50_ms': qw.quantile(0.5) * 1e3,
            'queue_wait_p99_ms': qw.quantile(0.99) * 1e3,
            'compute_p50_ms': comp.quantile(0.5) * 1e3,
            'compute_p99_ms': comp.quantile(0.99) * 1e3,
            'per_bucket': per_bucket,
            'buckets': list(self._buckets),
        }

    def resident_bytes(self):
        """Modeled HBM residency of this servable: what serving this
        bucket ladder keeps resident on the device.  Per bucket, the
        artifact's serialized size (StableHLO module + the params baked
        into it as constants — each bucket bakes its OWN copy) plus the
        compiled executable's XLA ``memory_analysis()`` components
        (argument/output/temp buffers, generated code) when the bucket
        has compiled.  The sum over the ladder is the per-servable
        estimate the fleet's ``paddle_tpu_serving_resident_bytes``
        gauges and the deploy() HBM-budget precheck read.

        ``servable_key`` identifies the SHARED compiled servable:
        in-process replicas built with ``share_artifacts_with=`` report
        the same key, so a fleet aggregate can count the one servable
        once instead of once per dispatch lane."""
        per_bucket = {}
        total = 0
        for b in self._buckets:
            e = {'compiled': b in self._compiled}
            # artifact bytes count only while the bucket's artifact is
            # actually loaded (an evicted bucket keeps its file on
            # disk but holds nothing resident)
            p = self._bucket_paths.get(b)
            if p and b in self._servers:
                try:
                    e['artifact_bytes'] = os.path.getsize(p)
                except OSError:
                    pass
            fn = self._compiled.get(b)
            if fn is not None:
                try:
                    ma = fn.memory_analysis()
                except Exception:
                    ma = None
                if ma is not None:
                    e['argument_bytes'] = int(ma.argument_size_in_bytes)
                    e['output_bytes'] = int(ma.output_size_in_bytes)
                    e['temp_bytes'] = int(ma.temp_size_in_bytes)
                    e['code_bytes'] = int(
                        ma.generated_code_size_in_bytes)
            e['estimate_bytes'] = (
                e.get('artifact_bytes', 0) + e.get('argument_bytes', 0)
                + e.get('output_bytes', 0) + e.get('temp_bytes', 0)
                + e.get('code_bytes', 0))
            total += e['estimate_bytes']
            per_bucket[b] = e
        return {
            'total_bytes': int(total),
            'per_bucket': per_bucket,
            'servable_key': id(self._compiled),
            'basis': 'per-bucket artifact size (serialized module + '
                     'baked params) + compiled argument/output/temp/'
                     'code bytes from XLA memory_analysis, summed '
                     'over the ladder',
        }

    def close(self, timeout=10.0):
        """Stop accepting requests, flush what is queued, and join the
        worker threads."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._stopping = True
            self._cv.notify()
            self._cv_space.notify_all()
        self._dispatcher.join(timeout)
        self._collector.join(timeout)
        self._m.close()  # retire this server's metric series
        if self._owned_dir:
            import shutil
            shutil.rmtree(self._owned_dir, ignore_errors=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- batch formation (pure, unit-testable) -------------------------
    def _bucket_for(self, rows):
        """Smallest ladder bucket holding ``rows`` rows."""
        for b in self._buckets:
            if b >= rows:
                return b
        raise ValueError("rows=%d exceeds max_batch=%d"
                         % (rows, self.max_batch))

    def _normalize(self, feed):
        """Validate one request against the exported feed signature and
        cast to the artifact dtypes (in the caller's thread, so host-side
        conversion cost spreads across clients).  Returns
        ({name: (rows,)+example array}, rows)."""
        if len(feed) != len(self._feed_names):
            raise ValueError(
                "feed names %s do not match the exported signature %s"
                % (sorted(feed), self._feed_names))
        norm, rows = {}, None
        for n in self._feed_names:
            try:
                arr = feed[n]
            except KeyError:
                raise ValueError(
                    "feed is missing %r; the exported signature is %s"
                    % (n, self._feed_names))
            ex = self._example_shapes[n]
            if type(arr) is not np.ndarray:
                arr = np.asarray(arr)
            shape = arr.shape
            if shape == ex:
                arr, k = arr[None], 1
            elif len(shape) == len(ex) + 1 and shape[1:] == ex:
                k = shape[0]
            else:
                raise ValueError(
                    "feed %r has shape %s; expected the example shape %s "
                    "or (rows,) + %s" % (n, shape, ex, ex))
            if k == 0:
                raise ValueError(
                    "feed %r carries 0 rows; empty requests cannot be "
                    "batched" % n)
            if rows is None:
                rows = k
            elif k != rows:
                raise ValueError(
                    "feed rows disagree across names: %r has %d, others "
                    "have %d" % (n, k, rows))
            if arr.dtype != self._dtypes[n]:
                arr = arr.astype(self._dtypes[n])
            norm[n] = arr
        if rows > self.max_batch:
            raise ValueError(
                "request carries %d rows > max_batch %d; split it"
                % (rows, self.max_batch))
        return norm, rows

    def _assemble(self, reqs):
        """Form one device batch from requests: concatenate rows, pick
        the smallest bucket that fits, pad up to it by replicating the
        last real row.  The validity mask is realized as per-request
        (lo, hi) row slices — rows >= offsets[-1][1] are padding and are
        never returned to any request."""
        offsets, lo = [], 0
        for r in reqs:
            offsets.append((lo, lo + r.rows))
            lo += r.rows
        rows = lo
        bucket = self._bucket_for(rows)
        stacked = {}
        for n in self._feed_names:
            parts = [r.feed[n] for r in reqs]
            pad = bucket - rows
            if pad:
                parts.append(np.broadcast_to(
                    parts[-1][-1:],
                    (pad,) + self._example_shapes[n]))
            stacked[n] = (np.concatenate(parts, axis=0)
                          if len(parts) > 1 else parts[0])
        return bucket, stacked, offsets

    # -- compile management --------------------------------------------
    def _aot_key(self, bucket):
        """This bucket's AOT-cache key: the artifact's content digest
        (standing in for the composite plan key — the exported module
        embeds the pass pipeline's output and the baked params) +
        bucket + device kind + jax version.  Digests memoize per
        bucket and are shared across sibling servers."""
        digest = self._aot_digests.get(bucket)
        if digest is None:
            digest = artifact_digest(self._bucket_paths[bucket])
            self._aot_digests[bucket] = digest
        return self._aot.key(digest, bucket)

    def _ensure_compiled(self, bucket):
        """AOT-compile (lower + compile) the bucket's artifact call.  The
        serving loop only calls these executables — an AOT executable
        hard-rejects any other shape/dtype, so 'compiled at warmup' is a
        guarantee, not a hope.  Compiles after warmup are counted:
        nonzero means the ladder missed a shape and the loop stalled.

        Two fast paths skip the compile entirely: a bucket evicted by
        the HBM budget manager re-opens its (never-deleted) artifact
        here before re-warming, and a warm AOT cache entry
        (PADDLE_TPU_AOT_CACHE_DIR) deserializes the stored executable
        — a cache hit performs ZERO compiles and leaves the compile
        counters untouched, which is what makes a fresh process's
        deploy() counter-pinned at 0 on a warm disk cache.  A corrupt
        entry is counted by the cache and falls through to the normal
        compile, never a crash."""
        fn = self._compiled.get(bucket)
        if fn is None:
            srv = self._servers.get(bucket)
            if srv is None:
                # evicted earlier: the version dir outlives eviction
                # by contract, so re-open the artifact and re-warm
                # through the ordinary path below
                srv = InferenceServer(self._bucket_paths[bucket])
                self._servers[bucket] = srv
            if self._aot is not None:
                fn = self._aot.load_compiled(self._aot_key(bucket))
            if fn is None:
                zeros = {n: np.zeros(
                    (bucket,) + self._example_shapes[n],
                    self._dtypes[n]) for n in self._feed_names}
                with _obs.span('serving.bucket_compile'):
                    fn = srv._call.lower(zeros, srv._key).compile()
                self._m.compiles.inc()
                if self._warmup_done:
                    self._m.compiles_after_warmup.inc()
                if self._aot is not None:
                    self._aot.store(
                        self._aot_key(bucket), fn,
                        artifact=self._bucket_paths.get(bucket),
                        bucket=bucket)
            self._compiled[bucket] = fn
            self._res_gen[0] += 1
        return fn

    def evict_buckets(self, buckets=None):
        """The HBM budget manager's eviction unit: drop the compiled
        executable AND the deserialized artifact for the given buckets
        (default: the whole ladder).  The version directory is never
        touched — the next request for an evicted bucket re-opens the
        artifact and re-compiles through :meth:`_ensure_compiled`
        (counted as a normal post-warmup compile).  Affects every
        sibling sharing this servable, by design: the executables are
        one shared residency.  Returns the modeled bytes freed
        (resident_bytes delta).  Safe against in-flight batches: a
        launch holds its own references, so dropping the dict entries
        frees memory only once the last batch on the executable
        completes."""
        before = self.resident_bytes()['total_bytes']
        targets = (list(self._buckets) if buckets is None
                   else [int(b) for b in buckets])
        for b in targets:
            self._compiled.pop(b, None)
            self._servers.pop(b, None)
        self._res_gen[0] += 1
        return max(0, before - self.resident_bytes()['total_bytes'])

    def bucket_last_used(self):
        """{bucket: last dispatch stamp (time.monotonic)} across every
        sibling lane of this servable — buckets never dispatched are
        absent.  The budget manager's per-bucket LRU signal."""
        return dict(self._bucket_used)

    @property
    def residency_generation(self):
        """Bumped whenever the servable's residency changes (evict or
        post-warmup (re)compile); the fleet invalidates its cached
        resident_bytes() snapshots against it."""
        return self._res_gen[0]

    # -- worker threads ------------------------------------------------
    def _pop_batch(self):
        """Pop the longest prefix of the pending queue that fits
        max_batch.  Caller holds _cv."""
        batch, rows = [], 0
        while self._pending:
            r = self._pending[0]
            if rows + r.rows > self.max_batch:
                break
            batch.append(self._pending.popleft())
            rows += r.rows
        self._pending_rows -= rows
        self._m.queue_depth.set(len(self._pending))
        return batch

    def _flush_now(self, grew_full, t_first, now):
        """The dispatch policy.  Caller holds _cv."""
        if self._in_flight >= 2:
            return False  # double-buffer window full: wait for a sync
        if grew_full:
            return True   # bucket can't grow: launch immediately
        if self._draining or self._stopping:
            return True   # retiring: flush partials, don't linger
        if self._in_flight == 0 and now - t_first >= self.linger:
            return True   # device idle: don't hoard a partial batch
        return now - t_first >= self.max_wait  # deadline flush

    def _dispatch_loop(self):
        while True:
            with self._cv:
                while True:
                    if self._stopping and not self._pending:
                        self._inflight_q.put(_STOP)
                        return
                    if self._pending:
                        now = time.perf_counter()
                        t_first = self._pending[0].t_submit
                        grew_full = (self._pending_rows
                                     >= self.max_batch)
                        if self._flush_now(grew_full, t_first, now):
                            batch = self._pop_batch()
                            self._in_flight += 1
                            self._m.in_flight.set(self._in_flight)
                            self._cv_space.notify_all()  # queue space
                            break
                        if self._in_flight >= 2:
                            # saturated: only a completion can unblock
                            # us, and the collector notifies then
                            self._cv.wait()
                            continue
                        # sleep until the nearest applicable deadline;
                        # full buckets and batch completions notify us
                        wake = t_first + self.max_wait - now
                        if self._in_flight == 0:
                            wake = min(wake,
                                       t_first + self.linger - now)
                        self._cv.wait(max(wake, 1e-4))
                    else:
                        self._cv.wait()
            self._launch(batch)

    def _launch(self, reqs):
        """Stage + dispatch one batch without waiting for its result.
        jax dispatch is async, so control returns here while the device
        runs; the next iteration's device_put overlaps that execution
        (double buffering), and the collector owns the sync."""
        try:
            bucket, stacked, offsets = self._assemble(reqs)
            fn = self._ensure_compiled(bucket)
            self._bucket_used[bucket] = time.monotonic()
            srv = self._servers.get(bucket)
            if srv is None:
                # an eviction raced the window since _ensure_compiled:
                # the executable in hand stays valid, only the _key
                # holder needs re-opening
                srv = InferenceServer(self._bucket_paths[bucket])
                self._servers[bucket] = srv
            if self._stage_to_device:
                stacked = jax.device_put(stacked)
            outs = list(fn(stacked, srv._key))
        except Exception as e:
            # crash forensics for the dispatch thread (the executor
            # path's PADDLE_TPU_TRACE_DUMP_ON_ERROR contract extended
            # to serving): dump the ring tagged with this server's id.
            # maybe_dump_on_error never raises — the clients' futures
            # carry the ORIGINAL error either way
            _tlm.maybe_dump_on_error(tag=self._m._sid)
            for r in reqs:
                r.future.set_exception(e)
            with self._cv:
                self._in_flight -= 1
                self._m.in_flight.set(self._in_flight)
                self._cv.notify()
            return
        rows = offsets[-1][1]
        t_launch = time.perf_counter()
        tl = _tlm.ring_if_armed()
        if tl is not None:
            # per-request queue-wait regions: submit -> dispatch,
            # tagged with the threaded request id and the bucket the
            # request rode out in (Perfetto shows wait vs compute)
            for r in reqs:
                tl.record('serving.queue_wait', 'span',
                          t0=r.t_submit, dur=t_launch - r.t_submit,
                          args={'request_id': r.rid, 'bucket': bucket,
                                'server': self._m._sid})
        self._m.batches.inc()
        self._m.batch_rows.inc(rows)
        self._m.batch_capacity.inc(bucket)
        self._m.occupancy.observe(rows)
        # queue wait ends at dispatch: per request, labeled by the
        # bucket it rode out in (plus the "all" rollup)
        qw_b = self._m.queue_wait(bucket)
        qw_all = self._m.queue_wait('all')
        for r in reqs:
            w = t_launch - r.t_submit
            qw_b.observe(w)
            qw_all.observe(w)
        self._inflight_q.put((outs, reqs, offsets, bucket, t_launch))

    def _collect_loop(self):
        while True:
            item = self._inflight_q.get()
            if item is _STOP:
                return
            outs, reqs, offsets, bucket, t_launch = item
            try:
                host = [np.asarray(o) for o in outs]
            except Exception as e:  # pragma: no cover - defensive
                _tlm.maybe_dump_on_error(tag=self._m._sid)
                for r in reqs:
                    r.future.set_exception(e)
                with self._cv:
                    self._in_flight -= 1
                    self._m.in_flight.set(self._in_flight)
                    self._cv.notify()
                continue
            # the device is done: open the dispatch window BEFORE fanning
            # results out, so the next batch stages while clients wake
            with self._cv:
                self._in_flight -= 1
                self._m.in_flight.set(self._in_flight)
                self._cv.notify()
            now = time.perf_counter()
            # compute span = dispatch to host sync, one sample per batch
            self._m.compute(bucket).observe(now - t_launch)
            self._m.compute('all').observe(now - t_launch)
            tl = _tlm.ring_if_armed()
            if tl is not None:
                tl.record('serving.compute', 'compute', t0=t_launch,
                          dur=now - t_launch,
                          args={'bucket': bucket,
                                'rows': offsets[-1][1],
                                'server': self._m._sid,
                                'request_ids': [r.rid for r in reqs]})
            self._m.completed.inc(len(reqs))
            for r in reqs:
                self._m.latency.observe(now - r.t_submit)
            for r, (lo, hi) in zip(reqs, offsets):
                # copy partial slices: a view would pin the whole
                # bucket-sized output (all co-batched rows + padding)
                # for as long as any client holds its result
                r.future.set_result(
                    [h[lo:hi] if hi - lo == h.shape[0]
                     else h[lo:hi].copy() for h in host])

"""A5 — env-var configuration registry (gflags parity).

Reference parity: gflags definitions scattered through the C++ core
(FLAGS_check_nan_inf, FLAGS_fraction_of_gpu_memory_to_use, ...) set via
environment.  Here every flag is `PADDLE_TPU_<NAME>` in the environment,
declared with a type and default, and read through the global `FLAGS`.
"""
import os

__all__ = ['FLAGS', 'DEFINE_bool', 'DEFINE_int', 'DEFINE_float',
           'DEFINE_string']

_TRUE = ('1', 'true', 'yes', 'on')


class _Flags(object):
    def __init__(self):
        self._defs = {}

    def _define(self, name, default, parser, help_str):
        self._defs[name] = (default, parser, help_str)

    def __getattr__(self, name):
        defs = object.__getattribute__(self, '_defs')
        if name not in defs:
            raise AttributeError("flag %r was never defined" % name)
        default, parser, _ = defs[name]
        env = os.environ.get('PADDLE_TPU_' + name.upper())
        if env is None:
            return default
        return parser(env)

    def declared(self):
        return {n: getattr(self, n) for n in self._defs}

    def definitions(self):
        """{name: (default, help_str)} for every declared flag — the
        introspection surface tools/check_flags_doc.py audits against
        README.md (every flag must be documented in both places)."""
        return {n: (d, h) for n, (d, _, h) in self._defs.items()}

    def help(self):
        return '\n'.join(
            'PADDLE_TPU_%s (default %r): %s' % (n.upper(), d, h)
            for n, (d, _, h) in sorted(self._defs.items()))


FLAGS = _Flags()


def DEFINE_bool(name, default, help_str=''):
    FLAGS._define(name, default, lambda s: s.lower() in _TRUE, help_str)


def DEFINE_int(name, default, help_str=''):
    FLAGS._define(name, default, int, help_str)


def DEFINE_float(name, default, help_str=''):
    FLAGS._define(name, default, float, help_str)


def DEFINE_string(name, default, help_str=''):
    FLAGS._define(name, default, str, help_str)


# -- core flags (reference gflags counterparts) ---------------------------
DEFINE_bool('check_nan_inf', False,
            'arm jax_debug_nans: fault on the first NaN-producing op '
            '(FLAGS_check_nan_inf)')
DEFINE_bool('synth_data', True,
            'datasets serve deterministic synthetic samples (zero-egress '
            'environments)')
DEFINE_int('reader_buf_size', 64,
           'prefetch depth for buffered/xmap readers')
DEFINE_string('profile_dir', '/tmp/paddle_tpu_prof',
              'where profiler traces are written')
DEFINE_bool('use_native_runtime', True,
            'use the C++ dataio prefetcher when the extension builds')
DEFINE_bool('metrics_enabled', True,
            'arm the observability registry (paddle_tpu.observability): '
            'executor plan-cache/compile counters, serving queue/latency '
            'histograms, reader sample counters, and span() timings.  '
            '0 disables every instrumented path at one cached-bool cost '
            '(no registry allocation on the executor hot path)')
DEFINE_int('metrics_port', 0,
           'when >0, serving runtimes expose GET /metrics (Prometheus '
           'text exposition 0.0.4) and /healthz on this port via a '
           'stdlib daemon-thread HTTP server '
           '(observability.serve_metrics / maybe_serve_from_env).  '
           '0 (default) serves nothing')
DEFINE_string('metrics_host', '127.0.0.1',
              'bind address for the /metrics endpoint.  Defaults to '
              'loopback — the listener is unauthenticated, so binding '
              'wider (0.0.0.0 for a scrape sidecar/k8s probe) is a '
              'deliberate choice, not the default')
DEFINE_int('profiler_event_cap', 40000,
           'max RecordEvent/profile-region entries the profiler retains '
           '(deque maxlen; oldest drop first) so long-lived serving '
           'processes using RecordEvent do not leak memory.  <=0 means '
           'unbounded; takes effect at import or on reset_profiler()')
DEFINE_int('graph_opt_level', 2,
           'graph-optimization pass pipeline applied to every program '
           'block on a plan-cache miss, before tracing '
           '(transpiler/passes.py): 0 disables, 1 runs dead-op '
           'elimination only, 2 (default) adds constant folding and '
           'common-subexpression elimination.  Re-read on every plan '
           'build and part of the plan cache key, so flips (including '
           'after Executor.reset_cache()) take effect without a '
           'restart.  Levels 0 and 1 are fetch-exact; level 2 is '
           'numerically equivalent (folded constants are evaluated '
           'eagerly, so fused rounding in consumers can differ at ulp '
           'scale)')
DEFINE_string('sparse_apply', 'xla',
              'lowering for the row-wise sparse optimizer apply '
              '(SelectedRows grads in sgd/adagrad/adam): "xla" (default) '
              'keeps the .at[rows].add scatter path; "pallas" runs the '
              'row-walking Pallas table-update kernels '
              '(ops/pallas/table_update.py, interpret mode off-TPU), '
              'which compile and match on a v5e but measured slower '
              'there (PERF.md, chip bring-up) and which jax refuses '
              'inside a PADDLE_TPU_MESH step outside the embedding '
              'engine\'s shard_map.  Resolved per trace and part of the '
              'executor plan cache key, so flips take effect on the '
              'next plan build')
DEFINE_bool('device_prefetch', False,
            'device-resident double-buffered feed for '
            'Executor.run_steps with per-step feeds: the K-step feed '
            'stack is staged in chunks, and the host device_puts '
            'chunk c+1 while the device scans chunk c, so steady-state '
            'steps see zero blocking host transfers (counters '
            'paddle_tpu_executor_feed_blocking_puts_total / '
            '_feed_prefetched_bytes_total prove it) and only ~2 chunks '
            'of feed are resident in HBM instead of the whole [K, ...] '
            'stack.  Off (default) stages the full stack in one '
            'blocking put before dispatch.  Re-read on every '
            'run_steps call (and after Executor.reset_cache()); '
            'numerics are bitwise-identical either way')
DEFINE_int('device_prefetch_chunk', 0,
           'steps per staged chunk when PADDLE_TPU_DEVICE_PREFETCH is '
           'on; 0 (default) auto-sizes to ~K/4 (min 1) so the pipeline '
           'keeps one chunk in flight while one computes.  Each chunk '
           'size compiles its own scan plan (cached like every other '
           'plan)')
DEFINE_string('amp', '0',
              'automatic mixed-precision training pass '
              '(transpiler/amp.py), applied per plan build after the '
              'graph-opt pipeline: "bf16" runs white-listed ops '
              '(matmul/conv/attention/RNN gates — registry.AMP_WHITE) '
              'in bfloat16 with f32 master weights in the Scope; "f16" '
              'uses float16 and additionally wires dynamic loss '
              'scaling (scale the loss, unscale grads, skip the '
              'optimizer step on non-finite grads, grow/backoff the '
              'scale).  "0" (default) is off and bitwise-identical to '
              'not having the pass.  Re-read on every plan build and '
              'part of the executor plan-cache key, so flips take '
              'effect without a restart')
DEFINE_float('amp_init_loss_scale', 32768.0,
             'f16 mode: initial dynamic loss scale (2^15)')
DEFINE_int('amp_incr_every_n_steps', 1000,
           'f16 mode: consecutive finite steps before the loss scale '
           'doubles')
DEFINE_int('amp_decr_every_n_nan_or_inf', 2,
           'f16 mode: consecutive non-finite steps before the loss '
           'scale halves')
DEFINE_int('fleet_replicas', 2,
           'default replica count for inference.ServingFleet when the '
           'constructor is not passed replicas= explicitly: the fleet '
           'starts this many BatchingInferenceServer replicas behind '
           'its dispatcher, and deploy() builds the same number for '
           'the incoming version.  Only read by the fleet layer — a '
           'bare BatchingInferenceServer never consults it, so the '
           'single-replica serving path is untouched when no fleet is '
           'constructed')
DEFINE_int('fleet_unroutable_after', 3,
           'consecutive dispatch failures before the fleet marks a '
           'replica UNROUTABLE and stops routing to it.  Failed '
           'requests are re-dispatched onto healthy replicas (up to '
           'PADDLE_TPU_FLEET_RETRY_LIMIT), so clients see results, not '
           'errors; the health-check loop keeps probing the replica '
           'and restores it on the first successful probe')
DEFINE_int('fleet_retry_limit', 2,
           'how many times one request is re-dispatched onto a '
           'DIFFERENT replica after a dispatch failure before the '
           'client future finally carries the error.  Each retry '
           'excludes every replica the request already failed on')
DEFINE_float('fleet_health_interval_ms', 250.0,
             'period of the ServingFleet health-check loop: every '
             'interval it probes each UNROUTABLE replica with a '
             'synthetic single-row request (zeros at the exported feed '
             'signature) and marks the replica routable again on '
             'success.  <=0 disables the loop (unroutable replicas '
             'then stay out until remove/replace)')
DEFINE_float('fleet_drain_timeout_s', 30.0,
             'seconds a retiring replica is given to finish queued + '
             'in-flight requests (BatchingInferenceServer.drain) '
             'before the fleet closes it anyway — bounds how long '
             'remove_replica(), deploy() old-version retirement, and '
             'fleet.close() can block on a stuck replica')
DEFINE_string('fleet_hbm_admission', 'warn',
              "ServingFleet HBM budget mode.  'warn' (default): an "
              'over-budget deploy() is logged and counted but '
              'proceeds (the PR-10 precheck behavior).  '
              "'enforce': the budget manager first LRU-evicts cold "
              'tenants\' compiled buckets to make room and, when the '
              'projection still does not fit, rejects the deploy with '
              'a typed tenancy.AdmissionError BEFORE any replica '
              'build cost is paid')
DEFINE_int('fleet_tenant_quota', 0,
           'base outstanding-request quota per fleet tenant, scaled '
           'by SLO-class weight (gold keeps the full base, silver '
           'base/2, bronze base/8, min 1).  A tenant at its quota has '
           'further submits parked on a per-tenant queue and drained '
           'in SLO-weighted round-robin order as slots free up — '
           'deferred, never dropped.  0 (default) disables quota '
           'gating entirely')
DEFINE_string('aot_cache_dir', '',
              'root directory of the serving AOT-executable cache '
              '(entries live under <dir>/paddle_tpu_aot).  Each '
              'warmed bucket\'s compiled executable is serialized '
              'there (jax serialize_executable) so a brand-new '
              'PROCESS deploys by deserializing instead of '
              'trace+compile: zero warmup compiles on a warm cache.  '
              'Empty (default) disables AOT serialization: unlike the '
              'tuner cache it does not fall back to the compile-cache '
              'directory, because deserializing fails when the process '
              'has more devices than the executable was built for '
              '(ROADMAP D10)')
DEFINE_string('verify_ir', 'boundary',
              'static program verifier over the pass-manager rewrite '
              'pipeline (transpiler/verify.py): "boundary" (default) '
              'checks the final rewritten block once per plan build — '
              'def-before-use, op signatures vs the registry, declared '
              'dtype/shape vs re-inference, op_seq monotonicity, pinned-'
              'name and AMP-cast invariants, donation-ordering safety; '
              '"every_pass" re-checks after each pass and attributes a '
              'failure to the offending pass (debug mode, used by the '
              'mutation tests); "off" skips verification and restores '
              'the pre-verifier plan-build path verbatim.  Re-read on '
              'every plan build and part of the composite plan-cache '
              'key, so flips take effect without a restart')
DEFINE_string('trace_dir', '',
              'arm the step-timeline flight recorder '
              '(observability/timeline.py) and export it here: the '
              'executor records per-step phase events (feed staging, '
              'compile, dispatch, scope update, prefetch overlap) into '
              'the bounded event ring and, after every run_steps call, '
              'writes the ring as Chrome trace_event JSON '
              '(trace_<pid>.json, atomic replace) loadable in Perfetto '
              'or chrome://tracing.  Empty (default) records nothing on '
              'the executor paths — one cached-bool check per call, the '
              'same zero-cost contract as PADDLE_TPU_METRICS_ENABLED=0. '
              'The ring is shared with the legacy profiler RecordEvent '
              'API and bounded by PADDLE_TPU_PROFILER_EVENT_CAP')
DEFINE_int('trace_steps', 256,
           'how many trailing steps of timeline events each exported '
           'trace retains (the flight-recorder window for both the '
           'per-run_steps flush and the dump-on-error file).  0 exports '
           'every event still in the ring; the ring itself stays '
           'bounded by PADDLE_TPU_PROFILER_EVENT_CAP either way')
DEFINE_bool('trace_dump_on_error', False,
            'crash forensics: on any executor exception, flush the '
            'last PADDLE_TPU_TRACE_STEPS steps of the timeline ring to '
            'trace_<pid>_error.json under PADDLE_TPU_TRACE_DIR (or '
            'PADDLE_TPU_PROFILE_DIR when no trace dir is set) before '
            're-raising — a long run that dies at step 40k leaves its '
            'final timeline behind.  Arming this also arms timeline '
            'recording even without a trace dir')
DEFINE_int('peak_hbm_bytes', 0,
           'device HBM capacity in bytes for headroom accounting: when '
           '>0, Executor.last_step_report["memory"] adds a headroom '
           'block (modeled and measured peak as a ratio of this '
           'budget), and inference.ServingFleet uses it as the default '
           'hbm_budget_bytes for the deploy() warn-only resident-bytes '
           'precheck.  0 (default) disables both — the memory model '
           'still reports absolute bytes either way.  Set it to the '
           'chip HBM size (e.g. 16 GiB for a v5e core) minus whatever '
           'reserve the runtime claims')
DEFINE_int('online_round_rows', 256,
           'rows per online fine-tune round (paddle_tpu.online.'
           'OnlineTrainer): a round fires once this many clickstream '
           'rows are available (rounded down to whole batches; the '
           'remainder stays unconsumed in the log).  Explicit '
           'steps_per_round= on the trainer overrides it')
DEFINE_float('online_round_window_s', 0.0,
             'time trigger for online fine-tune rounds: when >0, a '
             'round also fires after this many seconds of collecting '
             'even if fewer than PADDLE_TPU_ONLINE_ROUND_ROWS rows '
             'arrived (at least one full batch is still required).  '
             '0 (default) triggers on row count only')
DEFINE_float('online_poll_ms', 25.0,
             'poll period of the clickstream tail reader '
             '(paddle_tpu.online.stream) while waiting for new rows '
             'to be appended to the log')
DEFINE_float('online_auc_floor', 0.55,
             'eval-gate floor for the online controller: a fine-tune '
             'round whose holdout AUC is below this is rejected (the '
             'round\'s checkpoint is rolled back, nothing is '
             'deployed)')
DEFINE_float('online_auc_delta', 0.02,
             'eval-gate regression margin: a candidate whose holdout '
             'AUC is more than this below the serving model\'s AUC on '
             'the SAME holdout is rejected even when it clears the '
             'floor')
DEFINE_float('online_freshness_slo_s', 0.0,
             'freshness SLO for the online-serving loop: when >0, the '
             'controller counts a violation '
             '(paddle_tpu_online_freshness_slo_violations_total) '
             'whenever the serving model\'s age — time since the data '
             'its version was trained on — exceeds this many seconds, '
             'and the /healthz endpoint reports degraded for the '
             'duration.  The age itself is always exported as the '
             'paddle_tpu_online_model_age_seconds gauge.  0 (default) '
             'disables the SLO check')
DEFINE_int('online_keep_versions', 4,
           'export-dir retention for promoted online versions: after '
           'each promote, io.gc_versions prunes numbered version dirs '
           'beyond the newest N, never touching the fleet\'s live '
           'version or its .prev rollback target')
DEFINE_string('mesh', '',
              'SPMD device mesh for whole-train-step pjit lowering, as '
              'comma-separated axis=size pairs over the canonical axis '
              'vocabulary dp (data), fsdp (params+optimizer-state '
              'sharding), tp (tensor parallel), pp (pipeline stages): '
              'e.g. "dp=2", "dp=4,tp=2", "fsdp=8", or the compact '
              'form "pp2,fsdp2".  When set, the executor builds a '
              'jax Mesh over the first prod(sizes) devices, the '
              'sharding-propagation pass (transpiler/sharding.py) '
              'stamps per-op input/output PartitionSpecs on the plan '
              'IR, and the whole step jits with the resulting '
              'NamedShardings: feeds batch-shard over dp (or fsdp when '
              'no dp axis exists), fsdp shards every divisible '
              'parameter AND its optimizer accumulators, tp follows '
              'the TensorParallelTranspiler plan, and gradient '
              'allreduce lowers to ICI collectives inside the one '
              'compiled step.  A pp axis routes through the 1F1B '
              'engine instead (distributed/pipeline.from_mesh) — the '
              'plain SPMD path refuses it with an actionable error.  '
              'Empty (default) is off — bitwise the '
              'pre-mesh executor.  Re-read per plan build and part of '
              'the composite plan-cache key, so flips take effect '
              'without a restart.  CPU smoke: force host devices with '
              'XLA_FLAGS=--xla_force_host_platform_device_count=8')
DEFINE_float('ici_gbps', 0.0,
             'modeled ICI link bandwidth in GB/s for the collective '
             'cost term: when >0, the executor annotates the '
             '"collective" phase of last_step_report (and the '
             'timeline event) with an estimated wall time = modeled '
             'ICI bytes / this bandwidth, next to the exact byte '
             'count the ring-allreduce closed form produces either '
             'way.  0 (default) reports bytes only — no fake seconds '
             'on hardware whose interconnect was never measured')
DEFINE_string('embed_shard', 'auto',
              'sharded embedding engine '
              '(distributed/embedding_engine.py) under PADDLE_TPU_MESH:'
              ' "auto"/"on" (default) row-shards every lookup_table '
              'weight over the mesh\'s model axes (fsdp/tp, SNIPPETS '
              'SpecLayout embeddings role) and lowers its lookup to '
              'all-to-all of ids -> per-shard local gather -> '
              'all-to-all of rows back, with the sparse optimizer '
              'apply routed per shard onto local rows only; '
              'non-divisible vocab heights sentinel-pad to the next '
              'shard-divisible height (padding_idx semantics preserved '
              'bitwise).  "off" keeps the pre-engine behavior (tables '
              'follow the generic fsdp param rule, lookups stay '
              'single-route).  Without a mesh the flag is inert.  '
              'Re-read per plan build and part of the composite '
              'plan-cache key, so flips take effect without a restart')
DEFINE_int('embed_bucket_tile', 8,
           'tile alignment for the sharded-embedding engine\'s '
           'per-shard id buckets: each shard\'s bucket pads to a '
           'multiple of this many slots with PR-4-style sentinel rows '
           '(skipped by the Pallas apply, dropped by the XLA oracle), '
           'so ragged per-shard id counts compile one bucket shape per '
           'batch size.  Part of the composite plan-cache key')
DEFINE_int('embed_cache_rows', 0,
           'capacity of the hot-row embedding cache '
           '(distributed/embedding_engine.HotRowCache) benches and '
           'serving paths construct for frequency-skewed id traffic: '
           'the top-K observed rows replicate on every device and '
           'serve lookups locally (write-through coherent, eviction '
           'invalidates), so the common case moves zero interconnect '
           'bytes.  0 (default) builds no cache')
DEFINE_bool('lock_debug', False,
            'runtime lock watchdog (paddle_tpu.analysis.lockdebug): '
            'when on, the threaded serving/online modules create '
            'their locks through checking wrappers that record '
            'per-thread acquisition stacks and assert the static '
            'concurrency analyzer\'s lock-acquisition-order graph at '
            'runtime — acquiring B while holding A when B-before-A '
            'holds elsewhere (statically, or earlier in this process) '
            'counts a paddle_tpu_lock_order_violations_total and '
            'records the thread/held-locks/stack for forensics.  Off '
            '(default) the factories return plain threading '
            'primitives: zero added cost, the PR-2 cached-bool '
            'contract.  Read when a lock is CREATED, so flips apply '
            'to servers/fleets/controllers constructed afterwards')
DEFINE_string('tune', 'off',
              'feedback-directed autotuner (paddle_tpu.tuning): "off" '
              '(default) is bitwise the untuned framework — one env '
              'read per executor call, nothing imported; "cached" makes '
              'the executor apply persisted tuner winners for a program '
              '(keyed by plan key + device kind + mesh, from '
              'PADDLE_TPU_TUNE_CACHE_DIR) before its plan builds, so a '
              'fresh process starts tuned with zero search; "search" is '
              'consumed by the bench harness (bench.py --tune search) '
              'to run the cost-model-pruned measured search and persist '
              'the winners.  The executor itself never searches')
DEFINE_string('tune_cache_dir', '',
              'where tuner winners persist (JSON, one file per '
              '(plan key, device kind, mesh) under a paddle_tpu_tuning/ '
              'subdir).  Empty falls back to the compile-cache '
              'directory (compile_cache.py: JAX_COMPILATION_CACHE_DIR '
              'when set, else <checkout>/.jax_cache).  A '
              'corrupted cache file is counted '
              '(paddle_tpu_tune_cache_corrupt_total) and ignored — '
              'defaults apply, nothing crashes')
DEFINE_bool('tune_trace', False,
            'print the autotuner search trace (one line per candidate: '
            'modeled score, measured score, pruned/measured/adopted '
            'and why) to stderr after a bench-driven search — the '
            'attribution record BENCH rows cite')
DEFINE_int('tune_measure_budget', 24,
           'max candidates the autotuner MEASURES per search (pruned '
           'candidates are free; past the budget remaining candidates '
           'are pruned as measure-budget).  Bounds bench wall time on '
           'slow backends')
DEFINE_float('serving_max_wait_ms', 5.0,
             'default deadline flush for BatchingInferenceServer when '
             'the constructor is not passed max_wait_ms= explicitly: '
             'how long the oldest queued request may wait before a '
             'partial batch dispatches anyway.  A registered tunable '
             '(tuning/registry.py) the serving benches can search')
DEFINE_int('serving_max_batch', 8,
           'default bucket-ladder top for export_bucketed / '
           'BatchingInferenceServer.from_program when max_batch= is '
           'not passed explicitly: buckets are powers of two up to '
           'this many rows.  A registered tunable the serving benches '
           'can search')
DEFINE_int('decode_page_size', 16,
           'positions per KV-cache page in the autoregressive decode '
           'engine (inference/decode.py): each stream holds '
           'ceil(context/page_size) pages of the device-resident '
           '[num_pages, page_size, heads, head_dim] pools.  Smaller '
           'pages waste less tail capacity on short streams; larger '
           'ones shrink the page table and the gather fan-out.  A '
           'registered tunable (tuning/registry.py)')
DEFINE_int('decode_max_streams', 8,
           'decode batch slots: how many streams one DecodeEngine '
           'steps concurrently.  The continuous-batching server admits '
           'a queued stream the moment a slot (and pages) free up, at '
           'step granularity.  Fixed at engine build — it is the '
           'compiled decode-step batch shape.  A registered tunable')
DEFINE_int('decode_prefill_bucket', 128,
           'top of the prefill bucket ladder (page-size multiples '
           'doubling up to this): prompts pad to the next bucket so '
           'only ~log2 distinct prefill shapes ever compile; prompts '
           'longer than the top bucket are rejected at submit.  A '
           'registered tunable')
DEFINE_bool('decode_prefix_cache', False,
            'radix/trie prefix cache over the decode engine KV pages '
            '(inference/decode.py): page-aligned prompt prefixes map '
            'to ref-counted cached pages, a hitting stream claims them '
            'by reference and prefilles only the tail (zero MACs for '
            'the shared span); unreferenced pages LRU-evict under pool '
            'pressure.  Enabling switches prefill to the chunked '
            'executables (grid-aligned chunks, bitwise hit-vs-cold). '
            'A registered tunable (tuning/registry.py)')
DEFINE_int('decode_prefill_chunk_tokens', 0,
           'per-tick prefill token budget for chunked prefill in the '
           'decode worker loop: prompts prefill in page-aligned chunks '
           'of up to this many tokens a tick, the last chunk carrying '
           'the tick\'s decode step, so a long prompt no longer stalls '
           'running streams for one monolithic bucket dispatch.  '
           '0 = no per-tick budget (a stream\'s '
           'whole prefill runs at admission; chunked executables are '
           'still used when the prefix cache is on).  A registered '
           'tunable')
DEFINE_int('decode_page_reserve', 2,
           'free-page watermark the decode admission keeps in reserve '
           'when incremental page allocation is active (prefix cache '
           'or chunked prefill on): a stream admits only while '
           'free >= tail_pages + reserve, leaving headroom so running '
           'streams\' claim-as-context-grows page faults rarely hit an '
           'empty pool (exhaustion preempts the youngest stream back '
           'to the queue, recompute-on-resume).  A registered tunable')
DEFINE_float('peak_tflops', 0.0,
             'device peak TFLOP/s for MFU and roofline accounting '
             '(bench.py, benchmarks/common.py, tuning/roofline.py): '
             '0 (default) makes the roofline model fall back to 192 '
             '(the measured sustained square-matmul peak PERF.md '
             'calibrated) while bench MFU columns stay absent unless '
             'the env var is set — the pre-existing contract')
DEFINE_float('hbm_gbps', 0.0,
             'modeled HBM bandwidth in GB/s for the roofline model '
             '(tuning/roofline.py): the bytes-bound op floor is '
             'bytes / this.  0 (default) falls back to 819 GB/s '
             '(v5e HBM).  Only affects modeled numbers — reports, '
             'priors, pruning — never measured ones')
DEFINE_bool('overlap', True,
            'collective-overlap scheduling pass (transpiler/overlap.py,'
            ' registered as overlap_collectives): under a PADDLE_TPU_'
            'MESH with a data/fsdp axis, partition parameter-gradient '
            'allreduce/reduce-scatter into size-bounded buckets '
            '(PADDLE_TPU_OVERLAP_BUCKET_MB) ordered by backward '
            'retirement, group each bucket with an optimization '
            'barrier so XLA fires its collective as soon as the last '
            'producing backward op retires (concurrent with remaining '
            'backward compute), and report overlapped-vs-exposed '
            'comm bytes in the cost model and the collective step '
            'phase.  0 restores the inline-after-backward lowering '
            'bitwise.  dp=1 / no-mesh programs are never touched')
DEFINE_int('overlap_bucket_mb', 25,
           'gradient-bucket payload cap in MiB for the '
           'overlap_collectives pass: smaller buckets fire earlier '
           '(more overlap window) but pay more per-collective latency;'
           ' larger buckets amortize launch cost but serialize behind '
           'the last grad in the bucket.  25 is the PyTorch-DDP '
           'convention the pass defaults to.  A registered tunable '
           '(tuning/registry.py) the mesh benches can search')
DEFINE_int('pp_microbatches', 4,
           'microbatch count M for the pp mesh axis (1F1B pipeline '
           'schedule): the global batch splits into M microbatches '
           'flowing through S=pp stages, with modeled bubble fraction '
           '(S-1)/(M+S-1) reported by the cost model.  Larger M '
           'shrinks the bubble but shrinks per-microbatch work.  '
           'Read by distributed/pipeline.from_mesh and the sharding '
           'pass pp plan block; a registered tunable')


if __name__ == '__main__':
    # `python -m paddle_tpu.flags`: print every declared flag with its
    # env var name, default, and help string
    print(FLAGS.help())

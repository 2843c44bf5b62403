"""Shared timing harness for the secondary benchmarks (SURVEY §5 /
BASELINE.json configs).  Each script builds a train program, feeds a
device-staged synthetic batch, and prints ONE JSON line like bench.py.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench_cli(argv=None):
    """Shared bench flags: ``--tune {off,cached,search}``,
    ``--roofline``, ``--tune-trace``.  Unknown args pass through so
    benches with their own parsers compose (parse_known_args).  The
    defaults honour PADDLE_TPU_TUNE, so ``run_all.py`` children and a
    bare ``python bench_x.py`` under an env opt-in behave alike."""
    import argparse
    p = argparse.ArgumentParser(add_help=False)
    p.add_argument('--tune', choices=('off', 'cached', 'search'),
                   default=os.environ.get('PADDLE_TPU_TUNE') or 'off')
    p.add_argument('--roofline', action='store_true')
    p.add_argument('--tune-trace', action='store_true')
    args, _rest = p.parse_known_args(argv)
    if args.tune_trace:
        os.environ['PADDLE_TPU_TUNE_TRACE'] = '1'
    return args


# flag-scope tunables the generic bench driver searches for a fixed
# program (batch/K live in bench.py, which rebuilds per candidate)
_BENCH_TUNABLES = ('amp', 'device_prefetch_chunk')


def _tune_bench(build, feed_fn, mode, tunables=_BENCH_TUNABLES):
    """Search (or cache-load) tuner winners for one bench program.

    Returns ``(overrides, info)``: env overrides to apply around the
    measured run, and the RESULTS-row attribution dict recording which
    tunables were tuner-chosen vs defaults vs user-pinned."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.tuning import (cache as tcache, registry,
                                   runtime as trt, search as tsearch)

    program, startup, loss = build()
    feed_specs = {k: (tuple(np.asarray(v).shape),
                      str(np.asarray(v).dtype))
                  for k, v in feed_fn().items()}
    key = trt.cache_key_for(program)
    tun = [registry.tunable(n) for n in tunables]

    def model_fn(cfg):
        with registry.applied(cfg):
            return trt.model_program(program,
                                     fetch_names=(loss.name,),
                                     feed_specs=feed_specs)

    k = 40 if on_tpu() else 4

    def measure_fn(cfg):
        # short measured run per surviving candidate: fresh scope +
        # executor under the candidate env, one warm run_steps chain,
        # one timed — the per-phase walls land in last_step_report via
        # the same path the flight recorder instruments
        with registry.applied(cfg):
            scope = fluid.core.scope.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor(fluid.TPUPlace(0) if on_tpu()
                                     else fluid.CPUPlace())
                exe.run(startup)
                feed = feed_fn()
                out = exe.run_steps(program, feed=feed,
                                    fetch_list=[loss], repeat=k,
                                    return_numpy=False)
                jax.block_until_ready(out[0])
                t0 = time.perf_counter()
                out = exe.run_steps(program, feed=feed,
                                    fetch_list=[loss], repeat=k,
                                    return_numpy=False)
                jax.block_until_ready(out[0])
                return (time.perf_counter() - t0) / k

    result = tsearch.autotune(model_fn, measure_fn, tunables=tun,
                              cache=tcache.TuneCache(), cache_key=key,
                              mode=mode)
    if result is None:
        return {}, None
    if FLAGS.tune_trace:
        print(result.format_trace(), file=sys.stderr)
    current = registry.current_config(tun)
    info = {'mode': mode, 'cached': result.cached, 'tunables': {}}
    for t in tun:
        if t.name in result.winners:
            value, source = result.winners[t.name], 'tuned'
        elif registry.is_pinned(t):
            value, source = current[t.name], 'pinned'
        else:
            value, source = t.default, 'default'
        info['tunables'][t.name] = {'value': value, 'source': source}
    return dict(result.winners), info


def _maybe_roofline(result, exe, unit_count):
    """Attach the --roofline report to a result row (and print the
    human-readable top-ops lines to stderr)."""
    from paddle_tpu.tuning import roofline as rl
    cost = (exe.last_graph_opt_report or {}).get('cost')
    if not cost or not result.get('value'):
        return
    step_s = unit_count / result['value']
    rep = rl.report(cost, measured_step_s=step_s)
    result['roofline'] = {
        'floor_s': round(rep['floor_s'], 9),
        'gap': round(rep.get('gap', 0.0), 3),
        'mfu': round(rep['mfu'], 4) if 'mfu' in rep else None,
        'top': [{'type': o['type'], 'index': o['index'],
                 'role': o.get('role'), 'bound': o['bound'],
                 'share': round(o.get('share', 0.0), 4)}
                for o in rep['top']],
    }
    print(rl.format_report(rep), file=sys.stderr)


def generated_tokens_per_sec(n_generated, wall_s):
    """THE decode-throughput accounting, shared so every generation
    bench reports the same metric the same way: GENERATED tokens (the
    model's own emissions — prompt/source tokens excluded, beam
    hypotheses not multiplied in) per second of synced wall.  Used by
    bench_decode.py (batch x max_len per decode) and bench_serving.py's
    decode scenario (sum of per-stream new tokens)."""
    if wall_s <= 0:
        raise ValueError("wall_s must be positive, got %r" % wall_s)
    return float(n_generated) / float(wall_s)


def maybe_force_cpu():
    """Honour a CPU-smoke request (PADDLE_TPU_BENCH_CPU, or
    JAX_PLATFORMS=cpu) through the config API.  Call before any other
    jax use."""
    import jax
    if os.environ.get('PADDLE_TPU_BENCH_CPU') or \
            os.environ.get('JAX_PLATFORMS', '').lower() == 'cpu':
        jax.config.update('jax_platforms', 'cpu')


def on_tpu():
    import jax
    maybe_force_cpu()
    return any(d.platform == 'tpu' for d in jax.devices())


def ensure_mesh_devices(mesh_specs):
    """Provision enough devices for the largest requested mesh BEFORE
    any jax import: on CPU that means forcing virtual host devices via
    XLA_FLAGS (a no-op when the flag is already set or a real TPU
    backend provides the chips).  Call first thing in a bench main —
    after jax initializes its backend the count is frozen."""
    # parses the axis sizes locally: the canonical parser lives in
    # paddle_tpu.distributed.spec_layout, but importing the package
    # pulls in jax — exactly what must not happen before XLA_FLAGS is
    # set.  Malformed pieces fail HERE, not later as a confusing
    # device-count error
    need = 1
    for spec in mesh_specs:
        n = 1
        for piece in str(spec).split(','):
            piece = piece.strip()
            if not piece or piece in ('off', '1'):
                continue
            if '=' in piece:
                size = piece.split('=', 1)[1]
            else:
                # compact axisN form ('pp2', 'dp4'): trailing digits
                size = piece.rstrip('0123456789')
                size = piece[len(size):]
            try:
                n *= max(int(size), 1)
            except (TypeError, ValueError):
                raise SystemExit(
                    "--mesh %r: piece %r is not axis=size (or compact "
                    "axisN, e.g. pp2)" % (spec, piece))
        need = max(need, n)
    flags = os.environ.get('XLA_FLAGS', '')
    if need > 1 and '--xla_force_host_platform_device_count' not in flags:
        os.environ['XLA_FLAGS'] = (
            flags + ' --xla_force_host_platform_device_count=%d'
            % need).strip()
    return need


def mesh_bench(metric, unit_count, build, feed_fn, mesh_specs,
               steps=None, note=None):
    """Multi-chip SPMD scaling rows (PADDLE_TPU_MESH executor path):
    one JSON line per mesh spec with per-device step time, modeled
    collective ICI bytes/s, and per-device MFU — the scaling curve the
    MULTICHIP_r*.json trajectory tracks.  ``mesh_specs`` entries are
    PADDLE_TPU_MESH strings ('dp=2', 'fsdp=4', ...); 'off' (or '')
    runs the single-logical-device baseline."""
    import jax
    import paddle_tpu as fluid
    if steps is None:
        steps = 8 if on_tpu() else 3
    rows = []
    # ONE feed set for every spec (feed_fn advances its RNG per call):
    # with the seed pinned below, every row trains on identical data
    # from identical init.  The loss column is then a sanity signal —
    # same ballpark, finite — NOT an exact parity check: ulp-scale
    # reduction-order differences between mesh layouts amplify
    # chaotically over the warm+sample steps (measured: 2e-6 at step 3
    # -> ~0.5 at step 12 on the LSTM LM).  Exact parity is pinned
    # where it is provable, on few steps: tests/test_sharding.py
    feeds = [feed_fn() for _ in range(steps)]
    saved = os.environ.get('PADDLE_TPU_MESH')
    try:
        for spec in mesh_specs:
            spec = (spec or '').strip()
            off = spec in ('', 'off', '1')
            if off:
                os.environ.pop('PADDLE_TPU_MESH', None)
            else:
                os.environ['PADDLE_TPU_MESH'] = spec
            devices = 1
            if not off:
                from paddle_tpu.distributed import mesh_flag
                devices = mesh_flag.spmd_device_count(
                    mesh_flag.mesh_axes_from_flag(spec))
            program, startup, loss = build()
            # pinned seed: without it the executor derives the init
            # PRNG from id(self), and the loss column stops being a
            # cross-mesh parity signal
            program.random_seed = startup.random_seed = 1234
            scope = fluid.core.scope.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor(
                    fluid.TPUPlace(0) if on_tpu() else fluid.CPUPlace())
                exe.run(startup)
                out = exe.run_steps(program, feed=feeds,
                                    fetch_list=[loss],
                                    return_numpy=False)  # compile+warm
                jax.block_until_ready(out[0])
                samples = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    out = exe.run_steps(program, feed=feeds,
                                        fetch_list=[loss],
                                        return_numpy=False)
                    jax.block_until_ready(out[0])
                    samples.append(time.perf_counter() - t0)
                loss_val = float(np.asarray(out[0]).ravel()[-1])
                assert np.isfinite(loss_val), "loss went non-finite"
                wall = sorted(samples)[len(samples) // 2]
                step_s = wall / steps
                rep = exe.last_step_report or {}
                phases = rep.get('phases') or {}
                row = {
                    'metric': metric,
                    'mesh': spec if not off else 'off',
                    'devices': devices,
                    'step_s': round(step_s, 6),
                    'units_per_s': round(unit_count / step_s, 2),
                    'units_per_s_per_device': round(
                        unit_count / step_s / devices, 2),
                    'loss': round(loss_val, 4),
                }
                coll = phases.get('collective')
                if coll:
                    per_step = coll['modeled_ici_bytes_per_step']
                    row['modeled_ici_bytes_per_step'] = per_step
                    row['modeled_ici_bytes_per_s'] = round(
                        per_step / step_s, 1)
                    if coll.get('est_wall_s') is not None:
                        row['est_collective_s_per_step'] = round(
                            coll['est_wall_s'] / max(rep.get('k', 1),
                                                     1), 6)
                    # collective-overlap verdict (transpiler/overlap.py
                    # schedule): what fraction of the comm hid behind
                    # compute, and the exposed remainder in modeled
                    # ms/step.  The executor's number is the static
                    # roofline-priced schedule (the bench's async
                    # run_steps never syncs inside the executor); like
                    # the MFU convention above, re-price it here at the
                    # bench's own synced step wall — same buckets, same
                    # serial-channel arithmetic, real time base
                    if coll.get('overlap_fraction') is not None:
                        row['overlap_fraction'] = round(
                            coll['overlap_fraction'], 4)
                        row['overlap_basis'] = coll.get('overlap_basis')
                        row['exposed_ici_bytes_per_step'] = coll.get(
                            'exposed_bytes_per_step', 0)
                        if coll.get('exposed_est_wall_s') is not None:
                            row['exposed_comm_ms_per_step'] = round(
                                coll['exposed_est_wall_s'] * 1e3, 4)
                    cost = rep.get('cost') or {}
                    ccost = cost.get('collectives') or {}
                    sched = ccost.get('overlap')
                    if sched and sched.get('buckets') and \
                            ccost.get('modeled_compute_s'):
                        from paddle_tpu.transpiler.cost_model import \
                            overlap_schedule
                        scale = step_s / ccost['modeled_compute_s']
                        meas = overlap_schedule(
                            sched['buckets'],
                            sched['backward_s'] * scale,
                            sched['window_s'] * scale,
                            sched['ici_gbps'] * 1e9)
                        row['overlap_fraction'] = round(
                            meas['overlap_fraction'], 4)
                        row['overlap_basis'] = 'measured-step'
                        row['exposed_ici_bytes_per_step'] = \
                            meas['exposed_bytes']
                        row['exposed_comm_ms_per_step'] = round(
                            meas['exposed_bytes'] /
                            (sched['ici_gbps'] * 1e9) * 1e3, 4)
                    if coll.get('pp'):
                        row['pp_bubble_fraction'] = coll['pp'].get(
                            'bubble_fraction')
                comp = phases.get('compute') or {}
                peak = os.environ.get('PADDLE_TPU_PEAK_TFLOPS')
                if peak and comp.get('flops_per_step'):
                    # per-device MFU: the global program FLOPs split
                    # over the mesh, against one device's peak
                    row['mfu_per_device'] = round(
                        comp['flops_per_step'] / devices /
                        (step_s * float(peak) * 1e12), 4)
                mem = rep.get('memory') or {}
                if mem.get('modeled_peak_bytes'):
                    row['modeled_peak_bytes'] = mem[
                        'modeled_peak_bytes']
                if note:
                    row['note'] = note
                print(json.dumps(row))
                rows.append(row)
    finally:
        if saved is None:
            os.environ.pop('PADDLE_TPU_MESH', None)
        else:
            os.environ['PADDLE_TPU_MESH'] = saved
    return rows


def run_bench(metric, unit_count, build, feed_fn, steps=20, warmup=3,
              note=None, dtype=None, compile_stats=False,
              amp_compare=None, step_breakdown=False, tune='off',
              roofline=False):
    """build() -> (program, startup, loss_var); feed_fn() -> feed dict.
    unit_count = units (imgs/tokens/examples) per step.

    With compile_stats=True the single-step plan is staged through jit's
    AOT path first (fn.lower() -> .compile()) so the result carries
    trace_s / compile_s columns plus the graph-opt pipeline report —
    the numbers PADDLE_TPU_GRAPH_OPT_LEVEL exists to shrink.

    With amp_compare='bf16' (or 'f16') the whole measurement runs TWICE
    — PADDLE_TPU_AMP off, then at that mode, each in a fresh scope —
    and prints two JSON rows tagged with an ``amp`` column plus the
    pass's ops_lowered/casts and the donation-analysis activation-bytes
    estimate, so the f32-vs-bf16 step time and bytes read side by side.
    Returns [row_off, row_amp].

    With step_breakdown=True the row carries a per-step
    where-did-the-time-go table for the REAL feed path (distinct
    per-step batches through run_steps, not the repeat-mode staged
    batch): ``feed_s`` host staging on the step critical path /
    ``compute_s`` device step + fetch sync / ``update_s`` state
    write-back — measured twice, PADDLE_TPU_DEVICE_PREFETCH off and
    on, so the feed column visibly collapses to the pipeline prime
    when staging overlaps execution."""
    import contextlib
    overrides, tune_info = {}, None
    guard = contextlib.nullcontext()
    if tune and tune != 'off':
        # search/load winners first, then run the whole measurement
        # under the winning env overrides (every consumer re-reads its
        # flag per plan build, so the overrides just take effect)
        from paddle_tpu.tuning import registry as _treg
        overrides, tune_info = _tune_bench(build, feed_fn, tune)
        guard = _treg.applied(overrides)
    with guard:
        if amp_compare:
            import paddle_tpu as fluid
            from paddle_tpu.transpiler.amp import amp_guard
            results = []
            for mode in ('0', amp_compare):
                label = 'off' if mode == '0' else mode
                scope = fluid.core.scope.Scope()
                with amp_guard(mode), fluid.scope_guard(scope):
                    results.append(_bench_once(
                        metric, unit_count, build, feed_fn,
                        steps=steps, warmup=warmup, note=note,
                        dtype=dtype, compile_stats=compile_stats,
                        _amp_label=label,
                        step_breakdown=step_breakdown,
                        roofline=roofline, tune_info=tune_info))
            return results
        return _bench_once(metric, unit_count, build, feed_fn,
                           steps=steps, warmup=warmup, note=note,
                           dtype=dtype, compile_stats=compile_stats,
                           step_breakdown=step_breakdown,
                           roofline=roofline, tune_info=tune_info)


def _step_breakdown(exe, program, loss, feed_fn, k=None, chunk=2):
    """Per-step time breakdown over the per-step-feeds run_steps path,
    PADDLE_TPU_DEVICE_PREFETCH off vs on.  feed_s / feed_overlap_s /
    update_s come from Executor.last_run_steps_report (host wall the
    executor itself measured); compute_s is the residual of the
    measured call wall — the device scan plus the fetch sync."""
    import jax
    if k is None:
        k = 20 if on_tpu() else 4
    feeds = [feed_fn() for _ in range(k)]
    rows = {}
    keys = ('DEVICE_PREFETCH', 'DEVICE_PREFETCH_CHUNK')
    saved = {n: os.environ.get('PADDLE_TPU_' + n) for n in keys}
    try:
        for label, on in (('off', '0'), ('on', '1')):
            os.environ['PADDLE_TPU_DEVICE_PREFETCH'] = on
            os.environ['PADDLE_TPU_DEVICE_PREFETCH_CHUNK'] = str(chunk)
            out = exe.run_steps(program, feed=feeds, fetch_list=[loss],
                                return_numpy=False)  # compile + warm
            jax.block_until_ready(out[0])
            samples = []
            for _ in range(3):
                t0 = time.perf_counter()
                out = exe.run_steps(program, feed=feeds,
                                    fetch_list=[loss],
                                    return_numpy=False)
                jax.block_until_ready(out[0])
                samples.append((time.perf_counter() - t0,
                                exe.last_run_steps_report))
            # the median SAMPLE, wall and report together — mixing the
            # median wall with another run's feed_s would misattribute
            # time
            samples.sort(key=lambda s: s[0])
            wall, rep = samples[len(samples) // 2]
            feed_s = rep['feed_s']
            update_s = rep['update_s']
            rows[label] = {
                'feed_s': round(feed_s / k, 6),
                'compute_s': round(
                    max(wall - feed_s - update_s, 0.0) / k, 6),
                'update_s': round(update_s / k, 6),
                'feed_overlap_s': round(rep['feed_overlap_s'] / k, 6),
                'chunks': rep['chunks'],
                'step_s': round(wall / k, 6),
            }
            # cost-model join (Executor.last_step_report phases): the
            # modeled FLOPs/bytes each phase moves, so every breakdown
            # row carries its own MFU denominator instead of a
            # hand-derived constant.  MFU is derived HERE from the
            # externally-synced wall (block_until_ready above) — the
            # executor's own rate fields are absent on this
            # return_numpy=False path because its residual would only
            # measure host dispatch
            comp = (rep.get('phases') or {}).get('compute') or {}
            if 'flops_per_step' in comp:
                modeled = {
                    'flops_per_step': comp['flops_per_step'],
                    'bytes_per_step': comp['bytes_per_step'],
                    'intensity': round(comp['intensity'], 3),
                    'per_role_flops': comp['per_role_flops'],
                }
                peak = os.environ.get('PADDLE_TPU_PEAK_TFLOPS')
                row_compute_s = rows[label]['compute_s']
                if peak and float(peak) > 0 and row_compute_s > 0:
                    modeled['mfu'] = round(
                        comp['flops_per_step'] /
                        (row_compute_s * float(peak) * 1e12), 4)
                rows[label]['modeled'] = modeled
            # memory block (Executor.last_step_report['memory']): the
            # liveness model's peak next to the MEASURED device peak
            # when the backend reports memory_stats() — None on CPU,
            # stated rather than faked — plus the watermark op, so
            # PERF.md can print modeled-vs-measured deltas per bench
            mem = rep.get('memory') or {}
            if mem:
                wm = mem.get('watermark_op') or {}
                mrow = {
                    'modeled_peak_bytes': mem.get('modeled_peak_bytes'),
                    'measured_peak_bytes':
                        (mem.get('measured') or {}).get(
                            'peak_bytes_in_use'),
                    'watermark_op': wm.get('type'),
                    'watermark_op_seq': wm.get('op_seq'),
                }
                head = mem.get('headroom')
                if head:
                    mrow['headroom'] = {
                        k: (round(v, 4) if isinstance(v, float) else v)
                        for k, v in head.items()}
                rows[label]['memory'] = mrow
    finally:
        for n in keys:
            if saved[n] is None:
                os.environ.pop('PADDLE_TPU_' + n, None)
            else:
                os.environ['PADDLE_TPU_' + n] = saved[n]
    return rows


def _bench_once(metric, unit_count, build, feed_fn, steps=20, warmup=3,
                note=None, dtype=None, compile_stats=False,
                _amp_label=None, step_breakdown=False, roofline=False,
                tune_info=None):
    import jax
    import paddle_tpu as fluid

    program, startup, loss = build()
    place = fluid.TPUPlace(0) if on_tpu() else fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(startup)

    dev = place.jax_device()

    def stage(f):
        return {k: (tuple(v) if isinstance(v, tuple)
                    else jax.device_put(v, dev)) for k, v in f.items()}

    feed = stage(feed_fn())

    cstats = {}
    if compile_stats:
        # cold-path cost of one plan build, measured stage by stage:
        # graph-opt pass pipeline (inside compile()), trace to jaxpr
        # (lower), XLA compile.  The jit call below re-compiles through
        # its own cache, so steady-state numbers are unaffected.
        t0 = time.perf_counter()
        fn, args = exe.compile(program, feed=feed, fetch_list=[loss])
        plan_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        trace_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        lowered.compile()
        compile_s = time.perf_counter() - t0
        cstats = {"plan_s": round(plan_s, 3),
                  "trace_s": round(trace_s, 3),
                  "compile_s": round(compile_s, 3)}
        rep = exe.last_graph_opt_report
        if rep:
            cstats["graph_opt"] = {
                "level": rep["level"],
                "ops_before": rep["ops_before"],
                "ops_after": rep["ops_after"],
                "eliminated": rep["eliminated"],
                "pass_wall_s": round(rep["pass_wall_s"], 4)}
        else:
            from paddle_tpu.flags import FLAGS
            cstats["graph_opt"] = {"level": int(FLAGS.graph_opt_level),
                                   "ops_before": None, "ops_after": None}

    # K steps as one compiled lax.scan (Executor.run_steps) sampled 3x,
    # median reported
    out = exe.run_steps(program, feed=feed, fetch_list=[loss],
                        repeat=steps, return_numpy=False)  # compile+warm
    np.asarray(out[0])
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = exe.run_steps(program, feed=feed, fetch_list=[loss],
                            repeat=steps, return_numpy=False)
        vals = np.asarray(out[0])
        samples.append(unit_count * steps / (time.perf_counter() - t0))
    val = float(vals.ravel()[-1])
    assert np.isfinite(val), "loss went non-finite"

    result = {
        "metric": metric,
        "value": round(float(np.median(samples)), 2),
        "samples": [round(s, 1) for s in samples],
    }
    result.update(cstats)
    if step_breakdown:
        # where-did-the-time-go per step, prefetch off vs on — the
        # feed_s column collapsing to ~the pipeline prime under 'on'
        # is the device-residency claim, measured
        result["breakdown"] = _step_breakdown(exe, program, loss,
                                              feed_fn)
    if _amp_label is not None:
        # f32-vs-bf16 rows: the mode, the pass's lowering stats, and the
        # donation-analysis bytes of step intermediates (activations) —
        # bf16 roughly halves it, the bandwidth half of the AMP win
        result["amp"] = _amp_label
        rep = exe.last_graph_opt_report or {}
        arep = rep.get("amp")
        if arep:
            result["amp_ops_lowered"] = arep["ops_lowered"]
            result["amp_casts"] = arep["casts_inserted"]
        don = rep.get("donation")
        if don:
            result["act_bytes"] = don["bytes_known"]
    if dtype:
        # structured workload marker: keeps the metric key stable across
        # the fp32 -> bf16 config change while making it machine-visible
        result["dtype"] = dtype
    if note:
        result["note"] = note
    if tune_info is not None:
        # which tunables were tuner-chosen vs defaults vs user-pinned —
        # the attribution record that makes BENCH r06 explainable
        result["tune"] = tune_info
    if roofline:
        _maybe_roofline(result, exe, unit_count)
    print(json.dumps(result))
    return result

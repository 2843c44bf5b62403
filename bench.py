#!/usr/bin/env python
"""Headline benchmark: ResNet-50 ImageNet training throughput, img/sec on
one chip (SURVEY.md §5; reference number: 61 img/s/GPU fp32 batch 64 on
Tesla P40, benchmark/cluster docs).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The whole train step (forward + backward + momentum update) is one jitted
XLA program with donated parameter buffers — steady-state steps do zero
host work beyond the feed.

Autotuning (ISSUE 16): ``--tune search`` runs the cost-model-pruned
measured search (paddle_tpu.tuning) over amp / flat-tile budget /
prefetch chunk / train batch / run_steps K and persists the winners;
``--tune cached`` starts from persisted winners with zero search;
``--tune off`` (default) is the untuned bench, bitwise as before.
``--roofline`` attaches the top-ops roofline report; ``--tune-trace``
(or PADDLE_TPU_TUNE_TRACE=1) prints the search trace to stderr.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_IMG_S = 61.0  # reference P40 fp32, batch 64

# flag-scope tunables this bench searches (applied via env overrides);
# train_batch / run_steps_k are bench-scope: searched by rebuilding the
# program / resizing the scan below
_FLAG_TUNABLES = ('amp', 'device_prefetch_chunk')
_BENCH_TUNABLES = ('train_batch', 'run_steps_k')


def _parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--tune', choices=('off', 'cached', 'search'),
                    default=os.environ.get('PADDLE_TPU_TUNE') or 'off')
    ap.add_argument('--roofline', action='store_true')
    ap.add_argument('--tune-trace', action='store_true')
    args, _rest = ap.parse_known_args(argv)
    if args.tune_trace:
        os.environ['PADDLE_TPU_TUNE_TRACE'] = '1'
    return args


def _autotune(mode, build_prog, image_shape, classes, batch0, k0,
              on_tpu):
    """Search (or cache-load) winners; returns (batch, k, info) with
    flag-scope winners applied to the process env for the headline run.
    The objective is seconds per image (model and measurement agree),
    so batch candidates compare fairly."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.tuning import (cache as tcache, registry,
                                   runtime as trt, search as tsearch)

    tun = [registry.tunable(n)
           for n in _FLAG_TUNABLES + _BENCH_TUNABLES]
    budget = None  # Autotuner default (FLAGS.tune_measure_budget)
    if not on_tpu:
        # CPU smoke: every candidate recompiles the step, so clamp the
        # domains (and skip K — the CPU measurement caps it anyway) so
        # a search finishes in seconds, not minutes
        clamp = {'train_batch': tuple(
                     v for v in registry.tunable('train_batch').domain
                     if v <= max(batch0, 32)),
                 'device_prefetch_chunk': (0, 2)}
        tun = [registry.Tunable(t.name, clamp.get(t.name, t.domain),
                                t.default, t.subsystem, t.env,
                                scope=t.scope, help=t.help,
                                feasible=t.feasible)
               for t in tun if t.name != 'run_steps_k']
        budget = 8
    base = registry.current_config(tun)
    base['train_batch'] = batch0
    if any(t.name == 'run_steps_k' for t in tun):
        base['run_steps_k'] = k0
    rng = np.random.default_rng(0)

    def _flag_part(cfg):
        return {n: cfg[n] for n in _FLAG_TUNABLES}

    def model_fn(cfg):
        b = int(cfg.get('train_batch', batch0))
        prog, _startup, loss = build_prog(b)
        with registry.applied(_flag_part(cfg)):
            m = trt.model_program(
                prog, fetch_names=(loss.name,),
                feed_specs={'img': ((b,) + image_shape, 'float32'),
                            'label': ((b, 1), 'int32')})
        if m is None:
            return None
        return {'score': m['score'] / b, 'peak_bytes': m['peak_bytes']}

    def measure_fn(cfg):
        b = int(cfg.get('train_batch', batch0))
        kk = int(cfg.get('run_steps_k', k0))
        if not on_tpu:
            kk = min(kk, 5)  # CPU smoke: keep the search bounded
        prog, startup, loss = build_prog(b)
        images = rng.normal(size=(b,) + image_shape).astype(np.float32)
        labels = rng.integers(0, classes, (b, 1)).astype(np.int32)
        with registry.applied(_flag_part(cfg)):
            scope = fluid.core.scope.Scope()
            with fluid.scope_guard(scope):
                place = fluid.TPUPlace(0) if on_tpu else \
                    fluid.CPUPlace()
                exe = fluid.Executor(place)
                exe.run(startup)
                dev = place.jax_device()
                staged = {'img': jax.device_put(images, dev),
                          'label': jax.device_put(labels, dev)}
                out = exe.run_steps(prog, feed=staged,
                                    fetch_list=[loss], repeat=kk,
                                    return_numpy=False)
                jax.block_until_ready(out[0])
                t0 = time.perf_counter()
                out = exe.run_steps(prog, feed=staged,
                                    fetch_list=[loss], repeat=kk,
                                    return_numpy=False)
                jax.block_until_ready(out[0])
                return (time.perf_counter() - t0) / (kk * b)

    key = trt.cache_key_for(build_prog(batch0)[0])
    result = tsearch.autotune(model_fn, measure_fn, tunables=tun,
                              cache=tcache.TuneCache(), cache_key=key,
                              mode=mode, measure_budget=budget,
                              base=base)
    if result is None:
        return batch0, k0, None
    if FLAGS.tune_trace:
        print(result.format_trace(), file=sys.stderr)
    # apply the winners: flag-scope persistently (the headline run's
    # plan builds re-read them), bench-scope via the returned batch/k
    flag_winners = {n: v for n, v in result.winners.items()
                    if n in _FLAG_TUNABLES}
    registry.apply_persistent(flag_winners)
    batch = int(result.winners.get('train_batch', batch0))
    k = int(result.winners.get('run_steps_k', k0))
    info = {'mode': mode, 'cached': result.cached, 'tunables': {}}
    chosen = dict(base)
    chosen.update(result.winners)
    for t in tun:
        if t.name in result.winners:
            source = 'tuned'
        elif registry.is_pinned(t):
            source = 'pinned'
        else:
            source = 'default'
        info['tunables'][t.name] = {'value': chosen[t.name],
                                    'source': source}
    return batch, k, info


def main(argv=None):
    args = _parse_args(argv)
    import jax
    on_tpu = any(d.platform == 'tpu' for d in jax.devices())
    # CPU smoke mode (CI): tiny shapes, still the full train-step path
    if on_tpu:
        batch, hw, depth, classes, steps, warmup = 64, 224, 50, 1000, 20, 3
    else:
        batch, hw, depth, classes, steps, warmup = 8, 64, 18, 100, 3, 1
    batch = int(os.environ.get('PADDLE_TPU_BENCH_BATCH', batch))

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    # bf16 activations (fp32 accumulation + fp32 BN stats) on NHWC — the
    # MXU recipe (SURVEY §6.4); PADDLE_TPU_BENCH_DTYPE/LAYOUT override.
    dtype = os.environ.get('PADDLE_TPU_BENCH_DTYPE', 'bfloat16')
    if args.tune != 'off':
        # precision is the amp tunable's job when tuning: build the
        # pure-f32 program and let the AMP pass cast (the manual bf16
        # activations plus an AMP rewrite on top would double-cast and
        # fail IR verification)
        dtype = 'float32'
    layout = os.environ.get('PADDLE_TPU_BENCH_LAYOUT', 'NHWC')
    stem = os.environ.get('PADDLE_TPU_BENCH_STEM', '7x7')
    image_shape = (hw, hw, 3) if layout == 'NHWC' else (3, hw, hw)

    def build_prog(b):
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup):
            img, label, prediction, avg_cost, acc = \
                resnet.build_imagenet(
                    depth=depth, num_classes=classes,
                    image_shape=image_shape, dtype=dtype,
                    layout=layout, stem=stem)
            opt = fluid.optimizer.MomentumOptimizer(learning_rate=0.1,
                                                    momentum=0.9)
            opt.minimize(avg_cost)
        del b  # batch rides in the feed (declared dims are -1-batched)
        return main_prog, startup, avg_cost

    # K steps per run_steps chain.  PADDLE_TPU_BENCH_RUN_STEPS
    # overrides (and pins the run_steps_k tunable)
    k = int(os.environ.get('PADDLE_TPU_BENCH_RUN_STEPS',
                           500 if on_tpu else steps))

    tune_info = None
    if args.tune != 'off':
        batch, k, tune_info = _autotune(args.tune, build_prog,
                                        image_shape, classes, batch, k,
                                        on_tpu)

    main_prog, startup, avg_cost = build_prog(batch)

    place = fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(startup)

    rng = np.random.default_rng(0)
    images = rng.normal(size=(batch,) + image_shape).astype(np.float32)
    labels = rng.integers(0, classes, size=(batch, 1)).astype(np.int32)
    dev = place.jax_device()

    # Default: one device-staged batch replayed (the step alone).
    # PADDLE_TPU_BENCH_FEED=host streams fresh host batches through the
    # native feed pipeline (C++ staging arena + ring queue).
    feed_mode = os.environ.get('PADDLE_TPU_BENCH_FEED', 'device')
    if feed_mode == 'host':
        # Stream fresh host batches through the native staging pipeline
        # (C++ arena blocks + ring queue, runtime/feed.py): batch assembly
        # and the host->device transfer overlap the train step — the
        # end-to-end feed path, like the reference's threaded provider.
        from paddle_tpu.runtime import FeedPipeline

        def fill(views, step):
            views['img'][:] = images  # memcpy: host batch assembly
            views['label'][:] = labels

        pipe = FeedPipeline(
            {'img': ((batch,) + image_shape, np.float32),
             'label': ((batch, 1), np.int32)}, fill, depth=3, device=dev)
        feeds = iter(pipe)
    else:
        # device-staged fixed batch: pure train-step throughput
        staged = {'img': jax.device_put(images, dev),
                  'label': jax.device_put(labels, dev)}
        import itertools
        feeds = itertools.repeat(staged)

    # Measurement: K steps as ONE compiled lax.scan (run_steps),
    # sampled three times with the median reported.
    if feed_mode == 'host':
        for _ in range(warmup):
            out = exe.run(main_prog, feed=next(feeds),
                          fetch_list=[avg_cost])
        np.asarray(out[0])  # sync
        t0 = time.perf_counter()
        for _ in range(steps):
            out = exe.run(main_prog, feed=next(feeds),
                          fetch_list=[avg_cost], return_numpy=False)
        loss = float(np.asarray(out[0]).ravel()[0])
        dt = time.perf_counter() - t0
        samples = [batch * steps / dt]
    else:
        staged = next(feeds)
        out = exe.run_steps(main_prog, feed=staged, fetch_list=[avg_cost],
                            repeat=k, return_numpy=False)  # compile+warm
        np.asarray(out[0])
        samples = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = exe.run_steps(main_prog, feed=staged,
                                fetch_list=[avg_cost], repeat=k,
                                return_numpy=False)
            losses = np.asarray(out[0]).ravel()
            samples.append(batch * k / (time.perf_counter() - t0))
        loss = float(losses[-1])
    assert np.isfinite(loss), "bench loss went non-finite"

    img_per_sec = float(np.median(samples))
    result = {
        "metric": "resnet%d_train_img_per_sec_per_chip" % depth,
        "value": round(img_per_sec, 2),
        "unit": "img/s",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_S, 3),
        "samples": [round(s, 1) for s in samples],
    }
    if on_tpu:
        # MFU denominator comes from the static cost model when the plan
        # carries one (transpiler/cost_model.py: exact per-op MACs from
        # the IR, fwd counted per op, bwd = 2x the loss-contributing
        # forward slice) — the per-PROGRAM replacement for the old hand
        # constant "8.178 GFLOP/img fwd, train=3xfwd", which assumed
        # every forward FLOP is differentiated and rounded the MAC count
        # to a published figure.  Peak stays the 192 TFLOPS this part
        # SUSTAINS on a square matmul (PERF.md flash-roofline
        # calibration; PADDLE_TPU_PEAK_TFLOPS overrides).  The hand
        # constant remains the fallback when no cost report exists
        # (graph-opt level 0), and mfu_basis says which basis each row
        # used.  (The r1-r5 mfu series divided MACs by the 197 spec
        # peak and read ~2.05x low — retracted, PERF.md "MFU
        # accounting".)
        peak = float(os.environ.get('PADDLE_TPU_PEAK_TFLOPS', 192.0))
        cost = (exe.last_graph_opt_report or {}).get('cost')
        if cost and cost['total']['flops']:
            flops_per_step = cost['total']['flops']
            steps_per_sec = img_per_sec / batch
            result["mfu"] = round(
                flops_per_step * steps_per_sec / (peak * 1e12), 4)
            result["mfu_basis"] = (
                "cost_model: per-op MACs from the IR (fwd %.3g + bwd "
                "%.3g + opt %.3g FLOP/step), peak=%g TFLOPS measured"
                % (cost['per_role'].get('forward', {}).get('flops', 0),
                   cost['per_role'].get('backward', {}).get('flops', 0),
                   cost['per_role'].get('optimize', {}).get('flops', 0),
                   peak))
        else:
            train_flops_per_img = 3 * 2 * 4.089e9
            result["mfu"] = round(
                img_per_sec * train_flops_per_img / (peak * 1e12), 4)
            result["mfu_basis"] = (
                "hand fallback (no cost report): flops=2xMAC "
                "(8.178 GFLOP/img fwd), train=3xfwd, peak=%g TFLOPS "
                "measured" % peak)
    if os.environ.get('PADDLE_TPU_BENCH_TFLOPS') not in (None, '', '0'):
        # achieved compute rate from the compiler's own cost model —
        # opt-in: cost_analysis compiles a second copy of the step
        # (~30s on TPU; Lowered.cost_analysis is None on this backend)
        try:
            from paddle_tpu import profiler
            flops = profiler.cost_analysis(
                main_prog, {'img': images, 'label': labels},
                [avg_cost]).get('flops', 0)
            if flops:
                steps_per_sec = img_per_sec / batch
                result["achieved_tflops"] = round(
                    flops * steps_per_sec / 1e12, 2)
        except Exception:
            pass
    result["config"] = "%s %s batch=%d feed=%s" % (dtype, layout, batch,
                                                   feed_mode)
    if tune_info is not None:
        result["tune"] = tune_info
    if args.roofline:
        cost = (exe.last_graph_opt_report or {}).get('cost')
        if cost:
            from paddle_tpu.tuning import roofline as rl
            rep = rl.report(cost,
                            measured_step_s=batch / img_per_sec)
            result["roofline"] = {
                'floor_s': round(rep['floor_s'], 9),
                'gap': round(rep.get('gap', 0.0), 3),
                'mfu': round(rep['mfu'], 4) if 'mfu' in rep else None,
                'top': [{'type': o['type'], 'index': o['index'],
                         'role': o.get('role'), 'bound': o['bound'],
                         'share': round(o.get('share', 0.0), 4)}
                        for o in rep['top']],
            }
            print(rl.format_report(rep), file=sys.stderr)
    if not on_tpu:
        result["note"] = "cpu-smoke (depth=%d hw=%d batch=%d)" % (
            depth, hw, batch)
    print(json.dumps(result))


if __name__ == '__main__':
    main()

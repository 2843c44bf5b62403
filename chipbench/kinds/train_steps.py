"""Traffic of kind ``train_steps``: a training job.  One synced step
after another for the length of the window, each fed by the pipeline the
traffic file describes."""
import functools
import importlib
import time

import numpy as np

from .. import harness, xplane


def run(run):
    ph, spans, t = run.phases, run.spans, run.traffic
    system = importlib.import_module('chipbench.systems.'
                                     + run.config['system'])
    reference = importlib.import_module('chipbench.reference.'
                                        + run.config['reference'])
    import jax
    with ph('startup_program'):
        rig = system.Rig(run, run.devices)
    with ph('host_batches'):
        pool = system.host_batches(run, rig, int(t['feed']['host_batches']))
    why = []

    # the reference's loss on the first batch and the startup weights,
    # before any step changes them
    with ph('reference_check'):
        first = pool[0]
        args = (rig.weights(), first['img'], first['label'])
        if rig.chips > 1:
            # batch statistics span the global batch: give the reference
            # the same batch, split over the same chips
            from jax.sharding import NamedSharding, PartitionSpec
            mesh = jax.make_mesh((rig.chips,), ('dp',))
            split = NamedSharding(mesh, PartitionSpec('dp'))
            whole = NamedSharding(mesh, PartitionSpec())
            args = (jax.device_put(dict(args[0]), whole),
                    jax.device_put(args[1], split),
                    jax.device_put(args[2], split))
        ref_loss = float(jax.jit(functools.partial(
            reference.loss, config=run.config))(*args))
        del args
    with ph('compile_step'):
        first_loss = rig.step(first)
    rel = abs(first_loss - ref_loss) / abs(ref_loss)
    harness.info('REFERENCE', {'first_step_loss': first_loss,
                               'reference_loss': ref_loss, 'rel_diff': rel,
                               'rtol': reference.LOSS_RTOL})
    if not rel <= reference.LOSS_RTOL:
        why.append('first-step loss %r is %.3g from the reference %r'
                   % (first_loss, rel, ref_loss))

    with ph('memory_analysis'):
        run.scratch_bytes = rig.scratch_bytes(first)

    def fill(views, step):
        b = pool[step % len(pool)]
        views['img'][:] = b['img']
        views['label'][:] = b['label']

    with ph('pipeline_start'):
        pipe = rig.pipeline(t['feed'], fill)
        feeds = iter(pipe)
    done, losses = [], []

    def steps_until(deadline=None, count=None):
        n = 0
        while (time.perf_counter() < deadline) if count is None \
                else (n < count):
            with spans('bench.feed_next'):
                feed = next(feeds)
            with spans('bench.run'):
                losses.append(rig.step(feed))
            done.append(time.perf_counter())
            n += 1

    try:
        with ph('settle'):
            steps_until(count=int(t['settle_steps']))
        run.quiet_gc()
        compiles0 = run.compiles.count
        t_open = time.perf_counter()
        setup_s = t_open - harness.T0
        n_settle = len(done)
        if run.trace:
            t_host_end = t_open + max(run.seconds - run.trace_seconds(),
                                      0.5 * run.seconds)
            steps_until(deadline=t_host_end)
            t_host_end = done[-1]
            with run.traced():
                steps_until(deadline=time.perf_counter()
                            + min(run.trace_seconds(), 0.5 * run.seconds))
        else:
            steps_until(deadline=t_open + run.seconds)
            t_host_end = done[-1]
        compiled = run.compiles.count - compiles0
    finally:
        pipe.close()

    # throughput from step boundaries: whole steps between the first and
    # the last completion inside the window, never steps over --seconds
    win = [x for x in done[n_settle:] if x <= t_host_end]
    rate = rig.batch * (len(win) - 1) / (win[-1] - win[0])
    per_fifth = []
    for lo, hi in harness.fifths(win[0], win[-1]):
        part = [x for x in win if lo <= x <= hi]
        per_fifth.append(rig.batch * (len(part) - 1) / (part[-1] - part[0])
                         if len(part) > 1 else None)
    harness.info('FIFTHS', {
        'train_img_per_s': per_fifth, 'steps': len(win), 'img_per_s': rate,
        'feed_next_ms': [1e3 * float(np.mean(spans.between(
            'bench.feed_next', lo, hi) or [0]))
            for lo, hi in harness.fifths(win[0], win[-1])],
        'run_ms': [1e3 * float(np.mean(spans.between('bench.run', lo, hi)
                                       or [0]))
                   for lo, hi in harness.fifths(win[0], win[-1])]})
    if run.trace:
        # the same host-clock readings inside the traced seconds: if the
        # tracer slows the host, it shows here first
        lo, hi = t_host_end, done[-1]
        harness.info('TRACED_STEPS', {
            'steps': len([x for x in done if lo < x <= hi]),
            'feed_next_ms': 1e3 * float(np.mean(spans.between(
                'bench.feed_next', lo, hi) or [0])),
            'run_ms': 1e3 * float(np.mean(spans.between(
                'bench.run', lo, hi) or [0]))})
    window_losses = losses[n_settle:]
    if not np.isfinite(losses).all():
        why.append('a loss is not finite')
    fifth = max(len(window_losses) // 5, 1)
    head, tail = window_losses[:fifth], window_losses[-fifth:]
    if not np.median(tail) < np.median(head):
        why.append('the loss did not fall over the window: first fifth '
                   '%r, last fifth %r'
                   % (float(np.median(head)), float(np.median(tail))))
    if compiled:
        why.append('%d compilation(s) inside the window' % compiled)
    harness.info('LOSS', {'first': first_loss,
                          'window_first_fifth_median': float(np.median(head)),
                          'window_last_fifth_median': float(np.median(tail))})

    tr = run.obs.get('trace')
    if tr is not None and xplane.window(tr):
        calls = xplane.module_calls(tr, xplane.window(tr),
                                    run.config['device_programs']['step'])
        if calls:
            # the idle share a second way, from the host clock outside
            # the traced seconds: 1 - device time of the steps / elapsed
            run.obs['host_idle_share'] = 1.0 - float(np.median(calls)) \
                * (len(win) - 1) / (win[-1] - win[0])
    run.obs.update(kind='train_steps', batch=rig.batch, chips=rig.chips,
                   t_open=t_open, t_host_end=t_host_end,
                   host_steps=len(win), host_elapsed=win[-1] - win[0])
    run.emit(correct=True, attempted=len(done) - n_settle, failed=0,
             end_to_end={'train_img_per_s': rate, 'setup_s': setup_s},
             why=why)

"""Collective-overlap scheduling pass + pp mesh axis
(transpiler/overlap.py, the `overlap_collectives` registered pass, the
pp block of transpiler/sharding.py, distributed/pipeline.from_mesh).

Pins: DDP-style bucket partitioning under PADDLE_TPU_OVERLAP_BUCKET_MB
with backward-retirement ordering; the serial-comm-channel schedule
closed form; PADDLE_TPU_OVERLAP=0 and no-mesh runs bitwise-identical
(the pass stamps nothing and the executor lowers no barrier);
measured-compute overlap fraction in the run_steps collective phase and
the Chrome-trace counter series; the pp plan block (1F1B bubble closed
form, balanced cut selection, ppermute pricing); the SPMD executor's
actionable pp refusal; and from_mesh mesh-driven 1F1B lowering.
"""
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.program import reset_unique_name_guard
from paddle_tpu.distributed import spec_layout
from paddle_tpu.transpiler import cost_model as cm
from paddle_tpu.transpiler import overlap as ov
from paddle_tpu.transpiler import pass_manager as pm
from paddle_tpu.transpiler import sharding as sharding_mod

B = 8


def _wide_mlp(seed=7, width=512, layers=3):
    """Wide enough that a small PADDLE_TPU_OVERLAP_BUCKET_MB cap
    splits the gradient collectives into several buckets."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with reset_unique_name_guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[64], dtype='float32')
        label = fluid.layers.data(name='label', shape=[1],
                                  dtype='int64')
        h = x
        for _ in range(layers):
            h = fluid.layers.fc(input=h, size=width, act='relu')
        pred = fluid.layers.fc(input=h, size=10, act='softmax')
        loss = fluid.layers.mean(x=fluid.layers.cross_entropy(
            input=pred, label=label))
        fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
    return main, startup, loss


_FEEDS = {'x': ((B, 64), 'float32'), 'label': ((B, 1), 'int32')}


def _np_feed(seed=0):
    r = np.random.RandomState(seed)
    return {'x': r.randn(B, 64).astype('float32'),
            'label': r.randint(0, 10, (B, 1)).astype('int64')}


# ---------------------------------------------------------------------------
# bucket partitioning + pass plumbing
# ---------------------------------------------------------------------------

def test_overlap_buckets_golden_dp2(monkeypatch):
    """Bucket partition under a 1 MiB cap: multiple size-bounded
    buckets, retirement-ordered (monotone ready_frac, last fc's grads
    first), plan block and autodiff attr mirror each other, and the
    whole pipeline survives verify='every_pass'."""
    monkeypatch.setenv('PADDLE_TPU_OVERLAP_BUCKET_MB', '1')
    main, _s, loss = _wide_mlp()
    prog, rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('x', 'label'),
        feed_specs=_FEEDS, mesh='dp=2', verify='every_pass')
    plan = prog._sharding_plan
    ovp = plan['overlap']
    assert rep['overlap']['enabled']
    assert ovp['bucket_mb'] == 1
    buckets = ovp['buckets']
    assert len(buckets) >= 2  # 512x512 f32 grads exceed 1 MiB
    cap = 1 << 20
    for b in buckets:
        # a bucket only exceeds the cap when a single grad does
        assert b['bytes'] <= cap or len(b['names']) == 1
        assert b['kinds'] == ('allreduce',)
        assert b['ici_bytes'] > 0
    fracs = [b['ready_frac'] for b in buckets]
    assert fracs == sorted(fracs)  # retirement order
    assert all(0.0 <= f <= 1.0 for f in fracs)
    # the LAST fc layer's grads retire first (the backward re-walk
    # reaches them earliest), so they lead the first bucket
    first = buckets[0]['names']
    assert any('fc_3' in n for n in first), first
    # autodiff attr is the executor's lowering handle
    ad = [op for op in prog.global_block().ops
          if op.type == 'autodiff'][0]
    assert ad.attrs['overlap_buckets'] == tuple(
        b['names'] for b in buckets)
    # every bucketed name is a priced gradient allreduce
    table = {c['name'] for c in plan['collectives']
             if c['kind'] in ov.GRAD_COLLECTIVE_KINDS}
    assert set(ovp['grad_names']) <= table


def test_overlap_flag_off_stamps_nothing(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_OVERLAP', '0')
    main, _s, loss = _wide_mlp()
    prog, rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('x', 'label'),
        feed_specs=_FEEDS, mesh='dp=2', verify='every_pass')
    assert 'overlap' not in rep  # pass gated out of the plan entirely
    assert (prog._sharding_plan or {}).get('overlap') is None
    ad = [op for op in prog.global_block().ops
          if op.type == 'autodiff'][0]
    assert 'overlap_buckets' not in ad.attrs
    # and the cost model's split degrades to fully exposed
    coll = rep['cost']['collectives']
    assert coll['overlap'] is None
    assert coll['bytes']['exposed'] == coll['bytes']['total'] \
        == coll['ici_bytes']


def test_overlap_no_mesh_is_noop():
    main, _s, loss = _wide_mlp()
    prog, rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('x', 'label'),
        feed_specs=_FEEDS, mesh='', verify='every_pass')
    assert 'overlap' not in rep
    ad = [op for op in prog.global_block().ops
          if op.type == 'autodiff'][0]
    assert 'overlap_buckets' not in ad.attrs


def test_overlap_plan_key_tracks_knobs(monkeypatch):
    k_on = pm.plan_key()
    monkeypatch.setenv('PADDLE_TPU_OVERLAP_BUCKET_MB', '4')
    k_mb = pm.plan_key()
    monkeypatch.setenv('PADDLE_TPU_OVERLAP', '0')
    k_off = pm.plan_key()
    assert len({k_on, k_mb, k_off}) == 3
    monkeypatch.setenv('PADDLE_TPU_PP_MICROBATCHES', '16')
    assert pm.plan_key() != k_off


# ---------------------------------------------------------------------------
# the schedule closed form
# ---------------------------------------------------------------------------

def test_overlap_schedule_closed_form():
    """Hand-computed serial-channel schedule: bw 1e8 B/s, two 1e8-byte
    buckets.  b0 (ready 0.0) runs [0,1] inside the window; b1 (ready
    0.5) queues behind it, runs [1,2] against window 1.2 -> 0.8 s
    exposed = 8e7 bytes.  Fraction = 1.2e8/2e8 = 0.6."""
    buckets = (
        {'names': ('a',), 'bytes': 10**8, 'ici_bytes': 10**8,
         'ready_frac': 0.0},
        {'names': ('b',), 'bytes': 10**8, 'ici_bytes': 10**8,
         'ready_frac': 0.5},
    )
    s = cm.overlap_schedule(buckets, backward_s=1.0, window_s=1.2,
                            bw_bps=1e8)
    assert s['total_ici_bytes'] == 2 * 10**8
    assert s['buckets'][0]['exposed_bytes'] == 0
    assert s['buckets'][1]['start_s'] == 1.0  # channel busy until 1.0
    assert s['buckets'][1]['exposed_bytes'] == 8 * 10**7
    assert s['exposed_bytes'] == 8 * 10**7
    assert s['overlap_fraction'] == 0.6


def test_overlap_schedule_hides_everything_in_wide_window():
    buckets = ({'names': ('a',), 'bytes': 10**6, 'ici_bytes': 10**6,
                'ready_frac': 0.9},)
    s = cm.overlap_schedule(buckets, backward_s=1.0, window_s=10.0,
                            bw_bps=1e9)
    assert s['exposed_bytes'] == 0
    assert s['overlap_fraction'] == 1.0
    # and with no compute to hide behind, everything is exposed
    s0 = cm.overlap_schedule(buckets, backward_s=0.0, window_s=0.0,
                             bw_bps=1e9)
    assert s0['exposed_bytes'] == 10**6
    assert s0['overlap_fraction'] == 0.0


def test_cost_model_collective_split(monkeypatch):
    """The structured {total, exposed, overlapped} split is coherent
    and the old ici_bytes scalar is preserved for BENCH JSON."""
    monkeypatch.setenv('PADDLE_TPU_OVERLAP_BUCKET_MB', '1')
    main, _s, loss = _wide_mlp()
    _prog, rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('x', 'label'),
        feed_specs=_FEEDS, mesh='dp=2', verify='boundary')
    coll = rep['cost']['collectives']
    bts = coll['bytes']
    assert bts['total'] == coll['ici_bytes'] > 0
    assert bts['exposed'] + bts['overlapped'] == bts['total']
    sched = coll['overlap']
    assert sched['bucket_mb'] == 1
    assert sched['ici_gbps'] == cm.DEFAULT_ICI_GBPS  # flag unset
    assert 0.0 <= sched['overlap_fraction'] <= 1.0
    assert coll['modeled_compute_s'] > 0
    # schedule internal consistency: serial channel, in order
    starts = [b['start_s'] for b in sched['buckets']]
    ends = [b['end_s'] for b in sched['buckets']]
    for i in range(1, len(starts)):
        assert starts[i] >= ends[i - 1] - 1e-12


# ---------------------------------------------------------------------------
# parity: the barrier is an identity on values
# ---------------------------------------------------------------------------

def _run3(monkeypatch, overlap, bucket_mb='1'):
    monkeypatch.setenv('PADDLE_TPU_MESH', 'dp=2')
    monkeypatch.setenv('PADDLE_TPU_OVERLAP', overlap)
    monkeypatch.setenv('PADDLE_TPU_OVERLAP_BUCKET_MB', bucket_mb)
    main, startup, loss = _wide_mlp()
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = [exe.run(main, feed=_np_feed(i),
                          fetch_list=[loss])[0] for i in range(3)]
        param = np.asarray(scope.get('fc_0.w_0'))
    return [np.asarray(v) for v in losses], param


def test_overlap_on_off_losses_bitwise_param_within_ulps(monkeypatch):
    """PADDLE_TPU_OVERLAP=0 computes what the overlapped lowering
    computes: optimization_barrier is an identity on values, so the
    three losses are bitwise equal.  It is not an identity on XLA:CPU's
    choice of which multiply-adds of Adam's apply to contract into an
    fma, and that choice is the compiler's, not the repository's: the
    parameter is held to a few ulp, not to the bit (jax 0.9.0: 22% of
    fc_0.w_0 differs, by at most 7.3e-8 on values up to 0.13)."""
    on_losses, on_param = _run3(monkeypatch, '1')
    off_losses, off_param = _run3(monkeypatch, '0')
    for a, b in zip(on_losses, off_losses):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(on_param, off_param, rtol=2e-6, atol=1e-7)
    # and the bucket cap does not change numerics either
    mb_losses, mb_param = _run3(monkeypatch, '1', bucket_mb='100')
    for a, b in zip(on_losses, mb_losses):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(on_param, mb_param)


# ---------------------------------------------------------------------------
# executor: measured overlap fraction + trace counter
# ---------------------------------------------------------------------------

def test_run_steps_reports_measured_overlap(monkeypatch, tmp_path):
    from paddle_tpu.observability import timeline as tlm
    monkeypatch.setenv('PADDLE_TPU_TRACE_DIR', str(tmp_path))
    monkeypatch.setenv('PADDLE_TPU_MESH', 'dp=2')
    monkeypatch.setenv('PADDLE_TPU_OVERLAP_BUCKET_MB', '1')
    tlm.reset()
    try:
        main, startup, loss = _wide_mlp()
        scope = fluid.core.scope.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            exe.run_steps(main, feed=[_np_feed(i) for i in range(2)],
                          fetch_list=[loss])
            rep = exe.last_step_report
        phase = rep['phases']['collective']
        assert phase['overlap_basis'] == 'measured-compute'
        # CPU compute walls dwarf the modeled 100 GB/s transfers, so
        # the measured schedule hides (essentially) everything — this
        # is the >= 80% acceptance bar at its bench operating point
        assert phase['overlap_fraction'] >= 0.8
        assert phase['exposed_bytes_per_step'] + \
            phase['overlapped_bytes_per_step'] == \
            phase['modeled_ici_bytes_per_step']
        # the static (roofline-priced) schedule rides in the cost dict
        assert rep['cost']['collectives']['overlap'][
            'overlap_fraction'] >= 0.0
        # Chrome-trace counter series, 0-100 percent
        samples = [e for e in tlm.ring().events(cat='collective')
                   if e.get('ph') == 'C'
                   and e['name'] == 'paddle_tpu.collective_overlap_pct']
        assert samples, "no overlap counter series recorded"
        assert 80 <= samples[-1]['args']['bytes'] <= 100
    finally:
        monkeypatch.delenv('PADDLE_TPU_TRACE_DIR', raising=False)
        monkeypatch.delenv('PADDLE_TPU_MESH', raising=False)
        tlm.reset()


# ---------------------------------------------------------------------------
# pp mesh axis
# ---------------------------------------------------------------------------

def test_parse_mesh_spec_compact_forms():
    assert spec_layout.parse_mesh_spec('pp2') == (('pp', 2),)
    assert spec_layout.parse_mesh_spec('pp2,fsdp2') == \
        (('pp', 2), ('fsdp', 2))
    assert spec_layout.parse_mesh_spec('pp2,dp=2') == \
        (('pp', 2), ('dp', 2))
    assert spec_layout.parse_mesh_spec('pipe=2') == (('pp', 2),)
    with pytest.raises(ValueError):
        spec_layout.parse_mesh_spec('pp0')


def _pp_mlp(annotate=True):
    from paddle_tpu.distributed import pipeline as pl
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with reset_unique_name_guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[64], dtype='float32')
        label = fluid.layers.data(name='label', shape=[1],
                                  dtype='int64')
        h1 = fluid.layers.fc(input=x, size=64, act='relu')
        h2 = fluid.layers.fc(input=h1, size=64, act='relu')
        h3 = fluid.layers.fc(input=h2, size=64, act='relu')
        if annotate:
            pl.annotate_pp_cut(h1, main)
            pl.annotate_pp_cut(h2, main)
            pl.annotate_pp_cut(h3, main)
        pred = fluid.layers.fc(input=h3, size=10, act='softmax')
        loss = fluid.layers.mean(x=fluid.layers.cross_entropy(
            input=pred, label=label))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    return main, startup, loss


def test_pp_plan_block_and_bubble(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_PP_MICROBATCHES', '4')
    main, _s, loss = _pp_mlp()
    prog, rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('x', 'label'),
        feed_specs=_FEEDS, mesh='pp2,dp=2', verify='boundary')
    plan = prog._sharding_plan
    pp = plan['pp']
    assert pp['stages'] == 2 and pp['microbatches'] == 4
    # the 1F1B closed form (S-1)/(M+S-1) = 1/5
    assert pp['bubble_fraction'] == 0.2
    assert len(pp['cuts']) == 1  # balanced pick from 3 candidates
    assert pp['cuts'][0] in pp['annotated']
    # boundary ppermute priced at 2x the cut var (fwd act + bwd cot)
    perms = [c for c in plan['collectives'] if c['kind'] == 'ppermute']
    assert [c['name'] for c in perms] == list(pp['cuts'])
    # cut var is [B, 64] f32, batch dp-sharded 2 ways -> 4*64*4 bytes
    assert perms[0]['bytes'] == 2 * (B // 2) * 64 * 4
    # the cost model carries the pp exposure term + report block
    coll = rep['cost']['collectives']
    assert coll['pp']['bubble_fraction'] == 0.2
    assert coll['pp']['ppermute_ici_bytes'] > 0
    assert rep['sharding']['pp']['stages'] == 2
    # bubble closed form tracks M
    monkeypatch.setenv('PADDLE_TPU_PP_MICROBATCHES', '9')
    main2, _s2, loss2 = _pp_mlp()
    prog2, _ = pm.run_pipeline(
        main2, fetch_names=(loss2.name,), feed_names=('x', 'label'),
        feed_specs=_FEEDS, mesh='pp2', verify='boundary')
    assert prog2._sharding_plan['pp']['bubble_fraction'] == 0.1


def test_pp_plan_without_cuts_carries_note():
    main, _s, loss = _pp_mlp(annotate=False)
    prog, _rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('x', 'label'),
        feed_specs=_FEEDS, mesh='pp2', verify='boundary')
    pp = prog._sharding_plan['pp']
    assert pp['cuts'] is None
    assert 'annotate_pp_cut' in pp['note']
    assert not [c for c in prog._sharding_plan['collectives']
                if c['kind'] == 'ppermute']


def test_select_pp_cuts_balancing():
    main, _s, _loss = _pp_mlp()
    names = tuple(main._pp_cut_names)
    assert len(names) == 3
    # exact count passes through in program order
    assert sharding_mod.select_pp_cuts(main, names, 4) == names
    # too few candidates -> None
    assert sharding_mod.select_pp_cuts(main, names[:1], 4) is None
    # S=2 picks ONE balanced cut strictly from the candidates
    cut2 = sharding_mod.select_pp_cuts(main, names, 2,
                                       feed_specs=_FEEDS)
    assert len(cut2) == 1 and cut2[0] in names
    # uniform layers -> the middle candidate balances best
    assert cut2[0] == names[1]


def test_executor_refuses_pp_train_program(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_MESH', 'pp2')
    main, startup, loss = _pp_mlp()
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)  # startup has no autodiff: runs replicated
        with pytest.raises(RuntimeError, match='from_mesh'):
            exe.run(main, feed=_np_feed(), fetch_list=[loss])


def test_from_mesh_needs_pp_axis_and_cuts(monkeypatch):
    from paddle_tpu.distributed import pipeline as pl
    main, _s, _loss = _pp_mlp(annotate=False)
    monkeypatch.setenv('PADDLE_TPU_MESH', 'dp=2')
    with pytest.raises(ValueError, match='pp'):
        pl.from_mesh(main)
    monkeypatch.setenv('PADDLE_TPU_MESH', 'pp2')
    with pytest.raises(ValueError, match='annotate_pp_cut'):
        pl.from_mesh(main)


def test_from_mesh_cuts_and_microbatches(monkeypatch):
    from paddle_tpu.distributed import pipeline as pl
    monkeypatch.setenv('PADDLE_TPU_MESH', 'pp2')
    monkeypatch.setenv('PADDLE_TPU_PP_MICROBATCHES', '4')
    main, _s, _loss = _pp_mlp()
    t = pl.from_mesh(main)
    assert t.num_stages == 2
    assert t.num_microbatches == 4
    assert t.cut_names == [main._pp_cut_names[1]]  # balanced middle
    assert t.mesh.shape['pp'] == 2


def test_from_mesh_pp2_loss_parity(monkeypatch):
    """pp=2 1F1B run matches the no-pp executor losses to pinned
    tolerance (f32 reduction-order differences only)."""
    from paddle_tpu.distributed import pipeline as pl
    monkeypatch.setenv('PADDLE_TPU_MESH', 'pp2')
    monkeypatch.setenv('PADDLE_TPU_PP_MICROBATCHES', '4')
    main, startup, loss = _pp_mlp()
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        t = pl.from_mesh(main)
        pp_losses = [float(t.run_mesh_step(exe, _np_feed(i)))
                     for i in range(3)]
    monkeypatch.delenv('PADDLE_TPU_MESH')
    main2, startup2, loss2 = _pp_mlp(annotate=False)
    scope2 = fluid.core.scope.Scope()
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(startup2)
        ref = [float(np.asarray(exe2.run(main2, feed=_np_feed(i),
                                         fetch_list=[loss2])[0]))
               for i in range(3)]
    np.testing.assert_allclose(pp_losses, ref, rtol=2e-5, atol=2e-6)

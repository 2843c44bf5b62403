"""Sharding-propagation pass + SPMD executor tests
(transpiler/sharding.py, distributed/spec_layout.py, the
PADDLE_TPU_MESH executor path).

Golden per-op sharding tables on MLP / VGG / LSTM programs; the
ring-allreduce closed form pinned exactly; fsdp=8 modeled per-device
optimizer-state bytes at ~1/8; executor loss parity dp=2 / fsdp=2 vs
single-device on the 8 forced host devices (conftest.py); mesh=dp=1
bitwise-identical to no-mesh; feed donation APPLIED (not skipped)
under the mesh; the `collective` timeline phase; and PADDLE_TPU_MESH
flag-flip plan-cache invalidation on both run and run_steps paths.
"""
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.program import reset_unique_name_guard
from paddle_tpu.distributed import mesh_flag, spec_layout
from paddle_tpu.transpiler import pass_manager as pm
from paddle_tpu.transpiler import sharding as sharding_mod
from paddle_tpu.transpiler.verify import (IRVerificationError,
                                          verify_program)

B = 8


# ---------------------------------------------------------------------------
# spec vocabulary
# ---------------------------------------------------------------------------

def test_parse_mesh_spec_normalizes():
    assert spec_layout.parse_mesh_spec('dp=2') == (('dp', 2),)
    assert spec_layout.parse_mesh_spec('dp=4, tp=2') == \
        (('dp', 4), ('tp', 2))
    assert spec_layout.parse_mesh_spec('data=2,model=2') == \
        (('dp', 2), ('tp', 2))  # aliases canonicalize
    assert spec_layout.parse_mesh_spec('fsdp=8') == (('fsdp', 8),)


@pytest.mark.parametrize('bad', ['dp', 'dp=x', 'dp=0', 'dp=2,dp=4',
                                 'warp=2', ','])
def test_parse_mesh_spec_rejects(bad):
    with pytest.raises(ValueError):
        spec_layout.parse_mesh_spec(bad)


def test_spec_layout_roles():
    lo = spec_layout.SpecLayout({'dp': 2, 'fsdp': 2, 'tp': 2})
    assert lo.batch_axis == 'dp'
    assert lo.batch(3) == ('dp', None, None)
    assert lo.batch(2, batch_size=7) is None  # indivisible: refuse
    # largest divisible dim over fsdp, trailing preferred
    assert lo.param((16, 32)) == (None, 'fsdp')
    assert lo.param((3,)) is None  # nothing divides
    # embeddings: rows over (fsdp, tp) — the SNIPPETS.md [1] spec
    assert lo.embeddings((64, 16)) == (('fsdp', 'tp'), None)
    pure = spec_layout.SpecLayout({'fsdp': 4})
    assert pure.batch_axis == 'fsdp'  # pure-ZeRO mesh: fsdp IS data


def test_spec_divisor_and_normalize():
    axes = {'dp': 2, 'fsdp': 4}
    assert spec_layout.spec_divisor((None, 'fsdp'), axes) == 4
    assert spec_layout.spec_divisor((('dp', 'fsdp'), None), axes) == 8
    # axes the mesh lacks drop out (degrade to replication)
    assert spec_layout.normalize_spec(('tp', None), 2, axes) == \
        (None, None)


# ---------------------------------------------------------------------------
# golden pass tables
# ---------------------------------------------------------------------------

def _mlp(seed=7):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with reset_unique_name_guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        label = fluid.layers.data(name='label', shape=[1],
                                  dtype='int64')
        h = fluid.layers.fc(input=x, size=32, act='relu')
        pred = fluid.layers.fc(input=h, size=8, act='softmax')
        loss = fluid.layers.mean(x=fluid.layers.cross_entropy(
            input=pred, label=label))
        fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
    return main, startup, loss


_MLP_FEEDS = {'x': ((B, 16), 'float32'), 'label': ((B, 1), 'int32')}


def _out_specs_of(prog):
    """{name: spec} union of every op's stamped sharding_out table."""
    out = {}
    for op in prog.global_block().ops:
        for name, spec in (op.attrs.get('sharding_out') or ()):
            if spec is not None:
                out[name] = spec
    return out


def test_golden_mlp_dp2_table():
    main, _s, loss = _mlp()
    prog, rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('x', 'label'),
        feed_specs=_MLP_FEEDS, mesh='dp=2', verify='every_pass')
    plan = prog._sharding_plan
    assert plan['mesh_axes'] == (('dp', 2),)
    assert plan['batch_axis'] == 'dp'
    assert plan['batch'] == B
    # feeds batch-shard over dp
    assert plan['feeds']['x'] == ('dp', None)
    assert plan['feeds']['label'] == ('dp', None)
    # dp alone shards no parameters
    assert plan['params'] == {}
    specs = _out_specs_of(prog)
    # activations ride the batch axis; grads replicate like params
    assert specs['fc_0.tmp_1'] == ('dp', None)
    assert specs['fc_0.w_0@GRAD'] == (None, None)
    # every trainable param grad allreduces over dp
    kinds = {c['kind'] for c in plan['collectives']}
    assert kinds == {'allreduce'}
    names = {c['name'] for c in plan['collectives']}
    assert 'fc_0.w_0@GRAD' in names and 'fc_1.b_0@GRAD' in names
    assert rep['sharding']['ops_annotated'] == \
        len(prog.global_block().ops)


def test_golden_mlp_fsdp2_params_and_accumulators():
    main, _s, loss = _mlp()
    prog, _rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('x', 'label'),
        feed_specs=_MLP_FEEDS, mesh='fsdp=2', verify='every_pass')
    plan = prog._sharding_plan
    params = plan['params']
    # params shard their largest divisible dim...
    assert params['fc_0.w_0'] == (None, 'fsdp')
    assert params['fc_1.w_0'] == (None, 'fsdp')
    assert params['fc_0.b_0'] == ('fsdp',)
    # ...and so do their Adam moments (the whole point of fsdp)
    assert params['fc_0.w_0_moment1_0'] == (None, 'fsdp')
    assert params['fc_0.w_0_moment2_0'] == (None, 'fsdp')
    # beta-pow scalars replicate (shape [1] never matches)
    assert not any('beta' in n for n in params)
    # grads reduce-scatter to the shard owner, params all-gather back
    by_kind = {}
    for c in plan['collectives']:
        by_kind.setdefault(c['kind'], set()).add(c['name'])
    assert 'fc_0.w_0@GRAD' in by_kind['reduce_scatter']
    assert 'fc_0.w_0' in by_kind['all_gather']


def test_collective_ring_closed_form_dp4():
    """Acceptance pin: allreduce ICI bytes == 2(N-1)/N x payload."""
    main, _s, loss = _mlp()
    prog, rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('x', 'label'),
        feed_specs=_MLP_FEEDS, mesh='dp=4', verify='boundary')
    coll = rep['cost']['collectives']
    assert coll is not None and coll['items']
    expect = 0
    for it in coll['items']:
        assert it['kind'] == 'allreduce' and it['n'] == 4
        assert it['ici_bytes'] == int(2 * (4 - 1) / 4 * it['bytes'])
        expect += it['ici_bytes']
    assert coll['ici_bytes'] == expect > 0
    # the 16x32 fc weight grad: 2048 bytes payload -> 3072 over ICI
    w = {it['name']: it for it in coll['items']}['fc_0.w_0@GRAD']
    assert w['bytes'] == 16 * 32 * 4
    assert w['ici_bytes'] == 3072


def test_memory_model_fsdp8_eighth_state():
    """Acceptance pin: fsdp=8 models ~1/8 of param+accumulator bytes
    per device (exact up to the replicated beta-pow/LR scalars)."""
    main, startup = fluid.Program(), fluid.Program()
    with reset_unique_name_guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[32], dtype='float32')
        h = fluid.layers.fc(input=x, size=64, act='relu')
        y = fluid.layers.fc(input=h, size=64)
        loss = fluid.layers.mean(x=y)
        fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
    prog, rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('x',),
        feed_specs={'x': ((B, 32), 'float32')}, mesh='fsdp=8',
        verify='boundary')
    mem = rep['cost']['memory']
    full = mem['sharding']['persistable_bytes_unsharded']
    per_dev = mem['persistable_bytes']
    assert full > 0
    ratio = per_dev / full
    assert 1 / 8 <= ratio < 1 / 8 + 0.03, ratio
    # feeds divide too (batch rides fsdp on a pure-ZeRO mesh)
    assert mem['feed_bytes'] == B * 32 * 4 // 8


def test_golden_vgg_conv_program_dp2():
    main, startup = fluid.Program(), fluid.Program()
    with reset_unique_name_guard(), fluid.program_guard(main, startup):
        from paddle_tpu.models import vgg
        img = fluid.layers.data(name='img', shape=[3, 32, 32],
                                dtype='float32')
        label = fluid.layers.data(name='label', shape=[1],
                                  dtype='int64')
        pred = vgg.vgg16_bn_drop(img, num_classes=10)
        loss = fluid.layers.mean(x=fluid.layers.cross_entropy(
            input=pred, label=label))
        fluid.optimizer.MomentumOptimizer(0.01, 0.9).minimize(loss)
    prog, rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('img', 'label'),
        feed_specs={'img': ((4, 3, 32, 32), 'float32'),
                    'label': ((4, 1), 'int32')},
        mesh='dp=2', verify='boundary')
    plan = prog._sharding_plan
    assert plan['feeds']['img'] == ('dp', None, None, None)
    specs = _out_specs_of(prog)
    # conv activations batch-shard; every conv filter grad allreduces
    conv_outs = [n for n, s in specs.items()
                 if n.startswith('conv2d_') and s and s[0] == 'dp']
    assert conv_outs
    names = {c['name'] for c in plan['collectives']}
    assert any(n.startswith('conv2d_0.w_0@GRAD') for n in names)
    assert rep['sharding']['collectives'] == len(plan['collectives'])


def test_golden_lstm_program_dp2():
    from paddle_tpu.core.program import LEN_SUFFIX
    from paddle_tpu.models import rnn_lm
    main, startup = fluid.Program(), fluid.Program()
    with reset_unique_name_guard(), fluid.program_guard(main, startup):
        src, target, avg_cost = rnn_lm.build(
            vocab_size=64, emb_dim=16, hidden_dim=16, num_layers=1)
        fluid.optimizer.AdagradOptimizer(0.1).minimize(avg_cost)
    T = 4
    feed_specs = {
        'src': ((B, T, 1), 'int32'),
        'src' + LEN_SUFFIX: ((B,), 'int32'),
        'target': ((B, T, 1), 'int32'),
        'target' + LEN_SUFFIX: ((B,), 'int32'),
    }
    prog, rep = pm.run_pipeline(
        main, fetch_names=(avg_cost.name,),
        feed_names=tuple(feed_specs), feed_specs=feed_specs,
        mesh='dp=2', verify='boundary')
    plan = prog._sharding_plan
    # token ids AND their ragged-length companions batch-shard
    assert plan['feeds']['src'] == ('dp', None, None)
    assert plan['feeds']['src' + LEN_SUFFIX] == ('dp',)
    # one allreduce per trainable param (embedding, fc w/b, lstm
    # weight/bias, per-param adagrad state stays local)
    kinds = {c['kind'] for c in plan['collectives']}
    assert kinds == {'allreduce'}
    names = {c['name'] for c in plan['collectives']}
    assert any('embedding' in n or 'emb' in n for n in names) or \
        any('w_0@GRAD' in n for n in names)
    assert rep['sharding']['ops_annotated'] > 0


def test_tp_plan_folds_into_spec_table():
    """The TensorParallelTranspiler plan is the ONE tp spec source:
    transpile() stamps it on the program and build_param_specs folds
    it in (normalized to the mesh's axes)."""
    from paddle_tpu.distributed import TensorParallelTranspiler
    main, startup = fluid.Program(), fluid.Program()
    with reset_unique_name_guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        h = fluid.layers.fc(input=x, size=32)
        loss = fluid.layers.mean(x=h)
    t = TensorParallelTranspiler()
    t.transpile(main, trainers=2, shard_specs={'fc_0.w_0': 1})
    assert main._tp_shard_plan  # stamped for the sharding pass
    specs = spec_layout.build_param_specs(
        main, (('dp', 2), ('tp', 2)))
    assert specs['fc_0.w_0'] == (None, 'tp')
    # a mesh without tp degrades the plan instead of crashing
    specs_dp = spec_layout.build_param_specs(main, (('dp', 2),))
    assert 'fc_0.w_0' not in specs_dp


def test_embedding_table_row_shards_over_fsdp_x_tp():
    """The SpecLayout embeddings role is wired: a lookup_table weight
    on an fsdp x tp mesh row-shards over BOTH axes (SNIPPETS [1]
    ``PS((fsdp, tp), None)``), not just fsdp."""
    main, startup = fluid.Program(), fluid.Program()
    with reset_unique_name_guard(), fluid.program_guard(main, startup):
        ids = fluid.layers.data(name='ids', shape=[1], dtype='int64')
        emb = fluid.layers.embedding(input=ids, size=[64, 16])
        loss = fluid.layers.mean(x=emb)
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    specs = spec_layout.build_param_specs(
        main, (('fsdp', 2), ('tp', 2)))
    emb_w = [n for n in specs if 'embedding' in n or 'emb' in n
             or 'w_0' in n]
    assert emb_w, specs
    assert specs[emb_w[0]] == (('fsdp', 'tp'), None)


def test_compile_path_pins_mesh_off(monkeypatch):
    """compile()/compile_raw() hand out single-device executables
    (AOT/export/serving, and run_sharded re-jits with its own plan):
    under a process-wide PADDLE_TPU_MESH their plan must NOT run the
    sharding pass — a sharded memory report over an unsharded fn
    would under-state per-device residency by the shard count."""
    monkeypatch.setenv('PADDLE_TPU_MESH', 'fsdp=2')
    main, startup, loss = _mlp()
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.compile(main, feed=_STEP_FEEDS[0], fetch_list=[loss])
        rep = exe.last_graph_opt_report
        assert 'sharding' not in rep
        assert (rep['cost']['memory'].get('sharding')) is None


def test_param_dim0_coinciding_with_batch_stays_plan_owned():
    """A weight whose dim0 happens to equal the batch size must NOT be
    re-sharded by the batch rule at its optimizer update (that would
    poison the memory model's divisors with a phantom split)."""
    main, startup = fluid.Program(), fluid.Program()
    with reset_unique_name_guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        h = fluid.layers.fc(input=x, size=32)  # w_0 is [16, 32]
        loss = fluid.layers.mean(x=h)
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    prog, _rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('x',),
        # batch 16 == the weight's dim0
        feed_specs={'x': ((16, 16), 'float32')}, mesh='dp=2',
        verify='boundary')
    plan = prog._sharding_plan
    assert 'fc_0.w_0' not in plan['divisors']
    specs = _out_specs_of(prog)
    assert specs.get('fc_0.w_0') in (None, (None, None))


# ---------------------------------------------------------------------------
# verifier: sharding annotations are checked like AMP's casts
# ---------------------------------------------------------------------------

def test_verify_rejects_bogus_axis_and_indivisible_dim():
    main, _s, loss = _mlp()
    prog, _rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('x', 'label'),
        feed_specs=_MLP_FEEDS, mesh='dp=2', verify='boundary')
    ops = prog.global_block().ops
    ops[0].attrs['sharding_out'] = (('ghost', ('bogus',)),)
    errs = verify_program(prog, fetch_names=(loss.name,),
                          feed_names=('x', 'label'))
    assert any("names axis 'bogus'" in e for e in errs), errs
    # indivisible split: fc_0.b_0 is [32]; claim a 3-way-odd split
    prog2, _ = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('x', 'label'),
        feed_specs=_MLP_FEEDS, mesh='dp=2', verify='boundary')
    prog2._sharding_plan['params']['fc_0.w_0'] = ('dp', None)
    # 16 % 2 == 0 -> divisible; use the label var rank mismatch instead
    prog2._sharding_plan['params']['fc_0.b_0'] = ('dp', 'dp')
    errs2 = verify_program(prog2, fetch_names=(loss.name,),
                           feed_names=('x', 'label'))
    assert any('rank' in e for e in errs2), errs2


# ---------------------------------------------------------------------------
# executor: the pjit-lowered SPMD step
# ---------------------------------------------------------------------------

_FEED_RNG = np.random.default_rng(0)
_STEP_FEEDS = [{'x': _FEED_RNG.normal(size=(B, 16)).astype(np.float32),
                'label': _FEED_RNG.integers(0, 8, (B, 1)).astype(
                    np.int32)} for _ in range(4)]


def _train(mesh, monkeypatch, prefetch=None):
    if mesh:
        monkeypatch.setenv('PADDLE_TPU_MESH', mesh)
    else:
        monkeypatch.delenv('PADDLE_TPU_MESH', raising=False)
    if prefetch is not None:
        monkeypatch.setenv('PADDLE_TPU_DEVICE_PREFETCH', prefetch)
    main, startup, loss = _mlp()
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        l0 = exe.run(main, feed=_STEP_FEEDS[0], fetch_list=[loss])[0]
        ls = exe.run_steps(main, feed=_STEP_FEEDS[1:],
                           fetch_list=[loss])
        rep = exe.last_step_report
        graph_rep = exe.last_graph_opt_report
        cache_keys = list(exe._cache)
    return (np.asarray(l0), np.asarray(ls[0]), rep, graph_rep,
            cache_keys)


def test_executor_dp2_loss_parity_and_collective_phase(monkeypatch):
    l0r, lsr, _rep, _g, _k = _train(None, monkeypatch)
    l0, ls, rep, graph_rep, _k = _train('dp=2', monkeypatch)
    # acceptance: train-step loss matches single-device to tolerance
    np.testing.assert_allclose(l0, l0r, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(ls, lsr, rtol=2e-6, atol=2e-6)
    # the gradient allreduce appears with a nonzero cost estimate...
    coll = graph_rep['cost']['collectives']
    assert coll['ici_bytes'] > 0
    assert {i['kind'] for i in coll['items']} == {'allreduce'}
    # ...and as a `collective` step phase next to feed/compute/update
    phase = rep['phases']['collective']
    assert phase['modeled_ici_bytes'] == coll['ici_bytes'] * 3
    assert phase['collectives'] == len(coll['items']) * 3


def test_executor_fsdp2_parity_memory_and_donation(monkeypatch):
    l0r, lsr, _rep, _g, _k = _train(None, monkeypatch)
    l0, ls, rep, graph_rep, keys = _train('fsdp=2', monkeypatch)
    np.testing.assert_allclose(l0, l0r, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(ls, lsr, rtol=2e-6, atol=2e-6)
    # acceptance: per-device optimizer-state bytes halved
    mem = graph_rep['cost']['memory']
    full = mem['sharding']['persistable_bytes_unsharded']
    assert mem['persistable_bytes'] < 0.6 * full
    # acceptance: feed donation APPLIED under the mesh, not skipped —
    # run() built the donating plan variant (feed_donate is the last
    # component of the run plan key)
    assert any(k[-1] is True for k in keys
               if isinstance(k, tuple) and k and k[0] != 'multi')


def test_executor_mesh1_bitwise_vs_no_mesh(monkeypatch):
    l0r, lsr, _rep, _g, _k = _train(None, monkeypatch)
    l0, ls, _rep2, _g2, _k2 = _train('dp=1', monkeypatch)
    assert np.array_equal(l0, l0r)
    assert np.array_equal(ls, lsr)


def test_executor_dp2_prefetch_parity(monkeypatch):
    l0r, lsr, _rep, _g, _k = _train(None, monkeypatch, prefetch='0')
    l0, ls, rep, _g2, _k2 = _train('dp=2', monkeypatch, prefetch='1')
    np.testing.assert_allclose(ls, lsr, rtol=2e-6, atol=2e-6)
    assert rep['chunks'] > 1  # the chunked pipeline actually ran
    assert 'collective' in rep['phases']


def test_collective_timeline_event(monkeypatch, tmp_path):
    from paddle_tpu.observability import timeline as tlm
    monkeypatch.setenv('PADDLE_TPU_TRACE_DIR', str(tmp_path))
    monkeypatch.setenv('PADDLE_TPU_MESH', 'dp=2')
    tlm.reset()
    try:
        main, startup, loss = _mlp()
        scope = fluid.core.scope.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            exe.run_steps(main, feed=_STEP_FEEDS[:2],
                          fetch_list=[loss])
        evs = tlm.ring().events(cat='collective')
        assert evs, "no collective-category timeline event recorded"
        assert evs[-1]['args']['modeled_ici_bytes'] > 0
        # est wall appears when the link bandwidth is declared
        monkeypatch.setenv('PADDLE_TPU_ICI_GBPS', '100')
        with fluid.scope_guard(scope):
            exe.run_steps(main, feed=_STEP_FEEDS[:2],
                          fetch_list=[loss])
        evs = tlm.ring().events(cat='collective')
        assert evs[-1]['args']['est_wall_s'] > 0
    finally:
        monkeypatch.delenv('PADDLE_TPU_TRACE_DIR', raising=False)
        monkeypatch.delenv('PADDLE_TPU_MESH', raising=False)
        tlm.reset()


def test_mesh_flag_flip_rekeys_run_and_run_steps(monkeypatch):
    """Acceptance: flipping PADDLE_TPU_MESH re-keys the run plan AND
    the run_steps plan through the ONE composite pass-config key."""
    monkeypatch.delenv('PADDLE_TPU_MESH', raising=False)
    main, startup, loss = _mlp()
    feed = _STEP_FEEDS[0]
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        exe.run_steps(main, feed=[feed, feed], fetch_list=[loss])
        n0 = len(exe._cache)
        for spec in ('dp=2', 'fsdp=2'):
            monkeypatch.setenv('PADDLE_TPU_MESH', spec)
            exe.run(main, feed=feed, fetch_list=[loss])
            exe.run_steps(main, feed=[feed, feed], fetch_list=[loss])
            n1 = len(exe._cache)
            assert n1 >= n0 + 2, (
                "flipping PADDLE_TPU_MESH to %s did not re-key both "
                "run and run_steps plans (%d -> %d)" % (spec, n0, n1))
            n0 = n1


def test_mesh_errors_actionably_on_too_few_devices(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_MESH', 'dp=64')
    main, startup, loss = _mlp()
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        with pytest.raises(RuntimeError,
                           match='xla_force_host_platform'):
            exe.run(startup)


def test_parallel_do_program_keeps_legacy_path(monkeypatch):
    """A program with its own parallel_do distribution ignores
    PADDLE_TPU_MESH (one distribution mechanism per program)."""
    monkeypatch.setenv('PADDLE_TPU_MESH', 'dp=2')
    main, _s, loss = _mlp()
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        assert exe._spmd_mesh(main) is not None
        main.global_block().append_op(type='parallel_do', inputs={},
                                      outputs={}, attrs={})
        main._bump_version()
        assert exe._spmd_mesh(main) is None


def test_overlap_buckets_exclude_embed_all_to_alls(monkeypatch):
    """Composition pin: overlap_collectives (order 88) runs after
    embed_shard (order 87) and must bucket ONLY the parameter-gradient
    allreduce/reduce-scatters — the embedding lookup's two all_to_all
    entries are forward-path traffic with no backward window to hide
    in, so they stay out of every bucket but remain priced in the
    collective total."""
    monkeypatch.setenv('PADDLE_TPU_OVERLAP_BUCKET_MB', '1')
    main, startup = fluid.Program(), fluid.Program()
    with reset_unique_name_guard(), fluid.program_guard(main, startup):
        ids = fluid.layers.data(name='ids', shape=[1], dtype='int64')
        emb = fluid.layers.embedding(input=ids, size=[64, 16],
                                     is_sparse=False, param_attr='tbl')
        h = fluid.layers.fc(input=emb, size=8, act='relu')
        loss = fluid.layers.mean(x=h)
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    prog, rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=('ids',),
        feed_specs={'ids': ((B, 1), 'int32')}, mesh='fsdp=4',
        verify='every_pass')
    plan = prog._sharding_plan
    a2a = [c for c in plan['collectives'] if c['kind'] == 'all_to_all']
    assert len(a2a) == 2, plan['collectives']
    sched = plan.get('overlap')
    assert sched and sched['buckets'], rep.get('overlap')
    bucketed = {n for b in sched['buckets'] for n in b['names']}
    assert bucketed, sched
    assert bucketed.isdisjoint({c['name'] for c in a2a})
    # every bucketed collective is a gradient reduction by kind
    by_name = {c['name']: c for c in plan['collectives']}
    for n in bucketed:
        assert by_name[n]['kind'] in ('allreduce', 'reduce_scatter')
    # the split stays coherent with the a2a traffic folded in: the
    # all_to_alls can never be credited as overlapped
    coll = rep['cost']['collectives']
    split = coll['bytes']
    assert split['exposed'] + split['overlapped'] == split['total']
    a2a_ici = sum(c.get('ici_bytes', c['bytes']) for c in a2a)
    assert split['exposed'] >= min(a2a_ici, split['total'] -
                                   split['overlapped'])
    assert split['total'] == coll['ici_bytes']

"""Liveness-based peak-memory model (transpiler/memory_model.py):
hand-computed golden peaks, feed-donation credit, the bf16 byte shrink,
remat working-set reduction, the executor/pipeline join
(last_graph_opt_report['cost']['memory'] + last_step_report['memory']),
and the level-0 bypass.

Every golden below is derived by hand from the program's declared
shapes — a liveness or sizing regression shows up as an exact mismatch,
not a tolerance.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.transpiler import memory_model

B = 4


def _fwd_program():
    """x[B,4] -> fc(8) -> mean.  Ops: mul, elementwise_add, mean."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        h = fluid.layers.fc(input=x, size=8)
        out = fluid.layers.mean(x=h)
    return main, startup, out


# hand-derived constants for _fwd_program at B=4, f32:
_PERSIST = (4 * 8 + 8) * 4          # fc w[4,8] + b[8]
_FEED = B * 4 * 4                   # x[B,4]
_TMP = B * 8 * 4                    # each fc intermediate [B,8]
_OUT = 1 * 4                        # mean out [1]


def test_forward_golden_peak_and_watermark():
    main, _startup, out = _fwd_program()
    rep = memory_model.analyze_memory(
        main, fetch_names=(out.name,),
        feed_specs={'x': ((B, 4), 'float32')})
    # walk: op0 mul    = persist + x + tmp0         = 160+64+128 = 352
    #       op1 add    = persist + tmp0 + tmp1      = 160+256    = 416 *
    #       op2 mean   = persist + tmp1 + out       = 160+128+4  = 292
    # (x is donated: credited after its last use at op0)
    assert rep['persistable_bytes'] == _PERSIST
    assert rep['feed_bytes'] == _FEED
    assert rep['peak_bytes'] == _PERSIST + 2 * _TMP
    assert rep['peak_intermediate_bytes'] == 2 * _TMP
    wm = rep['watermark'][0]
    assert wm['type'] == 'elementwise_add' and wm['index'] == 1
    assert wm['live_bytes'] == rep['peak_bytes']
    # the full sawtooth, op by op
    assert [e['live_bytes'] for e in rep['timeline']] == [
        _PERSIST + _FEED + _TMP,
        _PERSIST + 2 * _TMP,
        _PERSIST + _TMP + _OUT,
    ]
    cov = rep['coverage']
    assert cov['no_verdict'] == [] and cov['unsized_vars'] == []


def test_donation_credit_is_the_feed_delta():
    """Without the donation credit the feed buffer stays live across
    the whole step — the modeled peak grows by exactly the feed
    bytes."""
    main, _startup, out = _fwd_program()
    specs = {'x': ((B, 4), 'float32')}
    donated = memory_model.analyze_memory(
        main, fetch_names=(out.name,), feed_specs=specs)
    held = memory_model.analyze_memory(
        main, fetch_names=(out.name,), feed_specs=specs,
        donate_feeds=False)
    assert held['peak_bytes'] == donated['peak_bytes'] + _FEED
    assert donated['donated_feed_credit'] is True
    assert held['donated_feed_credit'] is False


def test_fetched_intermediate_lives_to_the_end():
    """Fetching fc's pre-bias output pins it: it can no longer die at
    its last in-graph use, so the mean op's live set grows by it."""
    main, _startup, out = _fwd_program()
    specs = {'x': ((B, 4), 'float32')}
    # the mul op's output (fc's pre-bias tmp), by position — layer
    # name counters are process-global, so never hard-code fc_0.*
    tmp0 = main.global_block().ops[0].outputs['Out'][0]
    base = memory_model.analyze_memory(
        main, fetch_names=(out.name,), feed_specs=specs)
    pinned = memory_model.analyze_memory(
        main, fetch_names=(out.name, tmp0), feed_specs=specs)
    assert pinned['timeline'][-1]['live_bytes'] == \
        base['timeline'][-1]['live_bytes'] + _TMP


def _train_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name='img', shape=[32],
                                dtype='float32')
        label = fluid.layers.data(name='label', shape=[1],
                                  dtype='int64')
        h = fluid.layers.fc(input=img, size=64, act='relu')
        pred = fluid.layers.fc(input=h, size=10, act='softmax')
        loss = fluid.layers.mean(x=fluid.layers.cross_entropy(
            input=pred, label=label))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return main, startup, loss


_TRAIN_SPECS = {'img': ((B, 32), 'float32'),
                'label': ((B, 1), 'int32')}


def test_backward_keeps_activation_frontier_alive():
    """The autodiff op is the watermark of a train step: every saved
    forward activation is still live when it runs, plus the grads it
    writes."""
    main, _startup, loss = _train_program()
    rep = memory_model.analyze_memory(
        main, fetch_names=(loss.name,), feed_specs=_TRAIN_SPECS)
    ad = [e for e in rep['timeline']]
    ops = main.global_block().ops
    ad_idx = [i for i, op in enumerate(ops)
              if op.type == 'autodiff'][0]
    assert rep['watermark'][0]['index'] == ad_idx
    assert rep['watermark'][0]['type'] == 'autodiff'
    # the frontier is strictly larger than any pre-backward forward op
    assert rep['peak_bytes'] > max(
        e['live_bytes'] for e in ad[:ad_idx])
    assert rep['coverage']['no_verdict'] == []


def test_remat_shrinks_the_modeled_working_set():
    """memory_optimize's rematerialization levels reduce the modeled
    peak monotonically: save-everything >= dots (matmul outputs only)
    >= full (recompute everything)."""
    peaks = {}
    for level in (None, 'dots', 'full'):
        main, _startup, loss = _train_program()
        if level is not None:
            fluid.memory_optimize(main, level=level)
        rep = memory_model.analyze_memory(
            main, fetch_names=(loss.name,), feed_specs=_TRAIN_SPECS)
        assert rep['remat_level'] == level
        peaks[level] = rep['peak_bytes']
    assert peaks[None] >= peaks['dots'] >= peaks['full']
    assert peaks[None] > peaks['full']  # remat must actually shrink it


def test_bf16_values_count_two_bytes():
    """Low-precision values size at 2 bytes/element: the same op chain
    over bf16 models exactly half the f32 intermediate bytes (golden,
    no AMP involved — pure dtype sizing)."""
    from paddle_tpu.core.program import Program
    peaks = {}
    for dt in ('float32', 'bfloat16'):
        p = Program()
        b = p.global_block()
        b.create_var(name='mmx', shape=(B, 8), dtype=dt)
        b.append_op(type='scale', inputs={'X': ['mmx']},
                    outputs={'Out': ['mmy']}, attrs={'scale': 2.0})
        b.append_op(type='scale', inputs={'X': ['mmy']},
                    outputs={'Out': ['mmz']}, attrs={'scale': 0.5})
        rep = memory_model.analyze_memory(
            p, fetch_names=('mmz',),
            feed_specs={'mmx': ((B, 8), dt)})
        assert rep['coverage']['no_verdict'] == []
        peaks[dt] = rep['peak_bytes']
    # peak op holds x + y (f32: 2*4*B*8; bf16: 2*2*B*8), exactly
    assert peaks['float32'] == 2 * B * 8 * 4
    assert peaks['bfloat16'] == 2 * B * 8 * 2
    assert peaks['float32'] == 2 * peaks['bfloat16']


def test_amp_pipeline_reports_memory_with_cast_copies():
    """Integration: under the AMP pass the walk sees the rewritten
    program — bf16 aliases size at 2 bytes, but cast PAIRS (the f32
    source and its bf16 copy both live) and f32 master weights mean
    whole-program peak does NOT halve; the model reports what the
    rewrite actually costs instead of the folklore 0.5x."""
    from paddle_tpu.transpiler import pass_manager as pm
    reps = {}
    for amp in ('0', 'bf16'):
        main, _startup, loss = _train_program()
        _out, rep = pm.run_pipeline(
            main, fetch_names=(loss.name,),
            feed_names=tuple(_TRAIN_SPECS), level=2, amp_mode=amp,
            verify='off', feed_specs=_TRAIN_SPECS)
        reps[amp] = rep['cost']['memory']
    assert reps['bf16']['peak_bytes'] > 0
    assert reps['bf16']['coverage']['no_verdict'] == []
    # the two programs genuinely differ under the walk
    assert reps['bf16']['peak_intermediate_bytes'] != \
        reps['0']['peak_intermediate_bytes']


# -- pipeline / executor join ---------------------------------------------

def test_memory_report_reaches_executor_report():
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        main, startup, loss = _train_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {'img': np.zeros((B, 32), np.float32),
                'label': np.zeros((B, 1), np.int64)}
        exe.run(main, feed=feed, fetch_list=[loss])
        mem = exe.last_graph_opt_report['cost']['memory']
        assert mem['peak_bytes'] > 0
        assert mem['watermark'][0]['type'] == 'autodiff'
        assert len(mem['watermark']) >= 3
        # the memory pass is registered and reported like every pass
        names = [e['name'] for e in
                 exe.last_graph_opt_report['passes']]
        assert 'memory_model' in names
        entry = [e for e in exe.last_graph_opt_report['passes']
                 if e['name'] == 'memory_model'][0]
        assert entry['status'] == 'ok'


def test_run_steps_memory_block_honest_on_cpu(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_PEAK_HBM_BYTES', str(1 << 30))
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        main, startup, loss = _train_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feeds = [{'img': np.zeros((B, 32), np.float32),
                  'label': np.zeros((B, 1), np.int64)}
                 for _ in range(2)]
        exe.run_steps(main, feed=feeds, fetch_list=[loss])
    mem = exe.last_step_report['memory']
    assert mem['modeled_peak_bytes'] > 0
    assert mem['watermark_op']['type'] == 'autodiff'
    # CPU backend has no memory_stats(): the report says so, it does
    # not fake a zero
    assert mem['measured'] is None
    assert 'measured_peak_bytes' not in mem
    head = mem['headroom']
    assert head['budget_bytes'] == 1 << 30
    assert 0 < head['modeled_ratio'] < 1
    assert 'measured_ratio' not in head


def test_level0_bypasses_memory_model(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_GRAPH_OPT_LEVEL', '0')
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        main, startup, loss = _train_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feeds = [{'img': np.zeros((B, 32), np.float32),
                  'label': np.zeros((B, 1), np.int64)}
                 for _ in range(2)]
        exe.run_steps(main, feed=feeds, fetch_list=[loss])
    assert exe.last_graph_opt_report is None  # legacy bypass contract
    mem = exe.last_step_report['memory']
    assert mem['modeled_peak_bytes'] is None
    assert mem['watermark_op'] is None
    assert mem['measured'] is None


def test_waivers_name_real_ops():
    from paddle_tpu.core import registry
    for t in memory_model.WAIVED_OPS:
        assert registry.has_op(t), (
            "memory_model.WAIVED_OPS entry %r does not name a "
            "registered op" % t)
    assert 'autodiff' not in memory_model.WAIVED_OPS


# -- collective-overlap in-flight credit ----------------------------------

def _mesh_mem(monkeypatch, overlap, level=None):
    from paddle_tpu.transpiler import pass_manager as pm
    monkeypatch.setenv('PADDLE_TPU_OVERLAP', overlap)
    monkeypatch.setenv('PADDLE_TPU_OVERLAP_BUCKET_MB', '1')
    main, _startup, loss = _train_program()
    if level is not None:
        fluid.memory_optimize(main, level=level)
    prog, rep = pm.run_pipeline(
        main, fetch_names=(loss.name,), feed_names=tuple(_TRAIN_SPECS),
        feed_specs=_TRAIN_SPECS, mesh='dp=2', verify='boundary')
    return prog, rep['cost']['memory']


# all four grads fit one 1 MB bucket; dp leaves params unsharded so the
# in-flight payload is the full f32 gradient byte count:
#   fc_0.w_0[32,64] + fc_0.b_0[64] + fc_1.w_0[64,10] + fc_1.b_0[10]
_GRAD_BYTES = (32 * 64 + 64 + 64 * 10 + 10) * 4


def test_overlap_bucket_charges_peak_exactly(monkeypatch):
    """While a bucket's allreduce overlaps remaining backward compute
    its gradient payload stays live next to the backward frontier: the
    model charges the LARGEST bucket (serial comm channel — one in
    flight at a time) on top of the serial-walk peak, exactly."""
    prog, mem_on = _mesh_mem(monkeypatch, '1')
    _p, mem_off = _mesh_mem(monkeypatch, '0')
    assert mem_off['overlap_bucket_bytes'] == 0
    assert mem_on['overlap_bucket_bytes'] == _GRAD_BYTES
    assert mem_on['peak_bytes'] == \
        mem_off['peak_bytes'] + _GRAD_BYTES
    # the credit agrees with the schedule's own bucket accounting
    buckets = prog._sharding_plan['overlap']['buckets']
    assert max(sum(b['bytes'] for b in (bk,)) for bk in buckets) == \
        max(b['bytes'] for b in buckets) == _GRAD_BYTES


def test_overlap_credit_composes_with_remat(monkeypatch):
    """memory_optimize's remat shrinks the serial walk but the
    in-flight bucket rides on top unchanged — gradients are not
    rematerializable intermediates."""
    _p, dots_on = _mesh_mem(monkeypatch, '1', level='dots')
    _p2, dots_off = _mesh_mem(monkeypatch, '0', level='dots')
    _p3, full_on = _mesh_mem(monkeypatch, '1')
    assert dots_on['overlap_bucket_bytes'] == _GRAD_BYTES
    assert dots_on['peak_bytes'] == \
        dots_off['peak_bytes'] + _GRAD_BYTES
    assert dots_on['peak_bytes'] <= full_on['peak_bytes']


# -- golden: decode page pool (PR-19) --------------------------------------

def test_page_pool_bytes_golden():
    """The acceptance golden: pool bytes = num_pages x page_size x
    heads x head_dim x dtype itemsize, times layers and the K/V pair."""
    assert memory_model.page_pool_bytes(
        16, 8, 4, 32, dtype='float32', n_layers=1, kv=1) == \
        16 * 8 * 4 * 32 * 4
    # both pools, every layer
    assert memory_model.page_pool_bytes(
        16, 8, 4, 32, dtype='float32', n_layers=3, kv=2) == \
        3 * 2 * 16 * 8 * 4 * 32 * 4
    # dtype scales by itemsize
    assert memory_model.page_pool_bytes(
        16, 8, 4, 32, dtype='bfloat16') == \
        memory_model.page_pool_bytes(16, 8, 4, 32) // 2


def test_page_pool_bytes_matches_live_cache():
    """The model charges exactly what the engine keeps resident — the
    trash page included (the cache reports num_pages+1)."""
    from paddle_tpu.inference.decode import PagedKVCache
    cache = PagedKVCache(n_layers=2, num_pages=8, page_size=4,
                         n_heads=2, head_dim=8)
    assert cache.resident_bytes() == memory_model.page_pool_bytes(
        9, 4, 2, 8, dtype='float32', n_layers=2, kv=2)
    assert len(cache.k) == len(cache.v) == 2    # a buffer a layer
    assert cache.resident_bytes() == \
        sum(int(np.prod(pool.shape)) * pool.dtype.itemsize
            for pool in cache.k + cache.v)

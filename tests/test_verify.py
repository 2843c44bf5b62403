"""IR verifier + PassManager tests (transpiler/verify.py,
transpiler/pass_manager.py).

Golden broken programs assert the precise diagnostic for each verifier
check (use-before-def, dangling sub-block ref, dtype-mismatched VarDesc,
duplicated op_seq, renamed persistable, cast-into-AMP_BLACK, signature
mismatches, donation-order inversion); the mutation matrix corrupts one
pass output at a time and proves ``every_pass`` mode pins the failure to
that pass; plus the executor integration — the composite plan-cache key
(graph-opt level / AMP / verify flips re-key run AND run_steps), the
per-pass report, and verify=off restoring the unverified path.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.program import Program, Variable
from paddle_tpu.transpiler import pass_manager as pm
from paddle_tpu.transpiler import verify
from paddle_tpu.transpiler.verify import IRVerificationError


def _data_program():
    """x -> scale -> y, plus a persistable counter write."""
    main = fluid.Program()
    with fluid.program_guard(main):
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        h = fluid.layers.scale(x, scale=2.0)
        y = fluid.layers.elementwise_add(h, h)
        w = main.global_block().create_var(
            name='w_persist', shape=[-1, 4], dtype='float32',
            persistable=True)
        main.global_block().append_op(
            type='assign', inputs={'X': [y]}, outputs={'Out': [w]})
    return main, y.name


# ---------------------------------------------------------------------------
# golden broken programs — each asserts its precise diagnostic
# ---------------------------------------------------------------------------

def test_use_before_def_diagnostic():
    main = Program()
    main.global_block().append_op(
        type='scale', inputs={'X': ['ghost']}, outputs={'Out': ['y']},
        attrs={'scale': 2.0})
    errs = verify.verify_program(main, fetch_names=('y',))
    assert any(
        "op #0 (scale) in block 0 reads 'ghost' before any definition"
        in e for e in errs), errs


def test_dangling_sub_block_ref_diagnostic():
    main = Program()
    main.create_block()  # block 1 exists; 7 does not
    main.current_block_idx = 0
    main.global_block().append_op(
        type='while', inputs={}, outputs={},
        attrs={'sub_block': 7, 'condition': 'c', 'max_iters': 1})
    errs = verify.verify_program(main, feed_names=('c',))
    assert any(
        "attr 'sub_block' references sub-block 7, but the program has "
        "blocks 0..1 (dangling sub-block ref)" in e for e in errs), errs


def test_dtype_mismatched_vardesc_diagnostic():
    main = Program()
    block = main.global_block()
    Variable(block, name='x', shape=(4,), dtype='float32')
    Variable(block, name='y', shape=(4,), dtype='int32')  # wrong
    block.append_op(type='scale', inputs={'X': ['x']},
                    outputs={'Out': ['y']}, attrs={'scale': 2.0})
    errs = verify.verify_program(main, feed_names=('x',))
    assert any(
        "output 'y' is declared int32 but re-inference "
        "(core/infer.py) produces float32" in e for e in errs), errs


def test_shape_mismatched_vardesc_diagnostic():
    main = Program()
    block = main.global_block()
    Variable(block, name='x', shape=(4, 3), dtype='float32')
    Variable(block, name='y', shape=(9, 9), dtype='float32')  # wrong
    block.append_op(type='scale', inputs={'X': ['x']},
                    outputs={'Out': ['y']}, attrs={'scale': 2.0})
    errs = verify.verify_program(main, feed_names=('x',))
    assert any(
        "output 'y' is declared with shape (9, 9) but re-inference "
        "produces (4, 3)" in e for e in errs), errs


def test_duplicated_op_seq_diagnostic():
    main = Program()
    block = main.global_block()
    block.append_op(type='scale', inputs={'X': ['x']},
                    outputs={'Out': ['h']},
                    attrs={'scale': 2.0, 'op_seq': 3})
    block.append_op(type='scale', inputs={'X': ['h']},
                    outputs={'Out': ['y']},
                    attrs={'scale': 2.0, 'op_seq': 3})  # duplicate
    errs = verify.verify_program(main, feed_names=('x',))
    assert any(
        "op #1 (scale) in block 0 carries op_seq 3, but op #0 (scale) "
        "in block 0 already carries op_seq 3" in e and
        "strictly monotonic" in e for e in errs), errs


def test_renamed_persistable_diagnostic():
    main, fetch = _data_program()
    snap = verify.pin_snapshot(main, (fetch,), ('x',))
    # "a pass" renames the persistable's producing output
    for op in main.global_block().ops:
        if 'w_persist' in op.output_arg_names:
            op.outputs = {'Out': ['w_renamed']}
    errs = verify.verify_rewrite(snap, main, (fetch,), ('x',))
    assert any(
        "pinned name 'w_persist' (persistable) was written before the "
        "pass but no surviving op writes it — renamed or eliminated"
        in e for e in errs), errs


def test_retyped_persistable_diagnostic():
    main, fetch = _data_program()
    snap = verify.pin_snapshot(main, (fetch,), ('x',))
    main.global_block().vars['w_persist'].dtype = 'bfloat16'
    errs = verify.verify_rewrite(snap, main, (fetch,), ('x',))
    assert any(
        "persistable var 'w_persist' was re-typed from float32 to "
        "bfloat16" in e for e in errs), errs


def test_cast_into_amp_black_diagnostic():
    main = Program()
    block = main.global_block()
    block.append_op(type='cast', inputs={'X': ['x']},
                    outputs={'Out': ['x@amp.bf16']},
                    attrs={'out_dtype': 'bfloat16'})
    block.append_op(type='softmax', inputs={'X': ['x@amp.bf16']},
                    outputs={'Out': ['y']}, attrs={})
    errs = verify.verify_program(main, feed_names=('x',),
                                 amp_low='bfloat16')
    assert any(
        "op #1 (softmax) in block 0 is AMP_BLACK but reads "
        "'x@amp.bf16' straight from an f32->bfloat16 weaver cast"
        in e for e in errs), errs


def test_duplicate_weaver_cast_diagnostic():
    main = Program()
    block = main.global_block()
    for _ in range(2):  # cast CSE violated: same (src, dtype) twice
        block.append_op(type='cast', inputs={'X': ['x']},
                        outputs={'Out': ['x@amp.bf16']},
                        attrs={'out_dtype': 'bfloat16'})
    errs = verify.verify_program(main, feed_names=('x',),
                                 amp_low='bfloat16')
    assert any(
        "duplicates the AMP cast ('x' -> bfloat16) within one "
        "definition epoch" in e for e in errs), errs


def test_signature_unknown_input_slot_diagnostic():
    main = Program()
    main.global_block().append_op(
        type='scale', inputs={'X': ['x'], 'Bogus': ['x']},
        outputs={'Out': ['y']}, attrs={'scale': 1.0})
    errs = verify.verify_program(main, feed_names=('x',))
    assert any(
        "declares input slot 'Bogus'" in e and
        "only reads ['X']" in e for e in errs), errs


def test_signature_unknown_output_slot_diagnostic():
    main = Program()
    main.global_block().append_op(
        type='scale', inputs={'X': ['x']},
        outputs={'Out': ['y'], 'Phantom': ['z']}, attrs={'scale': 1.0})
    errs = verify.verify_program(main, feed_names=('x',))
    assert any(
        "declares output slot 'Phantom'" in e and
        "would stay undefined" in e for e in errs), errs


def test_signature_missing_required_attr_diagnostic():
    main = Program()
    main.global_block().append_op(
        type='cast', inputs={'X': ['x']}, outputs={'Out': ['y']},
        attrs={})  # cast reads attrs['out_dtype'] unconditionally
    errs = verify.verify_program(main, feed_names=('x',))
    assert any(
        "attr 'out_dtype' is read unconditionally by the compute "
        "function but the OpDesc does not carry it" in e
        for e in errs), errs


def test_unregistered_op_diagnostic():
    main = Program()
    main.global_block().append_op(
        type='definitely_not_an_op', inputs={}, outputs={}, attrs={})
    errs = verify.verify_program(main)
    assert any("op type 'definitely_not_an_op' is not registered" in e
               for e in errs), errs


def test_donation_order_inversion_diagnostic():
    """A read whose op_seq says it preceded an optimizer's in-place
    update must not appear after it (a pass moved it across the kill)."""
    main = Program()
    block = main.global_block()
    Variable(block, name='w', shape=(4,), dtype='float32',
             persistable=True)
    block.append_op(type='sgd',
                    inputs={'Param': ['w'], 'Grad': ['g'],
                            'LearningRate': ['lr']},
                    outputs={'ParamOut': ['w']},
                    attrs={'op_role': 'optimize', 'op_seq': 5})
    block.append_op(type='scale', inputs={'X': ['w']},
                    outputs={'Out': ['y']},
                    attrs={'scale': 1.0, 'op_seq': 2})  # originally BEFORE
    errs = verify.verify_program(main, feed_names=('g', 'lr'))
    assert any(
        "reads 'w' after" in e and "updated in place (donated alias)"
        in e and "read after last legal use" in e for e in errs), errs


def test_clean_program_verifies_clean():
    main, fetch = _data_program()
    assert verify.verify_program(main, (fetch,), ('x',)) == []


# ---------------------------------------------------------------------------
# mutation matrix: corrupt ONE pass's output, prove every_pass pins it
# ---------------------------------------------------------------------------

def _mut_drop_persistable_writer(program):
    blk = program.global_block()
    blk.ops = [op for op in blk.ops
               if 'w_persist' not in op.output_arg_names]


def _mut_read_ghost(program):
    op = program.global_block().ops[0]
    op.inputs = {slot: ['__ghost__' for _ in names]
                 for slot, names in op.inputs.items()}


def _mut_duplicate_op_seq(program):
    ops = program.global_block().ops
    stamped = [op for op in ops if 'op_seq' in op.attrs]
    if len(stamped) >= 2:
        stamped[-1].attrs['op_seq'] = stamped[0].attrs['op_seq']


def _mut_drop_fetch_producer(program):
    blk = program.global_block()
    blk.ops = [op for op in blk.ops
               if not any(n.startswith('elementwise_add')
                          for n in op.output_arg_names)]


def _mut_duplicate_weaver_cast(program):
    blk = program.global_block()
    for _ in range(2):
        blk.append_op(type='cast', inputs={'X': ['x']},
                      outputs={'Out': ['x@amp.bf16']},
                      attrs={'out_dtype': 'bfloat16'})


def _mut_corrupt_sharding_axis(program):
    # rewrite one stamped annotation to name an axis the mesh lacks —
    # the sharding-consistency check must catch and attribute it
    for op in program.global_block().ops:
        if op.attrs.get('sharding_out') is not None:
            op.attrs['sharding_out'] = (('__ghost__', ('bogus',)),)
            return


def _mut_stamp_overlap_on_non_autodiff(program):
    # stamp a bucket grouping on an op that is not an autodiff — the
    # barrier lowering only exists inside the autodiff closure, so the
    # overlap-consistency check must catch and attribute it
    op = program.global_block().ops[0]
    op.attrs['overlap_buckets'] = (('__ghost__@GRAD',),)


def _mut_stamp_embed_on_non_rowwise(program):
    # stamp embed routing attrs on an op that is neither a lookup nor
    # a row-wise sparse apply — such a consumer would scan the whole
    # table, so the embed-consistency check must catch and attribute it
    op = program.global_block().ops[0]
    op.attrs['embed_ways'] = 2
    op.attrs['embed_height'] = 7
    op.attrs['embed_padded'] = 8
    op.attrs['embed_tile'] = 8


# The verifier mutation-test matrix: every REWRITE pass registered in
# pass_manager.PASSES must appear here (enforced statically by
# tools/check_pass_registry.py) with a corruption the verifier catches.
PASS_MUTATIONS = {
    'dce': _mut_drop_persistable_writer,
    'constant_fold': _mut_read_ghost,
    'cse': _mut_duplicate_op_seq,
    'dce_sweep': _mut_drop_fetch_producer,
    'amp': _mut_duplicate_weaver_cast,
    'sharding': _mut_corrupt_sharding_axis,
    'embed_shard': _mut_stamp_embed_on_non_rowwise,
    'overlap_collectives': _mut_stamp_overlap_on_non_autodiff,
}


@pytest.mark.parametrize('pass_name', sorted(PASS_MUTATIONS))
def test_mutation_is_caught_and_attributed(pass_name, monkeypatch):
    main, fetch = _data_program()
    amp = 'bf16' if pass_name == 'amp' else '0'
    # the sharding + embed + overlap passes only join under a mesh
    mesh = 'dp=2' if pass_name in ('sharding', 'embed_shard',
                                   'overlap_collectives') else ''
    # control: the uncorrupted pipeline verifies clean at every_pass
    pm.run_pipeline(main, fetch_names=(fetch,), feed_names=('x',),
                    level=2, amp_mode=amp, mesh=mesh,
                    verify='every_pass')
    monkeypatch.setitem(pm._TEST_CORRUPTORS, pass_name,
                        PASS_MUTATIONS[pass_name])
    with pytest.raises(IRVerificationError) as ei:
        pm.run_pipeline(main, fetch_names=(fetch,), feed_names=('x',),
                        level=2, amp_mode=amp, mesh=mesh,
                        verify='every_pass')
    assert ei.value.pass_name == pass_name
    assert ei.value.errors


def test_mutation_boundary_mode_catches_without_attribution(monkeypatch):
    main, fetch = _data_program()
    monkeypatch.setitem(pm._TEST_CORRUPTORS, 'dce',
                        PASS_MUTATIONS['dce'])
    with pytest.raises(IRVerificationError) as ei:
        pm.run_pipeline(main, fetch_names=(fetch,), feed_names=('x',),
                        level=2, amp_mode='0', verify='boundary')
    assert ei.value.pass_name is None  # boundary can't attribute


def test_crashing_pass_is_skipped_and_reported(monkeypatch):
    """A pass that RAISES (vs. producing a bad program) is skipped with
    a per-pass failure entry — the fall-back-don't-die contract."""
    def boom(program, ctx):
        raise RuntimeError("pass exploded")
    broken = pm.PASSES['cse']._replace(fn=boom)
    monkeypatch.setitem(pm.PASSES, 'cse', broken)
    main, fetch = _data_program()
    out, rep = pm.run_pipeline(main, fetch_names=(fetch,),
                               feed_names=('x',), level=2,
                               amp_mode='0', verify='boundary')
    entry = {e['name']: e for e in rep['passes']}['cse']
    assert entry['status'].startswith('failed:')
    assert 'cse' not in rep['eliminated']
    # the rest of the pipeline still ran and verified
    assert rep['verify']['checks'] == 1
    assert rep['eliminated']['dce'] >= 0


def test_crashing_flag_requested_rewrite_reraises(monkeypatch):
    """AMP (like sharding, embed_shard, overlap_collectives) is in the
    plan only because a flag asked for it: a crash there must not
    degrade to an f32 / replicated run under exit 0."""
    def boom(program, ctx):
        raise RuntimeError("amp exploded")
    monkeypatch.setitem(pm.PASSES, 'amp',
                        pm.PASSES['amp']._replace(fn=boom))
    main, fetch = _data_program()
    with pytest.raises(RuntimeError, match='amp exploded'):
        pm.run_pipeline(main, fetch_names=(fetch,), feed_names=('x',),
                        level=2, amp_mode='bf16', verify='boundary')
    # ... and the executor no longer swallows it into an unrewritten run
    monkeypatch.setenv('PADDLE_TPU_AMP', 'bf16')
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(RuntimeError, match='amp exploded'):
        exe.run(main, feed={'x': np.ones((2, 4), np.float32)},
                fetch_list=[fetch])


# ---------------------------------------------------------------------------
# executor integration: composite plan key + reports + metrics
# ---------------------------------------------------------------------------

def _fresh_exe_run(exe, main, fetch, feed):
    return exe.run(main, feed=feed, fetch_list=[fetch])


def test_plan_cache_invalidation_on_config_flips(monkeypatch):
    """Acceptance: flipping graph-opt level, AMP mode, or verify mode
    each re-keys the run plan AND the run_steps plan through the ONE
    composite pass-configuration key."""
    main, fetch = _data_program()
    feed = {'x': np.ones((2, 4), np.float32)}
    scope = fluid.core.scope.Scope()
    monkeypatch.setenv('PADDLE_TPU_GRAPH_OPT_LEVEL', '2')
    monkeypatch.setenv('PADDLE_TPU_AMP', '0')
    monkeypatch.setenv('PADDLE_TPU_VERIFY_IR', 'boundary')
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(main, feed=feed, fetch_list=[fetch])
        exe.run_steps(main, feed=[feed, feed], fetch_list=[fetch])
        n0 = len(exe._cache)
        for var, val in (('PADDLE_TPU_GRAPH_OPT_LEVEL', '1'),
                         ('PADDLE_TPU_AMP', 'bf16'),
                         ('PADDLE_TPU_VERIFY_IR', 'every_pass')):
            monkeypatch.setenv(var, val)
            exe.run(main, feed=feed, fetch_list=[fetch])
            exe.run_steps(main, feed=[feed, feed], fetch_list=[fetch])
            n1 = len(exe._cache)
            assert n1 >= n0 + 2, (
                "flipping %s did not re-key both run and run_steps "
                "plans (%d -> %d)" % (var, n0, n1))
            n0 = n1


def test_executor_propagates_verifier_rejection(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_VERIFY_IR', 'boundary')
    main = Program()
    main.global_block().append_op(
        type='scale', inputs={'X': ['never_defined']},
        outputs={'Out': ['y']}, attrs={'scale': 1.0})
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(IRVerificationError) as ei:
        exe.run(main, feed={}, fetch_list=['y'])
    assert "reads 'never_defined' before any definition" in str(ei.value)


def test_verify_off_restores_unverified_path(monkeypatch):
    """verify=off: the same broken program sails past the (absent)
    verifier and dies at trace time with the legacy KeyError instead."""
    monkeypatch.setenv('PADDLE_TPU_VERIFY_IR', 'off')
    main = Program()
    main.global_block().append_op(
        type='scale', inputs={'X': ['never_defined']},
        outputs={'Out': ['y']}, attrs={'scale': 1.0})
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(KeyError):
        exe.run(main, feed={}, fetch_list=['y'])


def test_per_pass_report_structure(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_GRAPH_OPT_LEVEL', '2')
    monkeypatch.setenv('PADDLE_TPU_VERIFY_IR', 'every_pass')
    main, fetch = _data_program()
    feed = {'x': np.ones((2, 4), np.float32)}
    scope = fluid.core.scope.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(main, feed=feed, fetch_list=[fetch])
    rep = exe.last_graph_opt_report
    names = [e['name'] for e in rep['passes']]
    assert names == ['dce', 'constant_fold', 'cse', 'dce_sweep',
                     'donation', 'cost_model', 'memory_model']
    for e in rep['passes']:
        assert e['status'] == 'ok'
        assert e['ops_after'] <= e['ops_before']
        assert e['wall_s'] >= 0.0
        assert e['verify'] == (
            'ok' if e['name'] not in
            ('donation', 'cost_model', 'memory_model') else 'skipped')
    assert rep['verify']['mode'] == 'every_pass'
    assert rep['verify']['checks'] == 4  # one per rewrite pass


def test_verifier_failure_metric(monkeypatch):
    from paddle_tpu import observability as obs
    monkeypatch.setenv('PADDLE_TPU_VERIFY_IR', 'boundary')
    main = Program()
    main.global_block().append_op(
        type='scale', inputs={'X': ['never_defined']},
        outputs={'Out': ['y']}, attrs={'scale': 1.0})
    exe = fluid.Executor(fluid.CPUPlace())
    def current():
        m = obs.registry().snapshot().get(
            'paddle_tpu_ir_verify_failures_total')
        return sum(s['value'] for s in m['samples']) if m else 0.0
    before = current()
    with pytest.raises(IRVerificationError):
        exe.run(main, feed={}, fetch_list=['y'])
    assert current() == before + 1


def test_rng_streams_survive_managed_pipeline(monkeypatch):
    """Dropout masks are bitwise-identical across verify modes and with
    the pipeline off — op_seq stamping under the manager keeps the
    PR-3 RNG-exactness contract."""
    def run(mode, level):
        monkeypatch.setenv('PADDLE_TPU_VERIFY_IR', mode)
        monkeypatch.setenv('PADDLE_TPU_GRAPH_OPT_LEVEL', level)
        main = fluid.Program()
        main.random_seed = 1234
        with fluid.program_guard(main):
            x = fluid.layers.data(name='x', shape=[8], dtype='float32')
            fluid.layers.scale(x, scale=9.0)  # dead
            d = fluid.layers.dropout(x, dropout_prob=0.5)
            y = fluid.layers.scale(d, scale=1.0)
        scope = fluid.core.scope.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            out, = exe.run(
                main, feed={'x': np.ones((4, 8), np.float32)},
                fetch_list=[y.name])
        return np.asarray(out)
    ref = run('off', '0')
    for mode, level in (('boundary', '2'), ('every_pass', '2'),
                        ('boundary', '1')):
        np.testing.assert_array_equal(ref, run(mode, level))

"""Fused dense optimizer-apply kernels (ops/pallas/dense_update.py).

Exact-parity contract, mirroring tests/test_pallas_table_update.py for
the dense half: the Pallas flat-walk apply is BITWISE identical to the
jnp expression chains in ops/optim_ops.py for SGD (plain and fused
weight decay), momentum (plain and Nesterov), and Adam — across
tile-unaligned and multi-rank parameter shapes — on CPU interpret mode,
jitted on both sides (the executor always runs the step jitted, and
comparing an eager oracle against the traced kernel would measure
XLA:CPU's fma contraction instead of the kernel).

End-to-end: the full executor path under PADDLE_TPU_DENSE_APPLY=pallas
vs =xla trains to bitwise-identical persistable state — with AMP bf16
(f32 master weights) included, since the AMP grads are exactly what the
dense apply consumes on the mixed-precision path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.ops.pallas.dense_update import (dense_apply_adam,
                                                dense_apply_mode,
                                                dense_apply_momentum,
                                                dense_apply_sgd,
                                                pick_flat_tile)

rng = np.random.RandomState(11)

B1, B2, EPS = 0.9, 0.999, 1e-8

# tile-unaligned on purpose: odd flats, a sub-lane param, multi-rank
# shapes whose flattened size is not a multiple of 128, and one exact
# tile — Pallas masks the ragged last block, and parity must hold on
# every one
SHAPES = [(5,), (127,), (128,), (7, 5), (3, 4, 5), (1, 1), (385,),
          (2, 130)]


def _arrs(shape, signed=True):
    a = rng.randn(*shape).astype(np.float32)
    return jnp.asarray(a if signed else np.abs(a))


def _assert_bitwise(got, want, msg):
    got, want = np.asarray(got), np.asarray(want)
    eq = got == want
    assert eq.all(), '%s: %d/%d elements differ (max %g)' % (
        msg, (~eq).sum(), eq.size, np.abs(got - want).max())


@pytest.mark.parametrize('shape', SHAPES)
def test_sgd_bitwise(shape):
    lr = jnp.float32(0.13)

    @jax.jit
    def oracle(p, g):
        return p - lr * g  # ops/optim_ops.py _sgd dense branch

    @jax.jit
    def pallas(p, g):
        return dense_apply_sgd(p, g, lr)

    p, g = _arrs(shape), _arrs(shape)
    _assert_bitwise(pallas(p, g), oracle(p, g), 'sgd %r' % (shape,))


@pytest.mark.parametrize('shape', [(127,), (7, 5)])
def test_sgd_weight_decay_bitwise(shape):
    lr, wd = jnp.float32(0.05), jnp.float32(0.01)

    @jax.jit
    def oracle(p, g):
        return p - lr * (g + wd * p)

    @jax.jit
    def pallas(p, g):
        return dense_apply_sgd(p, g, lr, weight_decay=wd)

    p, g = _arrs(shape), _arrs(shape)
    _assert_bitwise(pallas(p, g), oracle(p, g), 'sgd+wd %r' % (shape,))


@pytest.mark.parametrize('nesterov', [False, True])
def test_momentum_bitwise(nesterov):
    lr, mu = jnp.float32(0.1), 0.9

    @jax.jit
    def oracle(p, v, g):
        # ops/optim_ops.py _momentum, verbatim
        v_new = mu * v + g
        if nesterov:
            p_new = p - (g + mu * v_new) * lr
        else:
            p_new = p - lr * v_new
        return p_new, v_new

    @jax.jit
    def pallas(p, v, g):
        return dense_apply_momentum(p, v, g, lr, mu,
                                    use_nesterov=nesterov)

    for shape in SHAPES:
        p, v, g = _arrs(shape), _arrs(shape), _arrs(shape)
        got, want = pallas(p, v, g), oracle(p, v, g)
        for name, a, b in zip(('param', 'velocity'), got, want):
            _assert_bitwise(a, b, 'momentum(n=%s) %s %r'
                            % (nesterov, name, shape))


@pytest.mark.parametrize('shape', SHAPES)
def test_adam_bitwise(shape):
    lr_t = jnp.float32(0.05)

    @jax.jit
    def oracle(p, m, v, g):
        # ops/optim_ops.py _adam dense tail, verbatim — the fma-
        # contraction duplicate of the PR-4 subtlety: the kernel must
        # restate these expressions exactly or XLA rounds differently
        m_new = B1 * m + (1 - B1) * g
        v_new = B2 * v + (1 - B2) * jnp.square(g)
        p_new = p - lr_t * m_new / (jnp.sqrt(v_new) + EPS)
        return p_new, m_new, v_new

    @jax.jit
    def pallas(p, m, v, g):
        return dense_apply_adam(p, m, v, g, lr_t, B1, B2, EPS)

    p, m, g = _arrs(shape), _arrs(shape), _arrs(shape)
    v = _arrs(shape, signed=False)
    got, want = pallas(p, m, v, g), oracle(p, m, v, g)
    for name, a, b in zip(('param', 'moment1', 'moment2'), got, want):
        _assert_bitwise(a, b, 'adam %s %r' % (name, shape))


def test_adam_amp_master_grads_bitwise():
    """The AMP f32-master path: grads accumulated from bf16 compute
    (cast round trip) are still f32 when they reach the apply — parity
    must hold on those exact bit patterns too."""
    lr_t = jnp.float32(0.01)
    shape = (129,)
    p, m = _arrs(shape), _arrs(shape)
    v = _arrs(shape, signed=False)
    # a grad that went through the bf16 compute round trip
    g = _arrs(shape).astype(jnp.bfloat16).astype(jnp.float32)

    @jax.jit
    def oracle(p, m, v, g):
        m_new = B1 * m + (1 - B1) * g
        v_new = B2 * v + (1 - B2) * jnp.square(g)
        p_new = p - lr_t * m_new / (jnp.sqrt(v_new) + EPS)
        return p_new, m_new, v_new

    @jax.jit
    def pallas(p, m, v, g):
        return dense_apply_adam(p, m, v, g, lr_t, B1, B2, EPS)

    for name, a, b in zip(('param', 'moment1', 'moment2'),
                          pallas(p, m, v, g), oracle(p, m, v, g)):
        _assert_bitwise(a, b, 'amp-grad adam %s' % name)


def test_pick_flat_tile():
    # the budget caps the tile; the floor is one lane tile
    assert pick_flat_tile(10 ** 8, 3, 1) * (2 * 3 + 1) * 4 <= \
        4 * 1024 * 1024
    assert pick_flat_tile(5, 1, 1) == 128  # never wider than the pad
    assert pick_flat_tile(300, 1, 1) == 256
    assert pick_flat_tile(10 ** 8, 3, 1, budget=1) == 128  # floor


def test_mode_flag(monkeypatch):
    monkeypatch.delenv('PADDLE_TPU_DENSE_APPLY', raising=False)
    # no platform selects the kernels on its own: they lost to the XLA
    # expressions on the v5e (PERF.md, chip bring-up)
    assert dense_apply_mode() == 'xla'
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert dense_apply_mode() == 'xla'
    monkeypatch.setenv('PADDLE_TPU_DENSE_APPLY', 'pallas')
    assert dense_apply_mode() == 'pallas'
    monkeypatch.setenv('PADDLE_TPU_DENSE_APPLY', 'xla')
    assert dense_apply_mode() == 'xla'


def _train_dense(optimizer, steps=3, amp=None):
    """Dense MLP training loop; returns the final persistable state.
    Built under a fresh unique-name scope so the pallas and xla runs
    generate identical auto names (comparable state dicts)."""
    from paddle_tpu.core.program import reset_unique_name_guard
    with reset_unique_name_guard():
        scope = fluid.core.scope.Scope()
        with fluid.scope_guard(scope):
            return _train_dense_inner(optimizer, steps, scope)


def _train_dense_inner(optimizer, steps, scope):
    main = fluid.Program()
    startup = fluid.Program()
    main.random_seed = 42
    startup.random_seed = 42
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[9], dtype='float32')
        label = fluid.layers.data(name='label', shape=[1],
                                  dtype='float32')
        h = fluid.layers.fc(
            input=x, size=7, act='tanh',
            param_attr=fluid.ParamAttr(
                name='w1',
                initializer=fluid.initializer.NormalInitializer(seed=3)))
        pred = fluid.layers.fc(
            input=h, size=1,
            param_attr=fluid.ParamAttr(
                name='w2',
                initializer=fluid.initializer.NormalInitializer(seed=9)))
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=pred, label=label))
        optimizer().minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    r = np.random.RandomState(5)
    for _ in range(steps):
        exe.run(main, feed={'x': r.randn(6, 9).astype('float32'),
                            'label': r.randn(6, 1).astype('float32')},
                fetch_list=[loss])
    return {v.name: np.asarray(scope.find_var(v.name)).copy()
            for v in main.list_vars()
            if v.persistable and scope.find_var(v.name) is not None}


@pytest.mark.parametrize('opt', ['sgd', 'momentum', 'adam'])
def test_executor_end_to_end_parity(opt, monkeypatch):
    """The full executor path — autodiff -> dense optimizer op —
    produces bitwise-identical training state under
    PADDLE_TPU_DENSE_APPLY=pallas and =xla (the escape hatch restores
    today's jnp chains verbatim; the kernel must match them exactly)."""
    mk = {'sgd': lambda: fluid.optimizer.SGDOptimizer(0.1),
          'momentum': lambda: fluid.optimizer.MomentumOptimizer(
              0.1, 0.9, use_nesterov=True),
          'adam': lambda: fluid.optimizer.AdamOptimizer(0.05)}[opt]
    monkeypatch.setenv('PADDLE_TPU_DENSE_APPLY', 'xla')
    want = _train_dense(mk)
    monkeypatch.setenv('PADDLE_TPU_DENSE_APPLY', 'pallas')
    got = _train_dense(mk)
    assert set(got) == set(want)
    for name in sorted(want):
        _assert_bitwise(got[name], want[name], '%s %s' % (opt, name))


def test_executor_parity_under_amp_bf16(monkeypatch):
    """AMP bf16 (f32 masters + cast-VJP-accumulated f32 grads) feeds
    the dense apply on the mixed-precision path; pallas and xla must
    still agree bitwise on every persistable."""
    monkeypatch.setenv('PADDLE_TPU_AMP', 'bf16')
    mk = lambda: fluid.optimizer.AdamOptimizer(0.05)
    monkeypatch.setenv('PADDLE_TPU_DENSE_APPLY', 'xla')
    want = _train_dense(mk)
    monkeypatch.setenv('PADDLE_TPU_DENSE_APPLY', 'pallas')
    got = _train_dense(mk)
    assert set(got) == set(want)
    for name in sorted(want):
        _assert_bitwise(got[name], want[name], 'amp %s' % name)
        # master weights stayed f32 under both lowerings
        assert got[name].dtype == np.float32


def test_mode_flip_retraces_same_executor(monkeypatch):
    """PADDLE_TPU_DENSE_APPLY is part of the plan cache key: flipping
    it between calls on ONE executor builds a second plan instead of
    serving the stale lowering."""
    from paddle_tpu.core.program import reset_unique_name_guard
    monkeypatch.setenv('PADDLE_TPU_DENSE_APPLY', 'xla')
    with reset_unique_name_guard():
        scope = fluid.core.scope.Scope()
        with fluid.scope_guard(scope):
            main = fluid.Program()
            startup = fluid.Program()
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name='x', shape=[4],
                                      dtype='float32')
                y = fluid.layers.fc(input=x, size=2)
                loss = fluid.layers.mean(x=y)
                fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            feed = {'x': np.ones((3, 4), np.float32)}
            exe.run(main, feed=feed, fetch_list=[loss])
            n_plans = len(exe._cache)
            exe.run(main, feed=feed, fetch_list=[loss])
            assert len(exe._cache) == n_plans  # cache hit
            monkeypatch.setenv('PADDLE_TPU_DENSE_APPLY', 'pallas')
            exe.run(main, feed=feed, fetch_list=[loss])
            assert len(exe._cache) == n_plans + 1  # retraced


def test_sgd_l2_decay_folds_into_op(monkeypatch):
    """SGD + L2Decay folds the coefficient into the sgd op's
    `weight_decay` attr (one fused apply pass) instead of weaving
    scale+sum ops; L1 and sparse-grad params keep the weave.  The
    fused update is bitwise-identical across both lowerings."""
    from paddle_tpu.core.program import reset_unique_name_guard

    def build_and_train(env_mode):
        monkeypatch.setenv('PADDLE_TPU_DENSE_APPLY', env_mode)
        with reset_unique_name_guard():
            scope = fluid.core.scope.Scope()
            with fluid.scope_guard(scope):
                main = fluid.Program()
                startup = fluid.Program()
                main.random_seed = 42
                startup.random_seed = 42
                with fluid.program_guard(main, startup):
                    x = fluid.layers.data(name='x', shape=[5],
                                          dtype='float32')
                    y = fluid.layers.data(name='y', shape=[1],
                                          dtype='float32')
                    p = fluid.layers.fc(
                        input=x, size=1, bias_attr=False,
                        param_attr=fluid.ParamAttr(
                            name='w_fold',
                            regularizer=fluid.regularizer.L2Decay(0.1),
                            initializer=fluid.initializer
                            .NormalInitializer(seed=3)))
                    loss = fluid.layers.mean(
                        x=fluid.layers.square_error_cost(input=p,
                                                         label=y))
                    fluid.optimizer.SGDOptimizer(0.5).minimize(loss)
                ops = main.global_block().ops
                sgd_ops = [op for op in ops if op.type == 'sgd' and
                           'w_fold' in op.input_arg_names]
                assert len(sgd_ops) == 1
                assert abs(sgd_ops[0].attrs['weight_decay'] - 0.1) < 1e-9
                # no scale+sum weave for the folded param
                assert not any(op.type == 'sum' and
                               any(n.endswith('_reg')
                                   for n in op.output_arg_names)
                               for op in ops)
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                r = np.random.RandomState(2)
                for _ in range(3):
                    exe.run(main,
                            feed={'x': r.randn(4, 5).astype('float32'),
                                  'y': r.randn(4, 1).astype('float32')},
                            fetch_list=[loss])
                return np.asarray(scope.find_var('w_fold')).copy()

    w_xla = build_and_train('xla')
    w_pal = build_and_train('pallas')
    _assert_bitwise(w_pal, w_xla, 'fused-wd sgd param')


def test_sgd_l2_decay_low_precision_param_keeps_weave():
    """A bf16 param with L2Decay must NOT fold: the weave's scale+sum
    intermediates round in param dtype, so folding into the f32 sgd
    expression would silently change the update numerics.  The fold is
    an optimization for f32-or-wider params only."""
    from paddle_tpu.core.program import reset_unique_name_guard
    with reset_unique_name_guard():
        main = fluid.Program()
        startup = fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name='x', shape=[5],
                                  dtype='float32')
            xb = fluid.layers.cast(x=x, dtype='bfloat16')
            w = fluid.layers.create_parameter(
                shape=[5, 1], dtype='bfloat16',
                attr=fluid.ParamAttr(
                    name='w_bf16',
                    regularizer=fluid.regularizer.L2Decay(0.1)))
            pred = fluid.layers.cast(
                x=fluid.layers.matmul(x=xb, y=w), dtype='float32')
            loss = fluid.layers.mean(x=fluid.layers.square(x=pred))
            fluid.optimizer.SGDOptimizer(0.5).minimize(loss)
        ops = main.global_block().ops
        sgd_ops = [op for op in ops if op.type == 'sgd' and
                   'w_bf16' in op.input_arg_names]
        assert len(sgd_ops) == 1
        assert not sgd_ops[0].attrs.get('weight_decay')
        # the scale+sum weave is still there for the bf16 param
        assert any(op.type == 'sum' and
                   any(n.endswith('_reg') for n in op.output_arg_names)
                   for op in ops)


def test_sgd_l2_decay_on_regularized_embedding_is_dense_and_folds():
    """A regularized `is_sparse` embedding never produces a
    SelectedRows grad in the first place — core/backward.py forces the
    dense path because decay must shrink the WHOLE table, not just the
    touched rows — so the fold applies cleanly there too (the
    optimizer's sparse_grad_assemble guard is a defensive invariant
    for the day that forcing changes, not a reachable branch today)."""
    from paddle_tpu.core.program import reset_unique_name_guard
    with reset_unique_name_guard():
        main = fluid.Program()
        startup = fluid.Program()
        with fluid.program_guard(main, startup):
            words = fluid.layers.data(name='words', shape=[4],
                                      dtype='int64')
            label = fluid.layers.data(name='label', shape=[1],
                                      dtype='float32')
            emb = fluid.layers.embedding(
                input=words, size=[30, 6], is_sparse=True,
                param_attr=fluid.ParamAttr(
                    name='emb_sp',
                    regularizer=fluid.regularizer.L2Decay(0.05)))
            pooled = fluid.layers.sequence_pool(input=emb,
                                                pool_type='sum')
            pred = fluid.layers.fc(input=pooled, size=1)
            loss = fluid.layers.mean(
                x=fluid.layers.square_error_cost(input=pred,
                                                 label=label))
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
        ops = main.global_block().ops
        # regularizer forced the dense grad: no assemble op exists
        assert not any(op.type == 'sparse_grad_assemble' for op in ops)
        emb_sgd = [op for op in ops if op.type == 'sgd' and
                   'emb_sp' in op.input_arg_names]
        assert len(emb_sgd) == 1
        assert abs(emb_sgd[0].attrs['weight_decay'] - 0.05) < 1e-9
        # and no scale+sum weave remains for it
        assert not any(op.type == 'sum' and
                       any(n.endswith('_reg')
                           for n in op.output_arg_names)
                       for op in ops)

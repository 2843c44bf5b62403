"""Optimizer update-op tests vs numpy update rules.

Reference parity: python/paddle/v2/fluid/tests/test_{sgd,momentum,adam,
adamax,adagrad,decayed_adagrad,adadelta,rmsprop,ftrl,proximal_gd,
proximal_adagrad}_op.py.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.program import reset_unique_name_guard

from op_test import run_op

rng = np.random.RandomState(11)
P = rng.randn(4, 3).astype('float32')
G = rng.randn(4, 3).astype('float32')
LR = np.array([0.1], dtype='float32')

# the dense rules of sgd / momentum / adam over the kinds of parameter a
# model holds: the file's own small matrix, a vector of odd length, a
# rank-4 filter, a matrix whose size is no multiple of 128
SHAPES = [(4, 3), (127,), (3, 3, 3, 8), (2, 130)]

# The ops are f32 expression chains and numpy restates them in f32: the
# two differ only in where a product and a sum round together (XLA may
# contract them into an fma), a few ulp of values of order 1.
TOL = dict(rtol=1e-5, atol=1e-6)
# adam divides by sqrt(v) + eps, which carries those ulp into a quotient
TOL_ADAM = dict(rtol=1e-4, atol=1e-5)


def _get(outs, slot):
    return np.asarray(outs[slot][0])


def _pg(shape):
    return (rng.randn(*shape).astype('float32'),
            rng.randn(*shape).astype('float32'))


@pytest.mark.parametrize('weight_decay', [0.0, 0.01])
@pytest.mark.parametrize('shape', SHAPES)
def test_sgd(shape, weight_decay):
    p, g = _pg(shape)
    outs = run_op('sgd', {'Param': p, 'Grad': g, 'LearningRate': LR},
                  {'weight_decay': weight_decay} if weight_decay else None)
    want = p - np.float32(0.1) * (g + np.float32(weight_decay) * p)
    assert _get(outs, 'ParamOut').shape == shape
    np.testing.assert_allclose(_get(outs, 'ParamOut'), want, **TOL)


@pytest.mark.parametrize('use_nesterov', [False, True])
@pytest.mark.parametrize('shape', SHAPES)
def test_momentum(shape, use_nesterov):
    p, g = _pg(shape)
    v = rng.randn(*shape).astype('float32')
    outs = run_op('momentum', {'Param': p, 'Grad': g, 'Velocity': v,
                               'LearningRate': LR},
                  {'mu': 0.9, 'use_nesterov': use_nesterov})
    v_new = np.float32(0.9) * v + g
    if use_nesterov:
        want = p - (g + np.float32(0.9) * v_new) * np.float32(0.1)
    else:
        want = p - np.float32(0.1) * v_new
    np.testing.assert_allclose(_get(outs, 'VelocityOut'), v_new, **TOL)
    np.testing.assert_allclose(_get(outs, 'ParamOut'), want, **TOL)


def _adam_np(p, g, m, v, lr, b1p, b2p, b1=0.9, b2=0.999, eps=1e-8):
    f = np.float32
    m_new = f(b1) * m + f(1 - b1) * g
    v_new = f(b2) * v + f(1 - b2) * g * g
    lr_t = f(lr) * np.sqrt(f(1) - f(b2p)) / (f(1) - f(b1p))
    return p - lr_t * m_new / (np.sqrt(v_new) + f(eps)), m_new, v_new


@pytest.mark.parametrize('shape', SHAPES)
def test_adam(shape):
    p, g = _pg(shape)
    m = rng.randn(*shape).astype('float32')
    v = np.abs(rng.randn(*shape)).astype('float32')
    outs = run_op('adam', {'Param': p, 'Grad': g, 'Moment1': m, 'Moment2': v,
                           'LearningRate': LR,
                           'Beta1Pow': np.array([0.9], 'float32'),
                           'Beta2Pow': np.array([0.999], 'float32')},
                  {'beta1': 0.9, 'beta2': 0.999, 'epsilon': 1e-8})
    want, m_new, v_new = _adam_np(p, g, m, v, 0.1, 0.9, 0.999)
    np.testing.assert_allclose(_get(outs, 'Moment1Out'), m_new, **TOL)
    np.testing.assert_allclose(_get(outs, 'Moment2Out'), v_new, **TOL)
    np.testing.assert_allclose(_get(outs, 'ParamOut'), want, **TOL_ADAM)


def test_adamax():
    m = rng.randn(4, 3).astype('float32')
    u = np.abs(rng.randn(4, 3)).astype('float32')
    outs = run_op('adamax', {'Param': P, 'Grad': G, 'Moment': m,
                             'InfNorm': u, 'LearningRate': LR,
                             'Beta1Pow': np.array([0.9], 'float32')},
                  {'beta1': 0.9, 'beta2': 0.999, 'epsilon': 1e-8})
    m_new = 0.9 * m + 0.1 * G
    u_new = np.maximum(0.999 * u, np.abs(G))
    want = P - (0.1 / (1 - 0.9)) * m_new / (u_new + 1e-8)
    np.testing.assert_allclose(_get(outs, 'ParamOut'), want,
                               rtol=1e-4, atol=1e-5)


def test_adagrad():
    mom = np.abs(rng.randn(4, 3)).astype('float32')
    outs = run_op('adagrad', {'Param': P, 'Grad': G, 'Moment': mom,
                              'LearningRate': LR}, {'epsilon': 1e-6})
    mom_new = mom + G * G
    want = P - 0.1 * G / (np.sqrt(mom_new) + 1e-6)
    np.testing.assert_allclose(_get(outs, 'ParamOut'), want,
                               rtol=1e-4, atol=1e-5)


def test_decayed_adagrad():
    mom = np.abs(rng.randn(4, 3)).astype('float32')
    outs = run_op('decayed_adagrad',
                  {'Param': P, 'Grad': G, 'Moment': mom,
                   'LearningRate': LR}, {'decay': 0.95, 'epsilon': 1e-6})
    mom_new = 0.95 * mom + 0.05 * G * G
    want = P - 0.1 * G / (np.sqrt(mom_new) + 1e-6)
    np.testing.assert_allclose(_get(outs, 'ParamOut'), want,
                               rtol=1e-4, atol=1e-5)


def test_adadelta():
    asg = np.abs(rng.randn(4, 3)).astype('float32')
    asu = np.abs(rng.randn(4, 3)).astype('float32')
    outs = run_op('adadelta',
                  {'Param': P, 'Grad': G, 'AvgSquaredGrad': asg,
                   'AvgSquaredUpdate': asu}, {'rho': 0.95, 'epsilon': 1e-6})
    asg_new = 0.95 * asg + 0.05 * G * G
    update = -np.sqrt((asu + 1e-6) / (asg_new + 1e-6)) * G
    asu_new = 0.95 * asu + 0.05 * update * update
    np.testing.assert_allclose(_get(outs, 'ParamOut'), P + update,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(_get(outs, 'AvgSquaredUpdateOut'), asu_new,
                               rtol=1e-4, atol=1e-5)


def test_rmsprop():
    ms = np.abs(rng.randn(4, 3)).astype('float32')
    mom = rng.randn(4, 3).astype('float32')
    outs = run_op('rmsprop', {'Param': P, 'Grad': G, 'MeanSquare': ms,
                              'Moment': mom, 'LearningRate': LR},
                  {'decay': 0.9, 'momentum': 0.5, 'epsilon': 1e-10})
    ms_new = 0.9 * ms + 0.1 * G * G
    mom_new = 0.5 * mom + 0.1 * G / np.sqrt(ms_new + 1e-10)
    np.testing.assert_allclose(_get(outs, 'ParamOut'), P - mom_new,
                               rtol=1e-4, atol=1e-5)


def test_ftrl():
    sq = np.abs(rng.randn(4, 3)).astype('float32')
    lin = rng.randn(4, 3).astype('float32')
    outs = run_op('ftrl', {'Param': P, 'Grad': G, 'SquaredAccumulator': sq,
                           'LinearAccumulator': lin, 'LearningRate': LR},
                  {'l1': 0.1, 'l2': 0.2, 'lr_power': -0.5})
    new_sq = sq + G * G
    sigma = (new_sq ** 0.5 - sq ** 0.5) / 0.1
    new_lin = lin + G - sigma * P
    x = np.clip(new_lin, -0.1, 0.1) - new_lin
    y = new_sq ** 0.5 / 0.1 + 2 * 0.2
    np.testing.assert_allclose(_get(outs, 'ParamOut'), x / y,
                               rtol=1e-4, atol=1e-5)


def test_proximal_gd():
    outs = run_op('proximal_gd', {'Param': P, 'Grad': G,
                                  'LearningRate': LR},
                  {'l1': 0.05, 'l2': 0.1})
    prox = P - 0.1 * G
    want = np.sign(prox) * np.maximum(np.abs(prox) - 0.1 * 0.05, 0.0) / \
        (1.0 + 0.1 * 0.1)
    np.testing.assert_allclose(_get(outs, 'ParamOut'), want,
                               rtol=1e-4, atol=1e-5)


def test_proximal_adagrad():
    mom = np.abs(rng.randn(4, 3)).astype('float32')
    outs = run_op('proximal_adagrad',
                  {'Param': P, 'Grad': G, 'Moment': mom,
                   'LearningRate': LR}, {'l1': 0.05, 'l2': 0.1})
    mom_new = mom + G * G
    lr_t = 0.1 / np.sqrt(mom_new)
    prox = P - lr_t * G
    want = np.sign(prox) * np.maximum(np.abs(prox) - lr_t * 0.05, 0.0) / \
        (1.0 + lr_t * 0.1)
    np.testing.assert_allclose(_get(outs, 'ParamOut'), want,
                               rtol=1e-4, atol=1e-5)


def test_sgd_sparse_grad_tuple():
    """Sparse (rows, values) grads scatter-add into the dense update —
    parity with lookup_table_op.cc SelectedRows grads + sgd_op sparse
    branch."""
    param = rng.randn(10, 4).astype('float32')
    rows = np.array([2, 7, 2], dtype='int32')
    vals = rng.randn(3, 4).astype('float32')
    outs = run_op('sgd', {'Param': param,
                          'Grad': [(rows, vals)],
                          'LearningRate': LR})
    dense = np.zeros_like(param)
    np.add.at(dense, rows, vals)
    np.testing.assert_allclose(_get(outs, 'ParamOut'), param - 0.1 * dense,
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the dense rules through the Executor: autodiff -> optimizer op -> state
# ---------------------------------------------------------------------------

def _mlp(optimizer):
    """fc 9 -> 7 tanh -> 1 under a squared error, and its optimizer."""
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
                initializer=fluid.initializer.NormalInitializer(seed=3)),
            bias_attr=fluid.ParamAttr(name='b1'))
        pred = fluid.layers.fc(
            input=h, size=1,
            param_attr=fluid.ParamAttr(
                name='w2',
                initializer=fluid.initializer.NormalInitializer(seed=9)),
            bias_attr=fluid.ParamAttr(name='b2'))
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=pred, label=label))
        optimizer().minimize(loss)
    return main, startup, loss


_PARAMS = ('w1', 'b1', 'w2', 'b2')


def _mlp_grads(p, x, y):
    """The gradients of `_mlp`'s loss in numpy, f32 throughout."""
    h = np.tanh(x @ p['w1'] + p['b1'])
    d_pred = np.float32(2.0 / x.shape[0]) * (h @ p['w2'] + p['b2'] - y)
    d_z = (d_pred @ p['w2'].T) * (1 - h * h)
    return {'w1': x.T @ d_z, 'b1': d_z.sum(0),
            'w2': h.T @ d_pred, 'b2': d_pred.sum(0)}


def _batches(steps):
    r = np.random.RandomState(5)
    return [{'x': r.randn(6, 9).astype('float32'),
             'label': r.randn(6, 1).astype('float32')}
            for _ in range(steps)]


def _state(scope, names):
    return {n: np.asarray(scope.find_var(n)).copy() for n in names}


def _sgd_rule(p, g, slots, t):
    return p - np.float32(0.1) * g


def _nesterov_rule(p, g, slots, t):
    v = slots['v'] = np.float32(0.9) * slots.get('v', 0 * p) + g
    return p - (g + np.float32(0.9) * v) * np.float32(0.1)


def _adam_rule(p, g, slots, t):
    p_new, slots['m'], slots['v'] = _adam_np(
        p, g, slots.get('m', 0 * p), slots.get('v', 0 * p), 0.05,
        0.9 ** t, 0.999 ** t)
    return p_new


@pytest.mark.parametrize('make, rule, tol', [
    (lambda: fluid.optimizer.SGDOptimizer(0.1), _sgd_rule, TOL),
    (lambda: fluid.optimizer.MomentumOptimizer(0.1, 0.9,
                                               use_nesterov=True),
     _nesterov_rule, TOL),
    (lambda: fluid.optimizer.AdamOptimizer(0.05), _adam_rule, TOL_ADAM),
], ids=['sgd', 'momentum', 'adam'])
def test_executor_three_steps_match_the_numpy_loop(make, rule, tol):
    with reset_unique_name_guard():
        scope = fluid.core.scope.Scope()
        with fluid.scope_guard(scope):
            main, startup, loss = _mlp(make)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            want = _state(scope, _PARAMS)
            slots = {n: {} for n in _PARAMS}
            for t, feed in enumerate(_batches(3), 1):
                exe.run(main, feed=feed, fetch_list=[loss])
                grads = _mlp_grads(want, feed['x'], feed['label'])
                want = {n: rule(want[n], grads[n], slots[n], t)
                        for n in _PARAMS}
            got = _state(scope, _PARAMS)
    for n in _PARAMS:
        assert got[n].dtype == np.float32
        np.testing.assert_allclose(got[n], want[n], err_msg=n, **tol)


def test_executor_adam_step_under_amp_bf16_keeps_f32_masters(monkeypatch):
    """Under AMP bf16 the matmuls compute in bf16, and what reaches the
    apply is the f32 gradient of the f32 master weight: the step is the
    numpy adam of exactly that gradient, and weights and moments stay
    f32."""
    monkeypatch.setenv('PADDLE_TPU_AMP', 'bf16')
    with reset_unique_name_guard():
        scope = fluid.core.scope.Scope()
        with fluid.scope_guard(scope):
            main, startup, loss = _mlp(
                lambda: fluid.optimizer.AdamOptimizer(0.05))
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            before = _state(scope, _PARAMS)
            feed, = _batches(1)
            grads = exe.run(main, feed=feed,
                            fetch_list=[n + '@GRAD' for n in _PARAMS])
            persist = [v.name for v in main.list_vars()
                       if v.persistable and
                       scope.find_var(v.name) is not None]
            after = _state(scope, persist)
    # the matmuls did run in bf16 (8 bits of mantissa): w1's gradient is
    # near the f32 one and not it
    f32_w1 = _mlp_grads(before, feed['x'], feed['label'])['w1']
    np.testing.assert_allclose(grads[0], f32_w1, rtol=0.1, atol=0.02)
    assert np.abs(np.asarray(grads[0]) - f32_w1).max() > 1e-4
    for n, g in zip(_PARAMS, grads):
        g = np.asarray(g)
        assert g.dtype == np.float32
        want, m_new, v_new = _adam_np(before[n], g, 0 * g, 0 * g, 0.05,
                                      0.9, 0.999)
        np.testing.assert_allclose(after[n], want, err_msg=n, **TOL_ADAM)
        moments = sorted(k for k in after
                         if k.startswith(n + '_') and 'moment' in k)
        assert len(moments) == 2, sorted(after)
        np.testing.assert_allclose(after[moments[0]], m_new, **TOL)
        np.testing.assert_allclose(after[moments[1]], v_new, **TOL)
    for name, value in after.items():
        assert value.dtype == np.float32, name


# ---------------------------------------------------------------------------
# SGD folds an L2Decay regularizer into the sgd op's weight_decay
# ---------------------------------------------------------------------------

def test_sgd_l2_decay_folds_into_op():
    """SGD + L2Decay folds the coefficient into the sgd op's
    `weight_decay` attr (one fused apply pass) instead of weaving
    scale+sum ops; L1 and sparse-grad params keep the weave.  Three
    steps of the fused update are the numpy `p - lr * (g + wd * p)`."""
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
                    x=fluid.layers.square_error_cost(input=p, label=y))
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
            want = np.asarray(scope.find_var('w_fold')).copy()
            r = np.random.RandomState(2)
            for _ in range(3):
                xs = r.randn(4, 5).astype('float32')
                ys = r.randn(4, 1).astype('float32')
                exe.run(main, feed={'x': xs, 'y': ys}, fetch_list=[loss])
                g = xs.T @ (np.float32(2.0 / 4) * (xs @ want - ys))
                want = want - np.float32(0.5) * (g + np.float32(0.1) * want)
            got = np.asarray(scope.find_var('w_fold'))
    np.testing.assert_allclose(got, want, **TOL)


def test_sgd_l2_decay_low_precision_param_keeps_weave():
    """A bf16 param with L2Decay must NOT fold: the weave's scale+sum
    intermediates round in param dtype, so folding into the f32 sgd
    expression would silently change the update numerics.  The fold is
    an optimization for f32-or-wider params only."""
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

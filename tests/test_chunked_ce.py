"""fused_linear_softmax_ce: chunked vocab-head CE (ops/chunked_ce.py).

Reference parity: operators/softmax_with_cross_entropy_op.cc composed
with the vocab fc (mul_op) — numerics must match the dense composition
while never materializing the [N, V] logits.
"""
import numpy as np

import paddle_tpu as fluid


def _dense_ce(x, w, b, lab):
    logits = x @ w + b
    m = logits.max(-1, keepdims=True)
    lse = m[..., 0] + np.log(np.exp(logits - m).sum(-1))
    return lse - np.take_along_axis(logits, lab[..., None], -1)[..., 0]


def test_fused_linear_softmax_ce_matches_dense_composition():
    from paddle_tpu.ops.chunked_ce import _chunked_linear_ce
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    n, d, v = 48, 24, 700  # v deliberately not a multiple of chunk
    x = rng.randn(n, d).astype('float32')
    w = (rng.randn(d, v) * 0.05).astype('float32')
    b = (rng.randn(v) * 0.1).astype('float32')
    lab = rng.randint(0, v, (n,)).astype('int32')
    got = np.asarray(_chunked_linear_ce(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
        jnp.asarray(lab), 256))
    np.testing.assert_allclose(got, _dense_ce(x, w, b, lab),
                               rtol=1e-5, atol=1e-5)


def test_fused_layer_trains_like_dense_layer():
    """A 2-layer classifier trained through fused_linear_softmax_ce
    matches the fc + softmax_with_cross_entropy build step-for-step."""
    from paddle_tpu.core.program import reset_unique_name_guard
    from paddle_tpu.param_attr import ParamAttr

    def build(fused):
        with reset_unique_name_guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 11
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name='x', shape=[16],
                                      dtype='float32')
                y = fluid.layers.data(name='y', shape=[1], dtype='int64')
                h = fluid.layers.fc(input=x, size=32, act='tanh',
                                    param_attr=ParamAttr(name='h_w'),
                                    bias_attr=ParamAttr(name='h_b'))
                if fused:
                    cost = fluid.layers.fused_linear_softmax_ce(
                        input=h, label=y, size=50, chunk=16, mode=fused,
                        param_attr=ParamAttr(name='o_w'),
                        bias_attr=ParamAttr(name='o_b'))
                else:
                    logits = fluid.layers.fc(
                        input=h, size=50,
                        param_attr=ParamAttr(name='o_w'),
                        bias_attr=ParamAttr(name='o_b'))
                    cost = fluid.layers.softmax_with_cross_entropy(
                        logits=logits, label=y)
                loss = fluid.layers.mean(x=cost)
                fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(5)
    proj = rng.randn(16, 50).astype('float32')  # learnable labeling
    batches = []
    for _ in range(6):
        xb = rng.randn(32, 16).astype('float32')
        yb = (xb @ proj).argmax(1)[:, None].astype('int64')
        batches.append({'x': xb, 'y': yb})

    runs = {}
    for fused in (False, 'chunked', 'dense'):
        main, startup, loss = build(fused)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        runs[fused] = [float(np.ravel(exe.run(main, feed=f,
                                              fetch_list=[loss])[0])[0])
                       for f in batches]
    np.testing.assert_allclose(runs['chunked'], runs[False], rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(runs['dense'], runs[False], rtol=1e-4,
                               atol=1e-5)
    assert runs['chunked'][-1] < runs['chunked'][0]  # it actually learns


def test_fused_layer_rank3_num_flatten_dims():
    """Code-review r4: a rank-3 non-lod input with num_flatten_dims=1
    flattens trailing dims into the feature axis (fc parity) — W is
    [d1*d2, V] and the loss is [B, 1]."""
    from paddle_tpu.core.program import reset_unique_name_guard
    from paddle_tpu.param_attr import ParamAttr

    def build(fused):
        with reset_unique_name_guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 2
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name='x', shape=[3, 8],
                                      dtype='float32')
                y = fluid.layers.data(name='y', shape=[1], dtype='int64')
                if fused:
                    cost = fluid.layers.fused_linear_softmax_ce(
                        input=x, label=y, size=30, chunk=8,
                        mode='chunked', param_attr=ParamAttr(name='o_w'),
                        bias_attr=ParamAttr(name='o_b'))
                else:
                    logits = fluid.layers.fc(
                        input=x, size=30,
                        param_attr=ParamAttr(name='o_w'),
                        bias_attr=ParamAttr(name='o_b'))
                    cost = fluid.layers.softmax_with_cross_entropy(
                        logits=logits, label=y)
                loss = fluid.layers.mean(x=cost)
        return main, startup, loss

    rng = np.random.RandomState(8)
    feed = {'x': rng.randn(6, 3, 8).astype('float32'),
            'y': rng.randint(0, 30, (6, 1)).astype('int64')}
    vals = {}
    for fused in (False, True):
        main, startup, loss = build(fused)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        vals[fused] = float(np.ravel(exe.run(main, feed=feed,
                                             fetch_list=[loss])[0])[0])
    np.testing.assert_allclose(vals[True], vals[False], rtol=1e-5)


def test_fused_layer_bf16_matches_dense_bf16():
    """bf16 activations with fp32 master head: fused loss stays close to
    the dense bf16 composition (same matmul precision class)."""
    from paddle_tpu.core.program import reset_unique_name_guard
    from paddle_tpu.param_attr import ParamAttr

    def build(fused):
        with reset_unique_name_guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 13
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name='x', shape=[16],
                                      dtype='float32')
                y = fluid.layers.data(name='y', shape=[1], dtype='int64')
                xb = fluid.layers.cast(x=x, dtype='bfloat16')
                h = fluid.layers.fc(input=xb, size=32, act='tanh',
                                    param_attr=ParamAttr(name='h_w'),
                                    bias_attr=ParamAttr(name='h_b'))
                if fused:
                    cost = fluid.layers.fused_linear_softmax_ce(
                        input=h, label=y, size=60, chunk=32,
                        param_attr=ParamAttr(name='o_w'),
                        bias_attr=ParamAttr(name='o_b'))
                else:
                    logits = fluid.layers.fc(
                        input=h, size=60,
                        param_attr=ParamAttr(name='o_w'),
                        bias_attr=ParamAttr(name='o_b'))
                    logits = fluid.layers.cast(x=logits, dtype='float32')
                    cost = fluid.layers.softmax_with_cross_entropy(
                        logits=logits, label=y)
                loss = fluid.layers.mean(x=cost)
        return main, startup, loss

    rng = np.random.RandomState(7)
    feed = {'x': rng.randn(16, 16).astype('float32'),
            'y': rng.randint(0, 60, (16, 1)).astype('int64')}
    vals = {}
    for fused in (False, True):
        main, startup, loss = build(fused)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        vals[fused] = float(np.ravel(exe.run(main, feed=feed,
                                             fetch_list=[loss])[0])[0])
    np.testing.assert_allclose(vals[True], vals[False], rtol=2e-2)


def test_seq2seq_fused_loss_matches_dense_build():
    """The seq2seq model's fused-vocab-loss build tracks the dense build
    step-for-step (fp32, small config)."""
    from paddle_tpu.core.program import reset_unique_name_guard
    from paddle_tpu.models import seq2seq

    def build(fuse):
        with reset_unique_name_guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 9
            with fluid.program_guard(main, startup):
                src, trg, label, pred, avg_cost = seq2seq.build(
                    dict_size=80, word_dim=8, hidden_dim=16,
                    fuse_vocab_loss=fuse)
                fluid.optimizer.SGDOptimizer(0.1).minimize(avg_cost)
        return main, startup, avg_cost

    rng = np.random.RandomState(1)
    b, t = 4, 6
    ln = np.full((b,), t, np.int32)
    feeds = [{'src_word_id': (rng.randint(1, 80, (b, t, 1)), ln),
              'target_language_word': (rng.randint(1, 80, (b, t, 1)), ln),
              'target_language_next_word': (rng.randint(1, 80, (b, t, 1)),
                                            ln)}
             for _ in range(3)]

    losses = {}
    for fuse in (False, True):
        main, startup, avg_cost = build(fuse)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses[fuse] = [float(np.ravel(exe.run(main, feed=f,
                                               fetch_list=[avg_cost])[0])[0])
                        for f in feeds]
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-4,
                               atol=1e-5)


def test_rnn_lm_fused_loss_matches_naive_build():
    """The stacked-LSTM LM's fused vocab loss tracks the naive
    cross_entropy(softmax(x)) build step-for-step (fp32)."""
    from paddle_tpu.core.program import reset_unique_name_guard
    from paddle_tpu.models import rnn_lm

    def build(fuse):
        with reset_unique_name_guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 3
            with fluid.program_guard(main, startup):
                src, target, avg_cost = rnn_lm.build(
                    vocab_size=60, emb_dim=8, hidden_dim=12,
                    fuse_vocab_loss=fuse)
                fluid.optimizer.AdagradOptimizer(0.1).minimize(avg_cost)
        return main, startup, avg_cost

    rng = np.random.RandomState(4)
    b, t = 4, 6
    ln = np.full((b,), t, np.int32)
    feeds = [{'src': (rng.randint(1, 60, (b, t, 1)), ln),
              'target': (rng.randint(1, 60, (b, t, 1)), ln)}
             for _ in range(3)]

    losses = {}
    for fuse in (False, True):
        main, startup, avg_cost = build(fuse)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses[fuse] = [float(np.ravel(exe.run(main, feed=f,
                                               fetch_list=[avg_cost])[0])[0])
                        for f in feeds]
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-4,
                               atol=1e-5)


def test_dense_budget_needs_a_device_it_can_identify(monkeypatch):
    """The dense/chunked crossover is sized from the device's HBM.  Off
    a TPU there is nothing to size against and the v5e figure serves; a
    TPU that reports no memory_stats() is an error, not a v5e."""
    import pytest

    from paddle_tpu.ops import chunked_ce as cc

    class FakeDev:
        device_kind = 'TPU v5 lite'

        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    monkeypatch.delenv('PADDLE_TPU_DENSE_CE_BUDGET_MB', raising=False)
    assert cc._dense_bytes_budget() == (16 << 30) // 8
    monkeypatch.setattr(cc.jax, 'default_backend', lambda: 'tpu')
    monkeypatch.setattr(cc.jax, 'devices',
                        lambda: [FakeDev({'bytes_limit': 32 << 30})])
    assert cc._dense_bytes_budget() == (32 << 30) // 8
    monkeypatch.setattr(cc.jax, 'devices', lambda: [FakeDev(None)])
    with pytest.raises(RuntimeError, match='memory_stats'):
        cc._dense_bytes_budget()

"""Executor end-to-end tests: feed/fetch, whole-block jit caching, training
convergence, rng determinism (ref tests/test_executor_and_mul.py)."""
import numpy as np

import paddle_tpu as fluid


def _build_linreg():
    x = fluid.layers.data(name='x', shape=[13], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    pred = fluid.layers.fc(input=x, size=1)
    cost = fluid.layers.square_error_cost(input=pred, label=y)
    avg = fluid.layers.mean(x=cost)
    return pred, avg


def test_feed_fetch_mul():
    x = fluid.layers.data(name='x', shape=[3], dtype='float32')
    y = fluid.layers.fc(input=x, size=2, bias_attr=False)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.random.rand(5, 3).astype('float32')
    out, = exe.run(feed={'x': xv}, fetch_list=[y])
    w_name = [v.name for v in fluid.default_main_program().list_vars()
              if isinstance(v, fluid.Parameter)][0]
    w = fluid.global_scope().get_numpy(w_name)
    np.testing.assert_allclose(out, xv @ w, rtol=1e-4)


def test_training_reduces_loss():
    pred, avg = _build_linreg()
    opt = fluid.optimizer.SGD(learning_rate=0.02)
    opt.minimize(avg)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    W = rng.randn(13, 1).astype('float32')
    losses = []
    for _ in range(60):
        xb = rng.randn(32, 13).astype('float32')
        loss, = exe.run(feed={'x': xb, 'y': xb @ W}, fetch_list=[avg])
        losses.append(float(np.asarray(loss).ravel()[0]))
    assert losses[-1] < losses[0] * 0.2, losses[::10]


def test_adam_training():
    pred, avg = _build_linreg()
    fluid.optimizer.Adam(learning_rate=0.05).minimize(avg)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(1)
    W = rng.randn(13, 1).astype('float32')
    losses = []
    for _ in range(60):
        xb = rng.randn(32, 13).astype('float32')
        loss, = exe.run(feed={'x': xb, 'y': xb @ W}, fetch_list=[avg])
        losses.append(float(np.asarray(loss).ravel()[0]))
    assert losses[-1] < losses[0] * 0.2, losses[::10]


def test_fetch_variable_and_name():
    x = fluid.layers.data(name='x', shape=[2], dtype='float32')
    y = fluid.layers.scale(x=x, scale=3.0)
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.ones((2, 2), 'float32')
    a, b = exe.run(feed={'x': xv}, fetch_list=[y, y.name])
    np.testing.assert_allclose(a, 3 * xv)
    np.testing.assert_allclose(b, 3 * xv)


def test_dropout_train_vs_test():
    x = fluid.layers.data(name='x', shape=[100], dtype='float32')
    d = fluid.layers.dropout(x=x, dropout_prob=0.5)
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.ones((4, 100), 'float32')
    out, = exe.run(feed={'x': xv}, fetch_list=[d])
    frac = (np.asarray(out) == 0).mean()
    assert 0.25 < frac < 0.75  # roughly half dropped

    test_prog = fluid.default_main_program().inference_optimize()
    out2, = exe.run(test_prog, feed={'x': xv}, fetch_list=[d.name])
    # reference dropout_op.h is_test path: Out = X * (1 - p)
    np.testing.assert_allclose(out2, xv * 0.5)


def test_run_steps_matches_run_loop():
    """run_steps(K) (one lax.scan-compiled XLA program, donated state)
    is numerics-identical to K successive run() calls — same PRNG chain
    (dropout included), same optimizer state evolution."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core.program import reset_unique_name_guard

    def build():
        with reset_unique_name_guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 17
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name='x', shape=[8],
                                      dtype='float32')
                y = fluid.layers.data(name='y', shape=[1],
                                      dtype='float32')
                h = fluid.layers.fc(input=x, size=16, act='relu')
                h = fluid.layers.dropout(x=h, dropout_prob=0.3)
                p = fluid.layers.fc(input=h, size=1)
                loss = fluid.layers.mean(
                    x=fluid.layers.square_error_cost(input=p, label=y))
                fluid.optimizer.AdamOptimizer(
                    learning_rate=0.01).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(8)
    w = rng.randn(8, 1).astype('float32')
    batches = [{'x': (xb := rng.randn(8, 8).astype('float32')),
                'y': xb @ w} for _ in range(4)]

    main, startup, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    want = [float(np.ravel(exe.run(main, feed=f, fetch_list=[loss])[0])[0])
            for f in batches]
    params_want = {p.name: np.asarray(fluid.global_scope().find_var(p.name))
                   for p in main.global_block().all_parameters()}

    # stacked-feeds mode
    main, startup, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    got = exe.run_steps(main, feed=batches, fetch_list=[loss])[0]
    np.testing.assert_allclose(np.ravel(got), want, rtol=1e-5, atol=1e-6)
    for n, v in params_want.items():
        np.testing.assert_allclose(
            np.asarray(fluid.global_scope().find_var(n)), v,
            rtol=1e-5, atol=1e-6, err_msg=n)

    # repeat-one-feed mode: equals 4 runs of the same batch
    main, startup, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    want_rep = [float(np.ravel(exe.run(main, feed=batches[0],
                                       fetch_list=[loss])[0])[0])
                for _ in range(4)]
    main, startup, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    got_rep = exe.run_steps(main, feed=batches[0], fetch_list=[loss],
                            repeat=4)[0]
    np.testing.assert_allclose(np.ravel(got_rep), want_rep, rtol=1e-5,
                               atol=1e-6)


def test_run_steps_stacked_ragged_feeds_match_run_loop():
    """Stacked-feeds run_steps with (array, lengths) ragged feeds: the
    @LEN companions stack and scan along with the data, matching K
    run() calls exactly (ragged mean masks padded positions)."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core.program import reset_unique_name_guard

    def build():
        with reset_unique_name_guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 23
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name='x', shape=[1], dtype='int64',
                                      lod_level=1)
                emb = fluid.layers.embedding(input=x, size=[30, 6])
                pooled = fluid.layers.sequence_pool(input=emb,
                                                    pool_type='sum')
                pred = fluid.layers.fc(input=pooled, size=1)
                loss = fluid.layers.mean(x=fluid.layers.square(x=pred))
                fluid.optimizer.SGDOptimizer(
                    learning_rate=0.01).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(11)
    batches = []
    for _ in range(3):
        ids = rng.randint(0, 30, (4, 7, 1)).astype('int64')
        ln = rng.randint(1, 8, (4,)).astype('int32')
        batches.append({'x': (ids, ln)})

    main, startup, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    want = [float(np.ravel(exe.run(main, feed=f,
                                   fetch_list=[loss])[0])[0])
            for f in batches]

    main, startup, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    got = exe.run_steps(main, feed=batches, fetch_list=[loss])[0]
    np.testing.assert_allclose(np.ravel(got), want, rtol=1e-5,
                               atol=1e-6)


def test_run_steps_inconsistent_feed_keys_named():
    """ADVICE r3: K feed dicts with different key sets fail with an error
    naming the step and the missing/extra keys, not an opaque scan-shape
    mismatch."""
    import pytest

    import paddle_tpu as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[2], dtype='float32')
        y = fluid.layers.data(name='y', shape=[2], dtype='float32')
        fluid.layers.elementwise_add(x=x, y=y)
    exe = fluid.Executor(fluid.CPUPlace())
    a = np.ones((3, 2), 'float32')
    feeds = [{'x': a, 'y': a}, {'x': a}]
    with pytest.raises(ValueError, match=r"step 1 is missing \['y'\]"):
        exe.run_steps(main, feed=feeds, fetch_list=[])


def test_run_steps_out_only_state_single_copy():
    """ADVICE r3: out-only persistables (written, never read — e.g. a
    metric accumulator snapshot) ride the scan carry; the value after
    run_steps(K) equals the K-th run() value."""
    import paddle_tpu as fluid
    from paddle_tpu.core.program import reset_unique_name_guard

    def build():
        with reset_unique_name_guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 5
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name='x', shape=[4],
                                      dtype='float32')
                h = fluid.layers.fc(input=x, size=4)
                loss = fluid.layers.mean(x=fluid.layers.square(x=h))
                fluid.optimizer.SGDOptimizer(
                    learning_rate=0.1).minimize(loss)
                snap = fluid.layers.assign(loss)
                snap.persistable = True
        return main, startup, loss, snap

    rng = np.random.RandomState(2)
    batches = [{'x': rng.randn(4, 4).astype('float32')}
               for _ in range(3)]

    main, startup, loss, snap = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    for f in batches:
        exe.run(main, feed=f, fetch_list=[loss])
    want = np.asarray(fluid.global_scope().find_var(snap.name))

    main, startup, loss, snap = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run_steps(main, feed=batches, fetch_list=[loss])
    got = np.asarray(fluid.global_scope().find_var(snap.name))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_plan_cache_keys_on_scope_uid_not_id():
    """Plan-cache scope identity is a monotonic uid: id() reuse after gc
    must not alias a new scope's plans with a dead scope's."""
    import gc

    import paddle_tpu as fluid

    s1 = fluid.Scope()
    s2 = fluid.Scope()
    assert s1._uid != s2._uid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[3], dtype='float32')
        y = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {'x': np.ones((2, 3), np.float32)}

    scope_a = fluid.Scope()
    exe.run(startup, scope=scope_a)
    exe.run(main, feed=feed, fetch_list=[y], scope=scope_a)
    n_after_a = len(exe._cache)
    uid_a = scope_a._uid
    del scope_a
    gc.collect()

    scope_b = fluid.Scope()
    assert scope_b._uid != uid_a
    exe.run(startup, scope=scope_b)
    exe.run(main, feed=feed, fetch_list=[y], scope=scope_b)
    # a fresh scope compiles fresh plans instead of aliasing the dead
    # scope's entries
    assert len(exe._cache) > n_after_a


def test_use_program_cache_false_bypasses_insertion():
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[3], dtype='float32')
        y = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, use_program_cache=False)
    feed = {'x': np.ones((2, 3), np.float32)}
    out1, = exe.run(main, feed=feed, fetch_list=[y],
                    use_program_cache=False)
    assert exe._cache == {}
    out2, = exe.run(main, feed=feed, fetch_list=[y])
    assert len(exe._cache) == 1
    np.testing.assert_allclose(out1, out2, rtol=1e-6)


def test_persistent_compilation_cache(compile_cache):
    """With the cache placed from outside (JAX_COMPILATION_CACHE_DIR),
    compiled executables land in that directory and survive a process
    restart."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[3], dtype='float32')
        y = fluid.layers.fc(input=x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed={'x': np.ones((2, 3), np.float32)},
            fetch_list=[y])
    assert compile_cache.exists() and any(compile_cache.iterdir())


def test_compile_cache_resolver(monkeypatch):
    """One resolver: with JAX_COMPILATION_CACHE_DIR set the package
    sets no cache directory (jax reads the variable itself); unset, it
    resolves to <checkout>/.jax_cache."""
    import os

    import jax

    import paddle_tpu as fluid
    from paddle_tpu import compile_cache as cc

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # an earlier Executor in this process may have resolved it already
    jax.config.update('jax_compilation_cache_dir', None)
    calls = []
    monkeypatch.setattr(jax.config, 'update',
                        lambda name, value: calls.append((name, value)))

    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/somewhere/else')
    assert cc.compile_cache_dir() == '/somewhere/else'
    fluid.Executor(fluid.CPUPlace())
    assert [c for c in calls if c[0] == 'jax_compilation_cache_dir'] == []

    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR')
    assert cc.compile_cache_dir() == os.path.join(checkout, '.jax_cache')
    fluid.Executor(fluid.CPUPlace())
    assert calls == [('jax_compilation_cache_dir',
                      os.path.join(checkout, '.jax_cache'))]

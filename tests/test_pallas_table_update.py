"""Pallas row-sparse table-update kernels (ops/pallas/table_update.py).

Exact-parity contract: the Pallas apply is BITWISE identical to the
`.at[rows].add` XLA scatter path for SGD / Adagrad / lazy Adam — with
duplicate rows, ragged sentinel-padded row counts, and the empty edge
included — on CPU interpret mode, jitted on both sides (the executor
always runs the step jitted; comparing an eager oracle against the
traced kernel would instead measure XLA:CPU's fma contraction).

The `-m slow` micro at the bottom is the scatter-apply benchmark
regression harness: on TPU it asserts the Pallas path stays height-flat
(<= 1.2x from the smallest to the largest table) where the XLA scatter
grows with table height; on CPU it still runs both paths and checks
parity, so tier-1's fast subset keeps the kernel honest.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core.selected_rows import (merge_duplicate_rows,
                                           merge_rows_sentinel)
from paddle_tpu.ops.pallas.table_update import (sparse_apply_adagrad,
                                                sparse_apply_adam,
                                                sparse_apply_mode,
                                                sparse_apply_sgd)

rng = np.random.RandomState(7)

H, D = 41, 8
B1, B2, EPS_ADAM, EPS_ADAGRAD = 0.9, 0.999, 1e-8, 1e-6


def _rows_vals(k=29, n_sentinel=3, n_dup=4):
    """Touched rows with duplicates and a ragged sentinel pad (ids ==
    height mark padding slots, like a bucketed caller would emit)."""
    real = rng.randint(0, H, size=(k - n_sentinel,)).astype(np.int32)
    if n_dup:
        real[-n_dup:] = real[:n_dup]  # guaranteed duplicates
    rows = np.concatenate([real, np.full((n_sentinel,), H, np.int32)])
    perm = rng.permutation(k)  # sentinels interleaved, not pre-sorted
    vals = rng.randn(k, D).astype(np.float32)
    return jnp.asarray(rows[perm]), jnp.asarray(vals)


def _table(signed=True):
    t = rng.randn(H, D).astype(np.float32)
    return jnp.asarray(t if signed else np.abs(t))


def _assert_bitwise(got, want, msg):
    got, want = np.asarray(got), np.asarray(want)
    eq = got == want
    assert eq.all(), '%s: %d/%d elements differ (max %g)' % (
        msg, (~eq).sum(), eq.size, np.abs(got - want).max())


def test_sgd_bitwise_vs_scatter():
    lr = jnp.float32(0.13)

    @jax.jit
    def oracle(p, rows, vals):
        return p.at[rows].add(-lr * vals)

    @jax.jit
    def pallas(p, rows, vals):
        return sparse_apply_sgd(p, rows, vals, lr)

    for trial in range(5):
        p = _table()
        rows, vals = _rows_vals()
        _assert_bitwise(pallas(p, rows, vals), oracle(p, rows, vals),
                        'sgd trial %d' % trial)


def test_adagrad_bitwise_vs_scatter():
    lr = jnp.float32(0.21)

    @jax.jit
    def oracle(p, mom, rows, vals):
        # ops/optim_ops.py _adagrad sparse branch, verbatim
        mrows, g, valid = merge_duplicate_rows(rows, vals)
        vmask = valid[:, None]
        mom_row = mom[mrows] + jnp.square(g)
        mom_new = mom.at[mrows].add(
            jnp.where(vmask, jnp.square(g), 0.0))
        step = -lr * g / (jnp.sqrt(mom_row) + EPS_ADAGRAD)
        return p.at[mrows].add(jnp.where(vmask, step, 0.0)), mom_new

    @jax.jit
    def pallas(p, mom, rows, vals):
        return sparse_apply_adagrad(p, mom, rows, vals, lr, EPS_ADAGRAD)

    for trial in range(5):
        p, mom = _table(), _table(signed=False)
        rows, vals = _rows_vals()
        p_got, m_got = pallas(p, mom, rows, vals)
        p_want, m_want = oracle(p, mom, rows, vals)
        _assert_bitwise(p_got, p_want, 'adagrad param trial %d' % trial)
        _assert_bitwise(m_got, m_want, 'adagrad moment trial %d' % trial)


def test_adam_bitwise_vs_scatter():
    lr_t = jnp.float32(0.05)

    @jax.jit
    def oracle(p, m, v, rows, vals):
        # ops/optim_ops.py _adam lazy sparse branch, verbatim
        mrows, g, valid = merge_duplicate_rows(rows, vals)
        vmask = valid[:, None]
        m_row = B1 * m[mrows] + (1 - B1) * g
        v_row = B2 * v[mrows] + (1 - B2) * jnp.square(g)
        m_new = m.at[mrows].add(jnp.where(vmask, m_row - m[mrows], 0.0))
        v_new = v.at[mrows].add(jnp.where(vmask, v_row - v[mrows], 0.0))
        step = -lr_t * m_row / (jnp.sqrt(v_row) + EPS_ADAM)
        return (p.at[mrows].add(jnp.where(vmask, step, 0.0)), m_new,
                v_new)

    @jax.jit
    def pallas(p, m, v, rows, vals):
        return sparse_apply_adam(p, m, v, rows, vals, lr_t, B1, B2,
                                 EPS_ADAM)

    for trial in range(5):
        p, m, v = _table(), _table(), _table(signed=False)
        rows, vals = _rows_vals()
        got = pallas(p, m, v, rows, vals)
        want = oracle(p, m, v, rows, vals)
        for name, a, b in zip(('param', 'moment1', 'moment2'), got, want):
            _assert_bitwise(a, b, 'adam %s trial %d' % (name, trial))


def test_ragged_padding_is_exact_noop():
    """Padding the id vector with `height` up to a bucket size changes
    nothing — bitwise — for every rule: sentinel slots are skipped, not
    applied-with-zero."""
    lr = jnp.float32(0.3)
    p, mom = _table(), _table(signed=False)
    rows, vals = _rows_vals(k=11, n_sentinel=0, n_dup=2)
    pad_rows = jnp.concatenate([rows, jnp.full((5,), H, jnp.int32)])
    pad_vals = jnp.concatenate(
        [vals, jnp.asarray(rng.randn(5, D).astype(np.float32))])
    _assert_bitwise(sparse_apply_sgd(p, pad_rows, pad_vals, lr),
                    sparse_apply_sgd(p, rows, vals, lr), 'sgd padded')
    got = sparse_apply_adagrad(p, mom, pad_rows, pad_vals, lr,
                               EPS_ADAGRAD)
    want = sparse_apply_adagrad(p, mom, rows, vals, lr, EPS_ADAGRAD)
    for name, a, b in zip(('param', 'moment'), got, want):
        _assert_bitwise(a, b, 'adagrad padded %s' % name)
    m, v = _table(), _table(signed=False)
    got = sparse_apply_adam(p, m, v, pad_rows, pad_vals,
                            jnp.float32(0.05), B1, B2, EPS_ADAM)
    want = sparse_apply_adam(p, m, v, rows, vals, jnp.float32(0.05),
                             B1, B2, EPS_ADAM)
    for name, a, b in zip(('param', 'moment1', 'moment2'), got, want):
        _assert_bitwise(a, b, 'adam padded %s' % name)


def test_all_slots_sentinel_and_empty():
    """K=0 and all-padding inputs both leave every table byte alone."""
    p = _table()
    lr = jnp.float32(0.5)
    _assert_bitwise(
        sparse_apply_sgd(p, jnp.zeros((0,), jnp.int32),
                         jnp.zeros((0, D), jnp.float32), lr), p,
        'sgd empty')
    rows = jnp.full((6,), H, jnp.int32)
    vals = jnp.asarray(rng.randn(6, D).astype(np.float32))
    _assert_bitwise(sparse_apply_sgd(p, rows, vals, lr), p,
                    'sgd all-sentinel')
    mom = _table(signed=False)
    p_got, m_got = sparse_apply_adagrad(p, mom, rows, vals, lr,
                                        EPS_ADAGRAD)
    _assert_bitwise(p_got, p, 'adagrad all-sentinel param')
    _assert_bitwise(m_got, mom, 'adagrad all-sentinel moment')
    m, v = _table(), _table(signed=False)
    p_got, m_got, v_got = sparse_apply_adam(
        p, m, v, rows, vals, jnp.float32(0.05), B1, B2, EPS_ADAM)
    _assert_bitwise(p_got, p, 'adam all-sentinel param')
    _assert_bitwise(m_got, m, 'adam all-sentinel m1 (no decay on pad)')
    _assert_bitwise(v_got, v, 'adam all-sentinel m2 (no decay on pad)')


def test_merge_rows_sentinel():
    rows = jnp.asarray([3, 1, 3, 50, 0, 50], jnp.int32)  # 50 = padding
    vals = jnp.asarray(rng.randn(6, 2).astype(np.float32))
    mrows, mvals, valid = merge_rows_sentinel(rows, vals, 10)
    assert int(valid.sum()) == 3
    got = {int(r): np.asarray(v)
           for r, v, ok in zip(mrows, mvals, valid) if bool(ok)}
    np.testing.assert_array_equal(got[0], np.asarray(vals[4]))
    np.testing.assert_array_equal(got[1], np.asarray(vals[1]))
    np.testing.assert_array_equal(got[3], np.asarray(vals[0] + vals[2]))
    # every non-real slot carries the sentinel row (scatter drops it)
    assert (np.asarray(mrows)[~np.asarray(valid)] == 10).all()
    # tile alignment: output length padded to a multiple, sentinel tail
    mrows, mvals, valid = merge_rows_sentinel(rows, vals, 10, pad_to=8)
    assert mrows.shape == (8,) and mvals.shape == (8, 2)
    assert (np.asarray(mrows)[3:] == 10).all()
    assert int(valid.sum()) == 3


def test_mode_flag(monkeypatch):
    monkeypatch.delenv('PADDLE_TPU_SPARSE_APPLY', raising=False)
    # no platform selects the kernels on its own: they lost to the XLA
    # expressions on the v5e (PERF.md, chip bring-up)
    assert sparse_apply_mode() == 'xla'
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert sparse_apply_mode() == 'xla'
    monkeypatch.setenv('PADDLE_TPU_SPARSE_APPLY', 'pallas')
    assert sparse_apply_mode() == 'pallas'
    monkeypatch.setenv('PADDLE_TPU_SPARSE_APPLY', 'xla')
    assert sparse_apply_mode() == 'xla'


def _train_emb(optimizer, steps=3):
    """Sparse-embedding training loop (the CTR shape in miniature);
    returns the final embedding table + optimizer state snapshot.
    Built under a fresh unique-name scope so the pallas and xla runs
    generate identical auto names (comparable state dicts)."""
    from paddle_tpu.core.program import reset_unique_name_guard
    with reset_unique_name_guard():
        return _train_emb_inner(optimizer, steps)


def _train_emb_inner(optimizer, steps):
    main = fluid.Program()
    startup = fluid.Program()
    main.random_seed = 42
    startup.random_seed = 42
    with fluid.program_guard(main, startup):
        words = fluid.layers.data(name='words', shape=[4], dtype='int64')
        label = fluid.layers.data(name='label', shape=[1],
                                  dtype='float32')
        emb = fluid.layers.embedding(
            input=words, size=[50, 8], is_sparse=True,
            param_attr=fluid.ParamAttr(
                name='emb_w',
                initializer=fluid.initializer.NormalInitializer(seed=7)))
        pooled = fluid.layers.sequence_pool(input=emb, pool_type='sum')
        pred = fluid.layers.fc(
            input=pooled, size=1, act=None,
            param_attr=fluid.ParamAttr(
                name='fc_w',
                initializer=fluid.initializer.NormalInitializer(seed=9)))
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=pred, label=label))
        optimizer().minimize(loss)
    assert any(op.type == 'sparse_grad_assemble'
               for op in main.global_block().ops)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    r = np.random.RandomState(3)
    for _ in range(steps):
        # duplicate ids inside one batch exercise the merge/accumulate
        words = r.randint(0, 50, (6, 4))
        words[0] = words[1]
        exe.run(main, feed={'words': words.astype('int64'),
                            'label': r.randn(6, 1).astype('float32')},
                fetch_list=[loss])
    scope = fluid.global_scope()
    state = {v.name: np.asarray(scope.find_var(v.name)).copy()
             for v in main.list_vars()
             if v.persistable and scope.find_var(v.name) is not None}
    return state


@pytest.mark.parametrize('opt', ['sgd', 'adagrad', 'adam'])
def test_executor_end_to_end_parity(opt, monkeypatch):
    """The full executor path — sparse_grad_assemble -> optimizer op —
    produces bitwise-identical training state under
    PADDLE_TPU_SPARSE_APPLY=pallas and =xla (the escape hatch restores
    today's path verbatim; the kernel must match it exactly)."""
    mk = {'sgd': lambda: fluid.optimizer.SGDOptimizer(0.1),
          'adagrad': lambda: fluid.optimizer.AdagradOptimizer(0.1),
          'adam': lambda: fluid.optimizer.AdamOptimizer(0.05)}[opt]
    monkeypatch.setenv('PADDLE_TPU_SPARSE_APPLY', 'xla')
    want = _train_emb(mk)
    monkeypatch.setenv('PADDLE_TPU_SPARSE_APPLY', 'pallas')
    got = _train_emb(mk)
    assert set(got) == set(want)
    for name in sorted(want):
        _assert_bitwise(got[name], want[name], '%s %s' % (opt, name))


@pytest.mark.slow
def test_scatter_apply_micro_height_flat():
    """Benchmark-regression harness for the scatter-apply micro: the
    Pallas path must stay height-flat where the XLA scatter pays an
    O(table-height) pass.  The flatness assert only bites on TPU (CPU
    scatter is already O(touched) and interpret-mode timing is
    meaningless); parity is asserted everywhere, so the kernel cannot
    silently fall off the curve OR off the exact result."""
    on_tpu = jax.default_backend() == 'tpu'
    heights = (100003, 1000003, 10000019) if on_tpu else (1009, 4001)
    k = 131072 if on_tpu else 96
    d = 8
    lr = jnp.float32(0.01)
    ratios = []
    r = np.random.RandomState(11)

    def timed(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(3):
            out = fn(*args)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / 3

    times = {'pallas': [], 'xla': []}
    for h in heights:
        p = jnp.asarray(r.randn(h, d).astype(np.float32))
        mom = jnp.asarray(np.abs(r.randn(h, d)).astype(np.float32))
        rows = jnp.asarray(r.randint(0, h, size=(k,)).astype(np.int32))
        vals = jnp.asarray(r.randn(k, d).astype(np.float32))

        @jax.jit
        def xla(p, mom, rows, vals):
            mrows, g, valid = merge_duplicate_rows(rows, vals)
            vmask = valid[:, None]
            mom_row = mom[mrows] + jnp.square(g)
            mom_new = mom.at[mrows].add(
                jnp.where(vmask, jnp.square(g), 0.0))
            step = -lr * g / (jnp.sqrt(mom_row) + EPS_ADAGRAD)
            return p.at[mrows].add(jnp.where(vmask, step, 0.0)), mom_new

        @jax.jit
        def pallas(p, mom, rows, vals):
            return sparse_apply_adagrad(p, mom, rows, vals, lr,
                                        EPS_ADAGRAD)

        got, t_pal = timed(pallas, p, mom, rows, vals)
        want, t_xla = timed(xla, p, mom, rows, vals)
        times['pallas'].append(t_pal)
        times['xla'].append(t_xla)
        for name, a, b in zip(('param', 'moment'), got, want):
            _assert_bitwise(a, b, 'micro h=%d %s' % (h, name))
    if on_tpu:
        flat = times['pallas'][-1] / times['pallas'][0]
        assert flat <= 1.2, (
            'pallas scatter-apply no longer height-flat: %.2fx from '
            '%d to %d rows (times %s)' % (flat, heights[0], heights[-1],
                                          times['pallas']))


def test_negative_ids_wrap_like_the_oracle():
    """XLA scatter/gather wraps Python-style negatives (-1 = last row);
    the kernels must reproduce that, not silently skip them — the =xla
    escape hatch and pallas mode may never diverge on the same feed."""
    lr = jnp.float32(0.17)
    p, mom = _table(), _table(signed=False)
    rows = jnp.asarray([3, -1, 7, -3, 3, -1], jnp.int32)
    vals = jnp.asarray(rng.randn(6, D).astype(np.float32))

    got = jax.jit(lambda p, r, v: sparse_apply_sgd(p, r, v, lr))(
        p, rows, vals)
    want = jax.jit(lambda p, r, v: p.at[r].add(-lr * v))(p, rows, vals)
    _assert_bitwise(got, want, 'sgd negative ids')

    @jax.jit
    def oracle(p, mom, rows, vals):
        mrows, g, valid = merge_duplicate_rows(rows, vals)
        vmask = valid[:, None]
        mom_row = mom[mrows] + jnp.square(g)
        mom_new = mom.at[mrows].add(jnp.where(vmask, jnp.square(g), 0.0))
        step = -lr * g / (jnp.sqrt(mom_row) + EPS_ADAGRAD)
        return p.at[mrows].add(jnp.where(vmask, step, 0.0)), mom_new

    # no positive alias of a wrapped id in the feed: the oracle's merge
    # keys on the RAW id, so -1 and H-1 together would merge differently
    # (a pathological mix with no well-defined "today" semantics)
    rows = jnp.asarray([5, -2, -2, 11], jnp.int32)
    vals = jnp.asarray(rng.randn(4, D).astype(np.float32))
    p_got, m_got = jax.jit(lambda p, m, r, v: sparse_apply_adagrad(
        p, m, r, v, lr, EPS_ADAGRAD))(p, mom, rows, vals)
    p_want, m_want = oracle(p, mom, rows, vals)
    _assert_bitwise(p_got, p_want, 'adagrad negative ids param')
    _assert_bitwise(m_got, m_want, 'adagrad negative ids moment')

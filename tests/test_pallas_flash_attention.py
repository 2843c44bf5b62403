"""Pallas flash-attention kernel vs dense reference (forward + grads).

Runs interpret=True on the CPU backend — same kernel code that compiles
to Mosaic on TPU.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention

rng = np.random.RandomState(47)


def _dense(q, k, v, causal, scale=None):
    d = q.shape[-1]
    scale = scale or d ** -0.5
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = s.shape[2], s.shape[3]
        mask = np.arange(tq)[:, None] >= np.arange(tk)[None, :]
        s = jnp.where(jnp.asarray(mask)[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v.astype(jnp.float32))


@pytest.mark.parametrize('causal', [False, True])
def test_flash_matches_dense(causal):
    b, t, h, d = 2, 256, 2, 64
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    want = _dense(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_uneven_blocks():
    # T not a multiple of the block size exercises cdiv/padding edges
    b, t, h, d = 1, 96, 1, 32
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    got = flash_attention(q, k, v, block_q=64, block_k=64)
    want = _dense(q, k, v, False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_3d_input():
    b, t, d = 2, 128, 32
    q = jnp.asarray(rng.randn(b, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, d), jnp.float32)
    got = flash_attention(q, k, v, block_q=64, block_k=64)
    assert got.shape == (b, t, d)
    want = _dense(q[:, :, None], k[:, :, None], v[:, :, None],
                  False)[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_nets_attention_flash_matches_matmul_path():
    """The program-level flash path == the matmul/softmax layer path."""
    import paddle_tpu as fluid

    b, t, dm, heads = 2, 64, 32, 4
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.layers.data(name='q', shape=[t, dm], dtype='float32')
        k = fluid.layers.data(name='k', shape=[t, dm], dtype='float32')
        v = fluid.layers.data(name='v', shape=[t, dm], dtype='float32')
        dense = fluid.nets.scaled_dot_product_attention(
            q, k, v, num_heads=heads, use_flash=False)
        flash = fluid.nets.scaled_dot_product_attention(
            q, k, v, num_heads=heads, use_flash=True,
            pallas_interpret=True)  # exercise the KERNEL path on CPU CI
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {n: rng.randn(b, t, dm).astype('float32') for n in 'qkv'}
    o1, o2 = exe.run(main, feed=feed, fetch_list=[dense, flash])
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1),
                               rtol=2e-4, atol=2e-4)


def test_nets_attention_defaults_to_flash():
    """VERDICT r3 #6: the TPU-first kernel is the layer DEFAULT where
    the config qualifies (no attention dropout); dropout falls back to
    the composed matmul+softmax path; numerics match the forced-dense
    build either way (off-TPU the op computes dense math itself)."""
    import paddle_tpu as fluid

    b, t, dm, heads = 2, 32, 16, 2

    def build(**kwargs):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            q = fluid.layers.data(name='q', shape=[t, dm],
                                  dtype='float32')
            k = fluid.layers.data(name='k', shape=[t, dm],
                                  dtype='float32')
            v = fluid.layers.data(name='v', shape=[t, dm],
                                  dtype='float32')
            o = fluid.nets.scaled_dot_product_attention(
                q, k, v, num_heads=heads, **kwargs)
        return main, startup, o

    main, startup, o = build()
    assert any(op.type == 'flash_attention'
               for op in main.global_block().ops), \
        "default must ride the flash op"
    md, sd, od = build(use_flash=False)
    assert not any(op.type == 'flash_attention'
                   for op in md.global_block().ops)
    mdrop, _, _ = build(dropout_rate=0.3)
    assert not any(op.type == 'flash_attention'
                   for op in mdrop.global_block().ops), \
        "dropout configs fall back to the composed path"

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {n: rng.randn(b, t, dm).astype('float32') for n in 'qkv'}
    got = exe.run(main, feed=feed, fetch_list=[o])[0]
    exe.run(sd)
    want = exe.run(md, feed=feed, fetch_list=[od])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize('causal', [False, True])
def test_flash_grads_match_dense(causal):
    b, t, h, d = 1, 128, 2, 32
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=64,
                            block_k=64)
        return jnp.sum(jnp.sin(o))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(_dense(q, k, v, causal)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gd, 'qkv'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg='d' + name)


@pytest.mark.parametrize('split', [False, True])
@pytest.mark.parametrize('causal', [False, True])
def test_pallas_backward_kernels_match_scan(causal, split, monkeypatch):
    """The TPU Pallas backward must produce the same grads as the
    jax-scan flash recompute — both the default fused k-major kernel
    and (split=True, via PADDLE_TPU_FLASH_BWD_SPLIT) the dkv/dq split
    pair, which stays the automatic fallback for sequences whose dq
    accumulator exceeds _FUSED_DQ_BYTES."""
    b, t, h, d = 2, 160, 2, 32  # non-multiple of the block: padding path
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    ct = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        return jnp.sum(o * ct)

    # force each path explicitly so the comparison is real on any backend
    monkeypatch.setenv('PADDLE_TPU_FLASH_BWD_SCAN', '1')
    jax.clear_caches()  # the env gate is read at trace time
    g_scan = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.delenv('PADDLE_TPU_FLASH_BWD_SCAN')
    monkeypatch.setenv('PADDLE_TPU_FLASH_BWD_PALLAS', '1')
    if split:
        monkeypatch.setenv('PADDLE_TPU_FLASH_BWD_SPLIT', '1')
    jax.clear_caches()
    g_pal = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.delenv('PADDLE_TPU_FLASH_BWD_PALLAS')
    if split:
        monkeypatch.delenv('PADDLE_TPU_FLASH_BWD_SPLIT')
    jax.clear_caches()
    for a, b_, name in zip(g_scan, g_pal, 'qkv'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg='d' + name)


@pytest.mark.parametrize('causal', [False, True])
def test_pallas_backward_mixed_tiles_match_scan(causal):
    """The split dkv/dq kernels may run with DIFFERENT tile pairs
    (shared padding goes to the lcm of the block sizes); grads must
    stay exact vs the scan recompute."""
    import importlib
    fa = importlib.import_module('paddle_tpu.ops.pallas.flash_attention')

    bh, t, d = 3, 160, 32
    scale = d ** -0.5
    q = jnp.asarray(rng.randn(bh, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(bh, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(bh, t, d), jnp.float32)
    do = jnp.asarray(rng.randn(bh, t, d), jnp.float32)

    o, lse = fa._fa_forward_sliced(q, k, v, causal, scale, 64, 64, True)
    res = (q, k, v, jnp.int32(0), jnp.int32(0), o, lse)
    want = fa._fa_backward(causal, scale, 64, res, do)
    got = fa._fa_backward_pallas(causal, scale, ((64, 32), (32, 64)),
                                 res, do, None, interpret=True,
                                 allow_fused=False)
    for a, b_, name in zip(got, want, ('dq', 'dk', 'dv')):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


def test_nets_attention_dense_fallback_matches_matmul_path():
    """Without pallas_interpret on a non-TPU place the op takes the
    _dense_attention fallback — it must equal the layer-composed path
    (this is what every CPU/GPU use_flash=True run executes)."""
    import paddle_tpu as fluid

    b, t, dm, heads = 2, 48, 32, 4
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.layers.data(name='q', shape=[t, dm], dtype='float32')
        k = fluid.layers.data(name='k', shape=[t, dm], dtype='float32')
        v = fluid.layers.data(name='v', shape=[t, dm], dtype='float32')
        dense = fluid.nets.scaled_dot_product_attention(
            q, k, v, num_heads=heads)
        flash = fluid.nets.scaled_dot_product_attention(
            q, k, v, num_heads=heads, use_flash=True)  # dense fallback
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {n: rng.randn(b, t, dm).astype('float32') for n in 'qkv'}
    o1, o2 = exe.run(main, feed=feed, fetch_list=[dense, flash])
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1),
                               rtol=2e-4, atol=2e-4)


def test_flash_bwd_env_gate_resolves_at_call_time(monkeypatch):
    """r2 advisor: the backward-mode env gates are read when
    flash_attention() is CALLED (and ride the vjp cache key as a
    nondiff arg), so toggling them mid-process changes the next trace
    instead of silently hitting a stale cached closure."""
    import importlib
    # the package re-exports the function under the module's name, so a
    # plain import binds the function; fetch the module itself
    fa = importlib.import_module('paddle_tpu.ops.pallas.flash_attention')
    monkeypatch.delenv('PADDLE_TPU_FLASH_BWD_PALLAS', raising=False)
    monkeypatch.delenv('PADDLE_TPU_FLASH_BWD_SCAN', raising=False)
    assert fa._bwd_mode_from_env(True) == 'scan'     # interpret => scan
    assert fa._bwd_mode_from_env(False) == 'pallas'  # tpu default
    monkeypatch.setenv('PADDLE_TPU_FLASH_BWD_SCAN', '1')
    assert fa._bwd_mode_from_env(False) == 'scan'
    monkeypatch.setenv('PADDLE_TPU_FLASH_BWD_PALLAS', '1')
    assert fa._bwd_mode_from_env(True) == 'pallas'


def test_rnn_vmem_budget_derives_from_device(monkeypatch):
    """r2 advisor: the BPTT VMEM budget tracks the device generation
    (16 MB through v5, 32 MB from v6) instead of a hardcoded 12 MB;
    the env override still wins."""
    from paddle_tpu.ops import rnn

    class FakeDev:
        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.delenv('PADDLE_TPU_RNN_VMEM_BUDGET_MB', raising=False)
    # off-TPU the kernels only run interpreted: the v5e figure serves
    assert rnn._rnn_vmem_budget() == int(16 * 1024 * 1024 * 0.75)
    monkeypatch.setattr(rnn.jax, 'default_backend', lambda: 'tpu')
    monkeypatch.setattr(rnn.jax, 'devices',
                        lambda: [FakeDev('TPU v5 lite')])
    assert rnn._rnn_vmem_budget() == int(16 * 1024 * 1024 * 0.75)
    monkeypatch.setattr(rnn.jax, 'devices', lambda: [FakeDev('TPU v6e')])
    assert rnn._rnn_vmem_budget() == int(32 * 1024 * 1024 * 0.75)
    # a TPU the chooser cannot identify is an error, not a v5e
    monkeypatch.setattr(rnn.jax, 'devices', lambda: [FakeDev('mystery')])
    with pytest.raises(RuntimeError, match='mystery'):
        rnn._rnn_vmem_budget()
    monkeypatch.setenv('PADDLE_TPU_RNN_VMEM_BUDGET_MB', '5')
    assert rnn._rnn_vmem_budget() == 5 * 1024 * 1024


def test_shared_padding_clamps_adversarial_lengths():
    """The shared backward padding must stay bounded by one block: the
    lcm of the two split kernels' clamped block sizes explodes when a
    sequence length lands between powers of two (tk=1100 under the
    default d<=64 tiles used to pad to lcm(1100, 1024) = 281600 rows —
    a 256x blowup, ADVICE.md).  Exactly-dividing lengths keep their
    zero-padding behavior."""
    from paddle_tpu.ops.pallas.flash_attention import _shared_padding
    bwd_tiles = ((1024, 2048), (1024, 1024))  # default d<=64 dkv/dq
    # the adversarial length from the advice item
    (_, bk1), (_, bk2), _tq_p, tk_p = _shared_padding(8192, 1100,
                                                      bwd_tiles)
    assert (bk1, bk2) == (1024, 1024)
    assert tk_p == 2048, tk_p  # not 281600
    # another mixed-lcm case: 1280 used to pad to lcm(1280,1024) = 5120
    _, _, _tq_p, tk_p = _shared_padding(8192, 1280, bwd_tiles)
    assert tk_p == 2048, tk_p
    # exactly-dividing lengths are untouched (no padding regression)
    (_, bk1), (_, bk2), _tq_p, tk_p = _shared_padding(8192, 768,
                                                      bwd_tiles)
    assert (bk1, bk2) == (768, 768) and tk_p == 768
    # q axis: equal clamped blocks never triggered the blowup
    (bq1, _), (bq2, _), tq_p, _ = _shared_padding(160, 2048, bwd_tiles)
    assert (bq1, bq2) == (160, 160) and tq_p == 160


def test_pallas_backward_adversarial_tk_matches_scan(monkeypatch):
    """End-to-end regression at the adversarial length: default
    (mixed) backward tiles at tk=1100 run the clamped padding path and
    the grads still match the scan recompute."""
    b, t, h, d = 1, 1100, 1, 8
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    ct = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)

    def loss(q, k, v):
        # no explicit blocks: the per-phase default tiles are what
        # produce the mixed (2048, 1024) k-axis pair under clamping
        o = flash_attention(q, k, v, causal=True)
        return jnp.sum(o * ct)

    monkeypatch.setenv('PADDLE_TPU_FLASH_BWD_SCAN', '1')
    jax.clear_caches()
    g_scan = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.delenv('PADDLE_TPU_FLASH_BWD_SCAN')
    monkeypatch.setenv('PADDLE_TPU_FLASH_BWD_PALLAS', '1')
    jax.clear_caches()
    g_pal = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.delenv('PADDLE_TPU_FLASH_BWD_PALLAS')
    jax.clear_caches()
    for a, b_, name in zip(g_scan, g_pal, 'qkv'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg='d' + name)

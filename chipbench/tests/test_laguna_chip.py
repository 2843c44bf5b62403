"""The laguna-s-2.1 configuration on the chip only, at the published
widths (skips without a TPU; the builder runs it through the chip tool:
``python3 -m pytest chipbench/tests/test_laguna_chip.py -s``):

- the cell's own comparison (``kinds/serving.py build``: weights from
  the seed, the two ``check`` requests replayed through chunked prefill
  and the paged step, the reference on the same weights) as the cell
  runs it, and with the reference told one thing the engine does not
  do: the window ignored on the sliding layers, the per-head gate left
  out, the held experts dropped, every query head over the K/V head
  after its own.  Each control has to come out NOT correct.  Each
  builds the whole served system: run them one a process (``-k
  window``, ``-k gate`` ...);
- the reference's own equations with both inputs of every matrix
  product cut to 4 mantissa bits (a scaled float8, the nearest
  precision below the stated bf16): their error against the float32
  reference has to lie ABOVE ``LOGITS_TOL``.
"""
import argparse
import functools
import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = 'laguna-s-2.1_serve_code32_chunked'


@pytest.fixture(scope='module')
def tpu():
    import jax
    if jax.devices()[0].platform != 'tpu':
        pytest.skip('runs at the published widths on a TPU')
    return jax.devices()[0]


@pytest.fixture(scope='module')
def config():
    with open(os.path.join(HERE, '..', 'configs', 'laguna-s-2.1.json')) as f:
        return json.load(f)


def _no_window(plain):
    def attention(p, n, u, pos, kind, spec, **kw):
        return plain(p, n, u, pos, kind, dict(spec, window=1 << 30), **kw)
    return attention


# what the reference is told, against what the engine runs: a function
# of the reference to swap, and what takes its place
CONTROLS = {
    'as_it_is': None,
    'window_ignored': ('attention', _no_window),
    'gate_left_out': ('attention',
                      lambda plain: functools.partial(plain, gated=False)),
    'held_experts_dropped': (
        'ffn', lambda plain: functools.partial(plain, with_held=False)),
    'kv_head_shifted_by_one': (
        'attention', lambda plain: functools.partial(plain, kv_shift=1)),
}


def compared(rehearse, control, seed=3000005101):
    """``kinds/serving.py build`` of the cell (the comparison that
    decides ``correct``) -> (why, the errors it printed), with the
    reference changed as ``control`` says."""
    from chipbench import harness
    from chipbench.kinds import serving
    from chipbench.reference import laguna as ref
    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    cell = next(w for w in bench['workloads'] if w['name'] == CELL)
    run = harness.Run(argparse.Namespace(
        seed=seed, seconds=40.0, trace=0, rehearse=rehearse), bench, cell)
    run.claim_device()
    said, info = [], harness.info
    name, swap = control or ('ffn', lambda plain: plain)
    plain = getattr(ref, name)
    setattr(ref, name, swap(plain))
    harness.info = lambda tag, what: (said.append((tag, what)),
                                      info(tag, what))
    try:
        served, why = serving.build(run)
    finally:
        setattr(ref, name, plain)
        harness.info = info
    served.close()
    return why, dict(said)['REFERENCE']['logits_rel_err']


@pytest.mark.parametrize('control', sorted(CONTROLS))
def test_the_cells_comparison_sees_what_the_model_is_made_of(tpu, control):
    from chipbench.reference import laguna as ref
    why, errs = compared(False, CONTROLS[control])
    print('CONTROL', json.dumps({'control': control, 'logits_rel_err': errs,
                                 'tol': ref.LOGITS_TOL, 'why': why}))
    if CONTROLS[control] is None:
        assert why == [] and max(errs) <= ref.LOGITS_TOL
    elif control == 'window_ignored':
        # the 96-token request never leaves its window: the long one
        assert why and errs[1] > 2 * ref.LOGITS_TOL
    else:
        assert why and min(errs) > 2 * ref.LOGITS_TOL


def cut(a, bits=4):
    """``a`` with each value cut to ``bits`` explicit mantissa bits."""
    import jax.numpy as jnp
    m, e = jnp.frexp(a.astype(jnp.float32))
    return jnp.ldexp(jnp.round(m * (1 << (bits + 1))) / (1 << (bits + 1)), e)


def seeded_params(c, seed):
    """The cell's weights as the system file seeds them, without the
    engine: the startup program and ``decide_held``."""
    import paddle_tpu as fluid
    from chipbench.systems.laguna_serve import decide_held, spec_of
    from paddle_tpu.inference.decode import extract_params
    from paddle_tpu.inference.blocks import LagunaBlock
    from paddle_tpu.models import laguna
    spec, a = spec_of(c), c['assumed']
    block = LagunaBlock(spec['heads'], spec['kv_heads'], c['head_dim'],
                        spec['kinds'], spec['window'], spec['rope'])
    scope = fluid.Scope()
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = seed % (2 ** 31 - 1) + 1
    with fluid.program_guard(main_p, startup):
        laguna.build_logits(
            vocab_size=c['vocab_size'], heads=spec['heads'],
            n_kv_heads=spec['kv_heads'], head_dim=c['head_dim'],
            d_model=c['hidden_size'], dense_size=c['intermediate_size'],
            router_width=c['router_width'], n_experts=c['num_experts'],
            expert_size=c['moe_intermediate_size'],
            shared_size=c['shared_expert_intermediate_size'],
            dtype=c['dtype'], init_std=a['init_std'],
            gate_init_std=a['gate_init_std'],
            dense_init_std=a['dense_init_std'],
            expert_init_std=a['expert_init_std'],
            shared_init_std=a['shared_init_std'],
            router_init_std=a['router_init_std'],
            embed_init_std=a['embed_init_std'])
    fluid.Executor(fluid.TPUPlace(0)).run(startup, scope=scope)
    return decide_held(extract_params(scope, c['num_hidden_layers'], block),
                       c, seed), spec


def test_one_precision_lower_is_not_correct(tpu, config):
    import jax
    import jax.numpy as jnp
    from chipbench.reference import laguna as ref
    c, readings, rms = config, [], None
    for seed in (1, 2, 3):
        p, spec = seeded_params(c, 3000005200 + seed)
        shape = dict(n_layers=c['num_hidden_layers'], n_heads=spec)
        exact = jax.jit(functools.partial(ref.branches, **{
            'n_layers': shape['n_layers'], 'spec': spec}))

        @jax.jit
        def low(p, seq):
            # a function of its own (jit keys its traces on the
            # function), traced while every product's inputs are cut
            plain = ref._mm
            ref._mm = lambda a, b: jnp.matmul(cut(a), cut(b))
            try:
                return ref.logits(p, seq, **shape)
            finally:
                ref._mm = plain
        for n in (96, 1500):
            rng = np.random.default_rng(seed * 10 + n)
            seq = np.zeros((2048,), np.int32)
            seq[:n + 6] = rng.integers(1, c['vocab_size'], n + 6)
            full, rms, _routed = exact(p, jnp.asarray(seq))
            want = np.asarray(full)[n - 1:n + 5]
            got = np.asarray(low(p, jnp.asarray(seq)))[n - 1:n + 5]
            readings.append(float(np.max(np.abs(got - want))
                                  / np.max(np.abs(want))))
        del p
    print('ONE_PRECISION_LOWER', json.dumps(
        {'rel_err_4_mantissa_bits': readings, 'tol': ref.LOGITS_TOL,
         'rms_stream_attention_ffn_by_layer': np.asarray(rms).tolist()}))
    assert min(readings) > ref.LOGITS_TOL

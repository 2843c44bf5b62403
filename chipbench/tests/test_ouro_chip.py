"""The ouro-2.6b configuration on the chip only, at the published widths
(skips without a TPU; the builder runs it through the chip tool:
``python3 -m pytest chipbench/tests/test_ouro_chip.py -s``):

- the cell's own comparison (``kinds/serving.py build``: weights from
  the seed, the two ``check`` requests replayed through chunked prefill
  and the paged step, the reference on the same weights) as the cell
  runs it, and with one thing wrong on one side: the reference told
  that the stack runs once and not four times, that no norm closes a
  recurrence before the next, that a branch has no norm on its way out;
  the ENGINE made to write and read every recurrence in recurrence 0's
  cache slots.  Each control has to come out NOT correct.  Each builds
  the whole served system: run them one a process (``-k one``, ...);
- the reference's own equations with both inputs of every matrix
  product cut to 4 mantissa bits (a scaled float8, the nearest
  precision below the stated bf16): their error against the float32
  reference has to lie ABOVE ``LOGITS_TOL``.
"""
import argparse
import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = 'ouro-2.6b_serve_reason16_chunked'


@pytest.fixture(scope='module')
def tpu():
    import jax
    if jax.devices()[0].platform != 'tpu':
        pytest.skip('runs at the published widths on a TPU')
    return jax.devices()[0]


@pytest.fixture(scope='module')
def config():
    with open(os.path.join(HERE, '..', 'configs', 'ouro-2.6b.json')) as f:
        return json.load(f)


def _reference():
    from chipbench.reference import ouro
    return ouro


def _cache():
    from paddle_tpu.inference.decode import PagedKVCache
    return PagedKVCache


# one thing wrong, on one side of the comparison: where the function
# lives, its name, and what takes its place
CONTROLS = {
    'as_it_is': None,
    'one_recurrence': (_reference, 'ut_steps', lambda plain: lambda spec: 1),
    'no_closing_norm_between': (
        _reference, 'close',
        lambda plain: lambda x, w, last: plain(x, w, last) if last else x),
    'no_out_norms': (_reference, 'out_norm',
                     lambda plain: lambda y, w: y),
    'slots_shared': (
        _cache, 'shift',
        lambda plain: lambda self, pages, t: [p + 0 * t for p in pages]),
}


def compared(rehearse, control, seed=3000005611):
    """``kinds/serving.py build`` of the cell (the comparison that
    decides ``correct``) -> (why, the errors it printed), with one
    function swapped as ``control`` says."""
    from chipbench import harness
    from chipbench.kinds import serving
    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    cell = next(w for w in bench['workloads'] if w['name'] == CELL)
    run = harness.Run(argparse.Namespace(
        seed=seed, seconds=40.0, trace=0, rehearse=rehearse), bench, cell)
    run.claim_device()
    said, info = [], harness.info
    where, name, swap = control or (_reference, 'close', lambda plain: plain)
    plain = getattr(where(), name)
    setattr(where(), name, swap(plain))
    harness.info = lambda tag, what: (said.append((tag, what)),
                                      info(tag, what))
    try:
        served, why = serving.build(run)
    finally:
        setattr(where(), name, plain)
        harness.info = info
    served.close()
    return why, dict(said)['REFERENCE']['logits_rel_err']


@pytest.mark.parametrize('control', sorted(CONTROLS))
def test_the_cells_comparison_sees_what_the_model_is_made_of(tpu, control):
    ref = _reference()
    why, errs = compared(False, CONTROLS[control])
    print('CONTROL', json.dumps({'control': control, 'logits_rel_err': errs,
                                 'tol': ref.LOGITS_TOL, 'why': why}))
    if CONTROLS[control] is None:
        assert why == [] and max(errs) <= ref.LOGITS_TOL
    else:
        assert why and min(errs) > 2 * ref.LOGITS_TOL


def seeded_params(c, seed):
    """The cell's weights as the system file seeds them, without the
    engine: the startup program."""
    import paddle_tpu as fluid
    from paddle_tpu.inference.blocks import OuroBlock
    from paddle_tpu.inference.decode import extract_params
    from paddle_tpu.models import ouro
    a = c['assumed']
    scope = fluid.Scope()
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = seed % (2 ** 31 - 1) + 1
    with fluid.program_guard(main_p, startup):
        ouro.build_logits(
            vocab_size=c['vocab_size'], n_layers=c['num_hidden_layers'],
            d_model=c['hidden_size'], ffn_size=c['intermediate_size'],
            dtype=c['dtype'], init_std=a['init_std'],
            embed_init_std=a['embed_init_std'],
            branch_norm_init=a['branch_norm_init'])
    fluid.Executor(fluid.TPUPlace(0)).run(startup, scope=scope)
    return extract_params(scope, c['num_hidden_layers'],
                          OuroBlock(c['num_attention_heads']))


def test_one_precision_lower_is_not_correct(tpu, config):
    import jax
    import jax.numpy as jnp
    from chipbench.tests.test_laguna_chip import cut
    ref = _reference()
    c, readings = config, []
    shape = dict(n_layers=c['num_hidden_layers'],
                 n_heads={'heads': c['num_attention_heads'],
                          'ut_steps': c['total_ut_steps']})
    exact = jax.jit(lambda p, seq: ref.logits(p, seq, **shape))

    @jax.jit
    def low(p, seq):
        # a function of its own (jit keys its traces on the function),
        # traced while every product's inputs are cut
        plain = ref._mm
        ref._mm = lambda a, b: jnp.matmul(cut(a), cut(b))
        try:
            return ref.logits(p, seq, **shape)
        finally:
            ref._mm = plain
    for seed in (1, 2, 3):
        p = seeded_params(c, 3000005620 + seed)
        for n in (96, 448):
            rng = np.random.default_rng(seed * 10 + n)
            seq = np.zeros((512,), np.int32)
            seq[:n + 6] = rng.integers(1, c['vocab_size'], n + 6)
            want = np.asarray(exact(p, jnp.asarray(seq)))[n - 1:n + 5]
            got = np.asarray(low(p, jnp.asarray(seq)))[n - 1:n + 5]
            readings.append(float(np.max(np.abs(got - want))
                                  / np.max(np.abs(want))))
        del p
    print('ONE_PRECISION_LOWER', json.dumps(
        {'rel_err_4_mantissa_bits': readings, 'tol': ref.LOGITS_TOL}))
    assert min(readings) > ref.LOGITS_TOL

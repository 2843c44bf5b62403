"""The ai21-jamba2-3b configuration on the chip only, at the published
widths (skips without a TPU; the builder runs it through the chip tool:
``python3 -m pytest chipbench/tests/test_jamba_chip.py -s``):

- the cell's own comparison (``kinds/serving.py build``: weights from
  the seed, the two ``check`` requests replayed through chunked prefill
  into a slot's state and the paged step, one after the other in ONE
  slot, the reference on the same weights) as the cell runs it, and with
  one thing wrong on one side: the ENGINE made to start every chunk from
  a zero state (the state not carried from one chunk to the next), to
  let a bucket's padding rows advance the state, to read whatever a
  slot holds at position 0 (the second request then starts from the
  first's state), to drop the convolution's carried inputs; the
  REFERENCE told that dt, B and C have no norms, and that the two
  attention layers turn q and k by rotary positions.  Each control has
  to come out NOT correct.  Each builds the whole served system: run
  them one a process (``-k as_it_is``, ...);
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
CELL = 'ai21-jamba2-3b_serve_longdoc64_chunked'


@pytest.fixture(scope='module')
def tpu():
    import jax
    if jax.devices()[0].platform != 'tpu':
        pytest.skip('runs at the published widths on a TPU')
    return jax.devices()[0]


@pytest.fixture(scope='module')
def config():
    with open(os.path.join(HERE, '..', 'configs',
                           'ai21-jamba2-3b.json')) as f:
        return json.load(f)


def _reference():
    from chipbench.reference import jamba
    return jamba


def _engine():
    from paddle_tpu.inference.decode import DecodeEngine
    return DecodeEngine


def _block():
    from paddle_tpu.inference.blocks import JambaBlock
    return JambaBlock


def rotary(plain):
    def positional(q, k, pos):
        from paddle_tpu.ops.moe import rotary_math
        return rotary_math(q, pos, 1e4), rotary_math(k, pos, 1e4)
    return positional


def no_norm(plain):
    return lambda x, w: x


# one thing wrong, on one side of the comparison: where the functions
# live, and for each name what takes its place
CONTROLS = {
    'as_it_is': None,
    'state_not_carried_between_chunks': (
        _engine, {'_from_zero': lambda plain: lambda self, pos0: pos0 >= 0}),
    'padding_rows_advance_the_state': (
        _block, {'seq_valid': lambda plain: lambda self, n_valid, rows: rows}),
    'a_slots_old_state_read_at_position_zero': (
        _engine, {'_from_zero': lambda plain: lambda self, pos0: pos0 < 0}),
    'carried_inputs_of_the_convolution_dropped': (
        _block, {'carried': lambda plain: lambda self, c: 0.0 * c}),
    'no_dt_b_c_norms': (
        _reference, {'dt_norm': no_norm, 'b_norm': no_norm,
                     'c_norm': no_norm}),
    'attention_given_rotary': (_reference, {'positional': rotary}),
}


def compared(rehearse, control, seed=3000005911):
    """``kinds/serving.py build`` of the cell (the comparison that
    decides ``correct``) -> (why, the errors it printed), with the
    functions ``control`` names swapped."""
    from chipbench import harness
    from chipbench.kinds import serving
    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    cell = next(w for w in bench['workloads'] if w['name'] == CELL)
    run = harness.Run(argparse.Namespace(
        seed=seed, seconds=40.0, trace=0, rehearse=rehearse), bench, cell)
    run.claim_device()
    said, info = [], harness.info
    where, swaps = control or (_reference, {})
    plain = {name: getattr(where(), name) for name in swaps}
    for name, swap in swaps.items():
        setattr(where(), name, swap(plain[name]))
    harness.info = lambda tag, what: (said.append((tag, what)),
                                      info(tag, what))
    try:
        served, why = serving.build(run)
    finally:
        for name, fn in plain.items():
            setattr(where(), name, fn)
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
        # (a slot's old state can only show in the second request, a
        # lost carry only in the one of several chunks)
        assert why and max(errs) > 2 * ref.LOGITS_TOL


def test_one_precision_lower_is_not_correct(tpu, config):
    import jax
    import jax.numpy as jnp
    from chipbench.systems.jamba_serve import seeded_params, spec_of
    from chipbench.tests.test_laguna_chip import cut
    import paddle_tpu as fluid
    ref = _reference()
    c, readings = config, []
    shape = dict(n_layers=c['num_hidden_layers'], n_heads=spec_of(c))
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
        p = seeded_params(c, 3000005920 + seed, fluid.TPUPlace(0))
        for n in (300, 1300):
            rng = np.random.default_rng(seed * 10 + n)
            seq = np.zeros((2048,), np.int32)
            seq[:n + 6] = rng.integers(1, c['vocab_size'], n + 6)
            want = np.asarray(exact(p, jnp.asarray(seq)))[n - 1:n + 5]
            got = np.asarray(low(p, jnp.asarray(seq)))[n - 1:n + 5]
            readings.append(float(np.max(np.abs(got - want))
                                  / np.max(np.abs(want))))
        del p
    print('ONE_PRECISION_LOWER', json.dumps(
        {'rel_err_4_mantissa_bits': readings, 'tol': ref.LOGITS_TOL}))
    assert min(readings) > ref.LOGITS_TOL

"""The dots-vlm1 configuration on the chip only, at the published
widths (skips without a TPU; the builder runs it through the chip tool:
``python3 -m pytest chipbench/tests/test_dots_vlm_chip.py -s``):

- the live-pages latent kernel against the gathered-span math, a decode
  step's shapes (128 heads over one 640-lane row, ragged contexts);
- a chunk's attention as the block runs it (absorbed, through the
  kernel) against the expanded form of a whole-prompt prefill over the
  same gathered rows (XLA), at 256 rows against 256 / 2048 / 3840
  cached positions: the same numbers, and the times that decided the
  form (PERF.md section 6);
- the reference's own equations with both inputs of every matrix
  product cut to 4 mantissa bits (a scaled float8, the nearest
  precision below the stated bf16): their error against the float32
  reference has to lie ABOVE ``LOGITS_TOL``;
- the cell's own comparison (``kinds/serving.py build``: weights from
  the seed, the two ``check`` requests replayed through the engine,
  the reference on the same weights) with the reference told that the
  held experts are other experts than the engine runs: not correct.
  Each control builds the whole served system: run them one a process
  (``-k dropped``, then ``-k shifted``).
"""
import argparse
import functools
import json
import os
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope='module')
def tpu():
    import jax
    if jax.devices()[0].platform != 'tpu':
        pytest.skip('runs at the published widths on a TPU')
    return jax.devices()[0]


@pytest.fixture(scope='module')
def config():
    with open(os.path.join(HERE, '..', 'configs', 'dots-vlm1.json')) as f:
        return json.load(f)


CELL = 'dots-vlm1_serve_doc64_chunked'
# what the reference is told the stacked experts are, against the
# engine's experts FIRST_EXPERT ..: None = the same (the cell as it
# runs); far outside the router = every held expert's part dropped;
# one further = each assignment computed with its neighbour's weights
CONTROLS = {'as_it_is': None, 'held_experts_dropped': 1 << 20,
            'held_experts_shifted_by_one': 1}


def compared(rehearse, first_expert, seed=3000003301):
    """``kinds/serving.py build`` of the cell (the comparison that
    decides ``correct``) -> (why, the errors it printed), with the
    reference's ``ffn`` handed ``first_expert``."""
    from chipbench import harness
    from chipbench.kinds import serving
    from chipbench.reference import dots_vlm as ref
    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    cell = next(w for w in bench['workloads'] if w['name'] == CELL)
    run = harness.Run(argparse.Namespace(
        seed=seed, seconds=40.0, trace=0, rehearse=rehearse), bench, cell)
    run.claim_device()
    plain, said = ref.ffn, []
    info = harness.info
    if first_expert is not None:
        ref.ffn = functools.partial(plain, first_expert=first_expert)
    harness.info = lambda tag, what: (said.append((tag, what)),
                                      info(tag, what))
    try:
        served, why = serving.build(run)
    finally:
        ref.ffn, harness.info = plain, info
    served.close()
    return why, dict(said)['REFERENCE']['logits_rel_err']


@pytest.mark.parametrize('control', sorted(CONTROLS))
def test_the_cells_comparison_sees_the_held_experts(tpu, control):
    from chipbench.reference import dots_vlm as ref
    why, errs = compared(False, CONTROLS[control])
    print('CONTROL', json.dumps({'control': control, 'logits_rel_err': errs,
                                 'tol': ref.LOGITS_TOL, 'why': why}))
    if CONTROLS[control] is None:
        assert why == [] and max(errs) <= ref.LOGITS_TOL
    else:
        # both requests, each by more than twice the bar
        assert why and min(errs) > 2 * ref.LOGITS_TOL


def timed(fn, *args, n=10):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def test_latent_kernel_against_the_math(tpu):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import latent_paged_attention_math
    from paddle_tpu.ops.pallas.paged_attention import latent_paged_attention
    keys = jax.random.split(jax.random.PRNGKey(32), 3)
    s, h, w, n, page, mpp = 64, 128, 640, 4097, 16, 256
    pool = jax.random.normal(keys[0], (n, page, w)).astype(jnp.bfloat16)
    q = jax.random.normal(keys[1], (s, h, w), jnp.float32) * 0.3
    pt = jax.random.permutation(keys[2], n - 1)[:16 * mpp].reshape(16, mpp)
    pt = jnp.tile(pt, (4, 1)).astype(jnp.int32)
    ctx = jnp.asarray(np.random.default_rng(0).integers(1, 4096, s),
                      jnp.int32).at[0].set(1).at[1].set(4096)
    kern = jax.jit(lambda *a: latent_paged_attention(*a, 0.135, 512))
    math = jax.jit(lambda *a: latent_paged_attention_math(
        a[0].astype(jnp.bfloat16), *a[1:], 0.135, 512))
    got, want = kern(q, pool, pt, ctx), math(q[:8], pool, pt[:8], ctx[:8])
    err = float(jnp.max(jnp.abs(got[:8] - want)) / jnp.max(jnp.abs(want)))
    ms = timed(kern, q, pool, pt, ctx)
    live = int(jnp.sum(ctx))
    print('LATENT_KERNEL', json.dumps({
        'rel_err': err, 'ms': ms, 'live_positions': live,
        'GB_per_s': live * w * 2 / ms / 1e6,
        'TFLOP_per_s': live * 2 * h * (w + 512) / ms / 1e9}))
    assert err < 2e-2


def test_chunk_forms(tpu, config):
    """256 chunk rows of one layer over the same latent pages: the
    block's ``attend_chunk`` (absorbed, kernel) and the expanded form
    over the gathered rows (XLA)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.blocks import DotsVlmBlock
    c = config
    h, rank, nope, rope, vd = (c['num_attention_heads'], c['kv_lora_rank'],
                               c['qk_nope_head_dim'],
                               c['qk_rope_head_dim'], c['v_head_dim'])
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    p = {'dots_l0_kvb_w': (jax.random.normal(
        keys[0], (rank, h * (nope + vd))) * 0.02).astype(jnp.bfloat16)}
    pool = jnp.pad(jax.random.normal(keys[1], (4097, 16, rank + rope)),
                   ((0, 0), (0, 0), (0, 64))).astype(jnp.bfloat16)
    q = jax.random.normal(keys[2], (256, h, nope + rope), jnp.float32)
    pt = jax.random.permutation(keys[3], 4096)[:256].astype(jnp.int32)
    blk = DotsVlmBlock(h, yarn={'factor': 40.0})

    def expanded(q, pool, pt, pos0):
        gathered = pool[pt].reshape(-1, pool.shape[-1])
        valid = jnp.arange(gathered.shape[0])[None, :] \
            <= (pos0 + jnp.arange(q.shape[0]))[:, None]
        return blk._attend_expanded(p, 0, q, gathered, valid)
    forms = {'absorbed': jax.jit(lambda q, pool, pt, pos0: blk.attend_chunk(
        p, 0, q, [pool], pt, pos0)), 'expanded': jax.jit(expanded)}
    rows = []
    for pos0 in (0, 1792, 3584):
        out = {}
        for form, fn in forms.items():
            out[form] = fn(q, pool, pt, jnp.int32(pos0))
            out[form + '_ms'] = timed(fn, q, pool, pt, jnp.int32(pos0))
        err = float(jnp.max(jnp.abs(out['absorbed'] - out['expanded']))
                    / jnp.max(jnp.abs(out['expanded'])))
        rows.append({'pos0': pos0, 'absorbed_ms': out['absorbed_ms'],
                     'expanded_ms': out['expanded_ms'], 'rel_diff': err})
        assert err < 3e-2
    print('CHUNK_FORMS', json.dumps(rows))


def cut(a, bits=4):
    """``a`` rounded to ``bits`` mantissa bits, exponent kept."""
    import jax.numpy as jnp
    m, ex = jnp.frexp(a.astype(jnp.float32))
    return jnp.ldexp(jnp.round(m * 2 ** bits) / 2 ** bits, ex)


def seeded_params(c, seed):
    """Weights at the configuration's widths and ``assumed`` scales,
    the held experts' choice decided as the system file decides it
    (drawn here, not by the startup program: the reading is of the
    equations, not of one seed's weights)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.dots_vlm import param_names
    a, d, h = c['assumed'], c['hidden_size'], c['num_attention_heads']
    e, f, dense = (c['n_routed_experts'], c['moe_intermediate_size'],
                   c['intermediate_size'])
    rank, rope = c['kv_lora_rank'], c['qk_rope_head_dim']
    # leaf -> (shape, std); a norm weight is ones
    attention = a['init_std']
    leaves = {
        'dots_embed': ((c['vocab_size'], d), a['embed_init_std']),
        'dots_head_w': ((d, c['vocab_size']), attention),
        'qa_w': ((d, c['q_lora_rank']), attention),
        'qb_w': ((c['q_lora_rank'],
                  h * (c['qk_nope_head_dim'] + rope)), attention),
        'kva_w': ((d, rank + rope), attention),
        'kvb_w': ((rank, h * (c['qk_nope_head_dim'] + c['v_head_dim'])),
                  attention),
        'o_w': ((h * c['v_head_dim'], d), attention),
        'router_w': ((d, c['router_width']), a['router_init_std']),
        'router_bias': ((c['router_width'],), a['router_bias_std']),
        'shared_gate_w': ((d, f), a['shared_init_std']),
        'shared_up_w': ((d, f), a['shared_init_std']),
        'shared_down_w': ((f, d), a['shared_init_std'])}
    ffn = {False: {'gate_w': (e, d, f), 'up_w': (e, d, f),
                   'down_w': (e, f, d)},
           True: {'gate_w': (d, dense), 'up_w': (d, dense),
                  'down_w': (dense, d)}}
    p = {}
    names = param_names(c['num_hidden_layers'], c['first_k_dense_replace'])
    for key, n in zip(jax.random.split(jax.random.PRNGKey(seed),
                                       len(names)), names):
        leaf = n.split('_', 2)[2] if n.startswith('dots_l') else n
        layer = int(n[6:].split('_')[0]) if n.startswith('dots_l') else -1
        if leaf in ffn[True]:
            first = layer < c['first_k_dense_replace']
            shape, std = ffn[first][leaf], a[
                'dense_init_std' if first else 'expert_init_std']
        elif leaf in leaves:
            shape, std = leaves[leaf]
        else:
            p[n] = jnp.ones((rank if leaf == 'kv_norm_w' else
                             c['q_lora_rank'] if leaf == 'q_norm_w'
                             else d,), jnp.float32)
            continue
        p[n] = (jax.random.normal(key, shape) * std).astype(
            jnp.float32 if leaf.startswith('router') else jnp.bfloat16)
    from chipbench.systems.dots_vlm_serve import decide_held
    return decide_held(p, c, seed)


def test_one_precision_lower_is_not_correct(tpu, config):
    import functools
    import jax
    import jax.numpy as jnp
    from chipbench.reference import dots_vlm as ref
    c = config
    readings = []
    shape = dict(n_layers=c['num_hidden_layers'],
                 n_heads=c['num_attention_heads'])
    exact = jax.jit(functools.partial(ref.logits, **shape))

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
        p = seeded_params(c, 3000003200 + seed)
        for n in (96, 1500):
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


# max|got - want| / max|want| of one expert layer's FFN branch with the
# routing GIVEN (the reference's own indices and weights), so that no
# rank-8/rank-9 flip can occur and what is left is the arithmetic of the
# held experts and the shared expert: bf16 inputs, f32 accumulation.
# OLMoE's comparison (C) read 2.9e-3 against a bar of 9e-3
# (test_olmoe_chip.py); the same bar here.
HELD_EXPERTS_TOL = 9e-3


def test_held_experts_with_given_routing(tpu, config):
    """The held experts' arithmetic alone, whatever the router chose:
    512 tokens through the 16 held experts (std 0.012; the choice here
    is the seeded bias's, so every one of them gets tokens) and the
    shared expert of one layer at the published widths."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import dots_vlm as ref
    from paddle_tpu.ops.moe import moe_experts
    c = config
    d, f, e = c['hidden_size'], c['moe_intermediate_size'], \
        c['n_routed_experts']
    keys = jax.random.split(jax.random.PRNGKey(3000003230), 9)
    bf16 = jnp.bfloat16
    normal = lambda k, shape, std, dt=bf16: (
        jax.random.normal(keys[k], shape) * std).astype(dt)
    n = 'dots_l1_'
    p = {n + 'router_w': normal(0, (d, c['router_width']), 0.02,
                                jnp.float32),
         n + 'router_bias': normal(1, (c['router_width'],), 0.05,
                                   jnp.float32),
         n + 'gate_w': normal(2, (e, d, f), 0.012),
         n + 'up_w': normal(3, (e, d, f), 0.012),
         n + 'down_w': normal(4, (e, f, d), 0.012),
         n + 'shared_gate_w': normal(5, (d, f), 0.012),
         n + 'shared_up_w': normal(6, (d, f), 0.012),
         n + 'shared_down_w': normal(7, (f, d), 0.012)}
    h = jax.random.normal(keys[8], (512, d), jnp.float32)

    @jax.jit
    def reference(p, h):
        with jax.default_matmul_precision('highest'):
            return ref.ffn(p, n, h)

    want, (w, idx, _s) = reference(p, h)
    system = jax.jit(lambda p, h: moe_experts(
        h, w, idx, p[n + 'gate_w'], p[n + 'up_w'], p[n + 'down_w'],
        first=ref.FIRST_EXPERT,
        shared=tuple(p[n + 'shared_%s_w' % s]
                     for s in ('gate', 'up', 'down'))))
    rel = lambda a: float(jnp.max(jnp.abs(a - want))
                          / jnp.max(jnp.abs(want)))
    err = rel(system(p, h))
    low = rel(system({k: v if 'router' in k else cut(v).astype(v.dtype)
                      for k, v in p.items()}, cut(h)))
    held = int(jnp.sum(idx < e))
    print('HELD_EXPERTS_GIVEN_ROUTING', json.dumps(
        {'rel_err': err, 'rel_err_4_mantissa_bits': low,
         'tol': HELD_EXPERTS_TOL, 'held_assignments': held,
         'of': int(idx.size)}))
    assert err <= HELD_EXPERTS_TOL < low and held > 0

"""OLMoE through DecodeEngine / DecodeServer against the plain reference
(tests/reference_olmoe.py, the copy of chipbench/reference/olmoe.py), on
the CPU at toy widths: hidden 64, 4 heads of 16, 16 experts of width 32,
8 (and 2) a token, 2 layers, page 8.  Every comparison is on LOGITS.

TOL_A is comparison (A) of the configuration: both sides are true
float32 here, so what is left is the order of summation (the system
weights the gated product and contracts over experts and width at
once; the reference sums whole expert outputs): measured 0.4e-6 to
1.3e-6,
the bar is 2e-5, as loose as OPT's 5e-7 measured / 1e-4 asserted allows.
This is where the mathematics is proven; the chip comparisons (B), (C)
carry the looser bars bf16 needs (chipbench/reference/olmoe.py,
chipbench/tests/test_olmoe_chip.py).

The engine serves every decoder through a block description, so what
holds of one description and not of the other is tested here side by
side: ``OptBlock`` (the engine's default) against tests/reference_opt.py
in the wrong-block cases, in how the weights enter each program, and in
what its spans carry.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.inference.blocks import OlmoeBlock, OptBlock
from paddle_tpu.inference.decode import (DecodeEngine, DecodeServer,
                                         extract_params)
from paddle_tpu.models import olmoe, transformer
from paddle_tpu.observability import timeline
from paddle_tpu.ops.moe import rms_norm_math

import reference_olmoe as ref
import reference_opt

TOL_A = 2e-5
V, L, D, H, E, F = 211, 2, 64, 4, 16, 32
PAGE, STREAMS, MAX_SEQ = 8, 4, 64
SHAPES = {'in_norm_w': (D,), 'q_w': (D, D), 'k_w': (D, D), 'v_w': (D, D),
          'q_norm_w': (D,), 'k_norm_w': (D,), 'o_w': (D, D),
          'post_norm_w': (D,), 'router_w': (D, E), 'gate_w': (E, D, F),
          'up_w': (E, D, F), 'down_w': (E, F, D)}


def make_params(seed=0, dtype=jnp.float32):
    """Seeded weights whose expert branch is about as large as the
    attention branch (std 0.3 for the experts, 0.12 elsewhere), norm
    weights around 1 so that a dropped norm weight shows."""
    rng, p = np.random.default_rng(seed), {}
    for n in olmoe.param_names(L):
        shape = {'olmoe_embed': (V, D), 'olmoe_head_w': (D, V),
                 'olmoe_norm_f_w': (D,)}.get(n) or SHAPES[n.split('_', 2)[2]]
        if len(shape) == 1:
            p[n] = jnp.asarray(1 + 0.1 * rng.normal(size=shape),
                               jnp.float32)
        else:
            std = 0.3 if len(shape) == 3 else 0.12
            p[n] = jnp.asarray(rng.normal(size=shape) * std, dtype)
    return p


def make_opt_params(seed=0):
    """Seeded OPT weights at the same toy widths (a position table of
    MAX_SEQ rows, FFN of 4 D): matrices at std 0.12, biases at 0.1 and
    LayerNorm weights around 1, so that a dropped bias or a norm in the
    wrong place shows (the startup program's biases are zero)."""
    rng, p = np.random.default_rng(seed), {}
    shapes = {'tr_embed': (V, D), 'tr_pos': (MAX_SEQ, D),
              'tr_head_w': (D, V), 'tr_head_b': (V,), 'qkv_w': (D, 3 * D),
              'qkv_b': (3 * D,), 'proj_w': (D, D), 'ffn_up_w': (D, 4 * D),
              'ffn_up_b': (4 * D,), 'ffn_down_w': (4 * D, D)}
    for n in transformer.param_names(L):
        shape = shapes.get(n) or shapes.get(n.split('_', 2)[2], (D,))
        if '_ln_' in n and n.endswith('_w'):
            w = 1 + 0.1 * rng.normal(size=shape)
        else:
            w = rng.normal(size=shape) * (0.12 if len(shape) == 2 else 0.1)
        p[n] = jnp.asarray(w, jnp.float32)
    return p


def make_engine(p, block=None, top=32, dtype=jnp.float32, **kw):
    kw.setdefault('prefix_cache', False)
    kw.setdefault('prefill_chunk_tokens', 0)
    return DecodeEngine(p, n_layers=L, n_heads=H, page_size=PAGE,
                        num_pages=40, max_streams=STREAMS,
                        prefill_bucket=top, max_seq=MAX_SEQ, dtype=dtype,
                        block=block or OlmoeBlock(H), **kw)


def ref_logits(p, seq, top_k=8):
    return np.asarray(ref.logits(p, jnp.asarray(seq, jnp.int32), L, H,
                                 top_k=top_k))


def ref_opt_logits(p, seq):
    return np.asarray(reference_opt.forward(
        p, jnp.asarray([seq], jnp.int32), L, H)[0])[0]


def rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def one_slot(eng, slot, tok, pages, ctx):
    """The step inputs with one running slot."""
    pt = np.full((STREAMS, eng.pages_per_stream), eng.cache.trash, np.int32)
    pt[slot, :len(pages)] = pages
    t, c = np.zeros(STREAMS, np.int32), np.zeros(STREAMS, np.int32)
    t[slot], c[slot] = tok, ctx
    return t, pt, c


def decode(eng, prompt, n_new, slot=1, prefill=None):
    """Prefill then ``n_new - 1`` greedy steps through the pages: the
    logits of every position produced, and the whole sequence."""
    pages = eng.cache.alloc(-(-(len(prompt) + n_new) // PAGE))
    rows = [(prefill or eng.prefill_into)(prompt, pages)]
    seq = list(prompt)
    for _ in range(n_new - 1):
        seq.append(int(np.argmax(rows[-1])))
        rows.append(eng.step(*one_slot(eng, slot, seq[-1], pages,
                                       len(seq) - 1))[1][slot])
    eng.cache.free(pages)
    return np.stack(rows), seq


@pytest.fixture(scope='module')
def params():
    return make_params(0)


@pytest.fixture(scope='module')
def engine(params):
    eng = make_engine(params)
    eng.warmup()
    return eng


@pytest.fixture
def ring(monkeypatch):
    monkeypatch.delenv('PADDLE_TPU_TRACE_DIR', raising=False)
    timeline.reset()
    yield timeline.ring()
    timeline.reset()


def spans(ring, name):
    return [e for e in ring.events(cat='span') if e['name'] == name]


# 1 -------------------------------------------------------------------------

@pytest.mark.parametrize('n', [5, 8, 13, 16, 27, 32])
def test_prefill_logits_of_every_bucket(params, engine, n):
    prompt = np.random.default_rng(n).integers(1, V, n)
    pages = engine.cache.alloc(-(-n // PAGE))
    got = engine.prefill_into(prompt, pages)
    engine.cache.free(pages)
    assert rel(got, ref_logits(params, prompt)[-1]) < TOL_A
    assert engine.compiles_after_warmup == 0


# 2 -------------------------------------------------------------------------

@pytest.mark.parametrize('top_k', [8, 2])
def test_prefill_then_decode_through_the_pages(params, engine, top_k):
    eng = engine if top_k == 8 else make_engine(
        params, OlmoeBlock(H, top_k=top_k))
    prompt = np.random.default_rng(1).integers(1, V, 13)
    got, seq = decode(eng, prompt, 13)       # positions 12..24: page
    want = ref_logits(params, seq, top_k)    # edges at 16 and 24
    assert rel(got, want[len(prompt) - 1:]) < TOL_A


# 3 -------------------------------------------------------------------------

def test_chunked_prefill_with_the_prefix_cache(params):
    eng = make_engine(params, prefix_cache=True, prefill_chunk_tokens=PAGE)
    rng = np.random.default_rng(3)
    shared = rng.integers(1, V, 24)
    prompts = [np.concatenate([shared, rng.integers(1, V, n)])
               for n in (5, 9)]

    def run(prompt):
        """The server's own admission, by hand: match, prefill the
        tail chunks, publish.  Returns (first logits, cached tokens)."""
        pages, nodes = eng.prefix.match(prompt)
        m = (min(len(pages) * PAGE, len(prompt) - 1)
             // eng.chunk_grid) * eng.chunk_grid
        eng.prefix.release(nodes[m // PAGE:])
        own = eng.cache.alloc(-(-len(prompt) // PAGE) - m // PAGE)
        table = pages[:m // PAGE] + own
        for lo, hi in eng.chunk_spans(len(prompt), m):
            logits = eng.prefill_chunk(prompt[lo:hi], table, lo)
        eng.prefix.insert(prompt, table)
        return logits, m

    cold, m0 = run(prompts[0])
    assert m0 == 0
    assert rel(cold, ref_logits(params, prompts[0])[-1]) < TOL_A
    warm, m1 = run(prompts[1])                  # hits the shared pages
    assert m1 == 24
    assert rel(warm, ref_logits(params, prompts[1])[-1]) < TOL_A
    again, m2 = run(prompts[0])                 # a hit, against the cold
    assert m2 >= 24
    assert np.array_equal(again, cold)          # run: bit-equal


@pytest.mark.parametrize('n', [5, 16, 27])
def test_chunked_prefill_without_the_prefix_cache(params, n):
    """The benchmark cell's path: every prompt goes through
    ``prefill_chunk`` on the grid anchored at position 0 (no prefix
    cache), then decodes through the pages."""
    eng = make_engine(params, prefill_chunk_tokens=PAGE)
    assert eng.chunked and eng.prefix is None
    prompt = np.random.default_rng(30 + n).integers(1, V, n)

    def chunks(prompt, pages):
        for lo, hi in eng.chunk_spans(len(prompt)):
            logits = eng.prefill_chunk(prompt[lo:hi], pages, lo)
        return logits

    rows, seq = decode(eng, prompt, 4, prefill=chunks)
    want = ref_logits(params, seq)[n - 1:]
    assert rel(rows, want) < TOL_A
    again, seq2 = decode(eng, prompt, 4, slot=2, prefill=chunks)
    assert seq2 == seq and np.array_equal(again, rows)


# 4 -------------------------------------------------------------------------

def test_two_streams_in_one_step_equal_each_alone(params, engine, ring):
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, V, 7), rng.integers(1, V, 19)]
    pages = [engine.cache.alloc(4) for _ in prompts]
    first = [engine.prefill_into(p, pg) for p, pg in zip(prompts, pages)]
    toks = [int(np.argmax(f)) for f in first]
    alone = []
    for slot in (0, 2):
        i = slot // 2
        ring.clear()
        alone.append(engine.step(*one_slot(
            engine, slot, toks[i], pages[i], len(prompts[i])))[1][slot])
        # one running slot: 8 assignments a layer, the other three slots
        # (all-trash page tables) are not counted
        args = spans(ring, 'decode.step')[-1]['args']
        assert args['moe_assignments'] == 8 * L
        assert args['moe_touched'] == 8.0 and args['moe_max_load'] == 1
    t, pt, c = one_slot(engine, 0, toks[0], pages[0], len(prompts[0]))
    t2, pt2, c2 = one_slot(engine, 2, toks[1], pages[1], len(prompts[1]))
    both = engine.step(t + t2, np.where(pt2 != engine.cache.trash, pt2, pt),
                       c + c2)[1]
    # (the steps above rewrote the same rows with the same values)
    for slot, want, p in zip((0, 2), alone, prompts):
        assert rel(both[slot], want) < 1e-6
        full = ref_logits(params, list(p) + [toks[slot // 2]])
        assert rel(both[slot], full[-1]) < TOL_A
    for pg in pages:
        engine.cache.free(pg)


# 5 -------------------------------------------------------------------------

@pytest.mark.parametrize('skew', ['all_pick_the_same_8', 'one_gets_none'])
def test_extreme_skew_is_exact_and_counted(skew, ring):
    p = make_params(5)
    # the router has no bias, so a fixed preference needs an input
    # coordinate of fixed sign: every token's embedding gets a large
    # positive first coordinate, which the residual stream keeps
    p['olmoe_embed'] = p['olmoe_embed'].at[:, 0].set(3.0)
    for i in range(L):
        w = np.asarray(p['olmoe_l%d_router_w' % i]).copy()
        if skew == 'all_pick_the_same_8':
            w[:] = 0.0
            w[0, :8], w[0, 8:] = 50.0, -50.0    # experts 0..7 always win
        else:
            w[0, 3] = -80.0                     # expert 3 never does
        p['olmoe_l%d_router_w' % i] = jnp.asarray(w)
    eng = make_engine(p)
    prompt = np.random.default_rng(6).integers(1, V, 21)
    ring.clear()
    got, seq = decode(eng, prompt, 4)
    assert rel(got, ref_logits(p, seq)[len(prompt) - 1:]) < TOL_A
    pre = spans(ring, 'decode.prefill_into')[-1]['args']
    assert pre['moe_assignments'] == 8 * len(prompt) * L
    for e in spans(ring, 'decode.step'):
        assert e['args']['moe_assignments'] == 8 * L
    probs = np.asarray(ref.branches(p, jnp.asarray(prompt, jnp.int32),
                                    L, H)[3])
    chosen = np.argsort(-probs, axis=-1)[..., :8]
    if skew == 'all_pick_the_same_8':
        assert set(chosen[0].ravel()) == set(range(8))
        assert pre['moe_max_load'] == len(prompt)
    else:
        assert 3 not in set(chosen.ravel())
        assert pre['moe_touched'] <= E - 1


# 6 -------------------------------------------------------------------------

class _InterleavedRotation(OlmoeBlock):
    """(2j, 2j+1) pairing in place of (j, j + Dh/2)."""

    def rotate(self, u, positions):
        dh = u.shape[-1]
        perm = np.concatenate([np.arange(0, dh, 2), np.arange(1, dh, 2)])
        inv = np.argsort(perm)
        return OlmoeBlock.rotate(self, u[..., perm], positions)[..., inv]


class _PerHeadQKNorm(OlmoeBlock):
    def qk_norm(self, u, w):
        t = u.shape[0]
        return rms_norm_math(u.reshape(t, self.n_heads, -1),
                             w.reshape(self.n_heads, -1),
                             self.eps).reshape(u.shape)


class _NoRotation(OlmoeBlock):
    def rotate(self, u, positions):
        return u.astype(jnp.float32)


class _NoPositionRow(OptBlock):
    def embed(self, p, tokens, positions):
        return p['tr_embed'][tokens]


class _PostLN(OptBlock):
    """Each LayerNorm after its residual add in place of before its
    branch."""

    def qkv(self, p, x, i, positions):
        n = 'tr_l%d_' % i
        q, k, v = jnp.split(x @ p[n + 'qkv_w'] + p[n + 'qkv_b'], 3, axis=-1)
        return q.reshape(x.shape[0], self.n_heads, -1), k, v

    def after_attention(self, p, x, ctx, i, active):
        n = 'tr_l%d_' % i
        x = self.norm(
            x + ctx.reshape(x.shape) @ p[n + 'proj_w'] + p[n + 'proj_b'],
            p[n + 'ln_attn_w'], p[n + 'ln_attn_b'])
        h = self.activation(x @ p[n + 'ffn_up_w'] + p[n + 'ffn_up_b'])
        return self.norm(
            x + h @ p[n + 'ffn_down_w'] + p[n + 'ffn_down_b'],
            p[n + 'ln_ffn_w'], p[n + 'ln_ffn_b']), None


class _NoHeadBias(OptBlock):
    def head(self, p, x):
        return self.norm(x, p['tr_ln_f_w'], p['tr_ln_f_b']) @ p['tr_head_w']


class _Gelu(OptBlock):
    def activation(self, h):
        return jax.nn.gelu(h)


VARIANTS = {
    'seven_experts': lambda: OlmoeBlock(H, top_k=7),
    'renormalised': lambda: OlmoeBlock(H, renormalize=True),
    'interleaved_rotation': lambda: _InterleavedRotation(H),
    'qk_norm_per_head': lambda: _PerHeadQKNorm(H),
    'no_rotation': lambda: _NoRotation(H),
}
OPT_VARIANTS = {
    'opt_no_position_row': lambda: _NoPositionRow(H),
    'opt_post_ln': lambda: _PostLN(H),
    'opt_no_head_bias': lambda: _NoHeadBias(H),
    'opt_gelu': lambda: _Gelu(H),
}


@pytest.mark.parametrize('variant', sorted(VARIANTS) + sorted(OPT_VARIANTS))
def test_a_wrong_block_moves_the_logits(params, variant):
    """Each departure from the equations is far outside (A)'s bar and
    outside the chip's (B): the checks are not blind to it."""
    if variant in OPT_VARIANTS:
        # the right description first, so that the bar means something:
        # OptBlock is inside it on the very weights each wrong one fails
        p, prompt = make_opt_params(7), \
            np.random.default_rng(7).integers(1, V, 14)
        got, seq = decode(make_engine(p, OptBlock(H), top=16), prompt, 3)
        assert rel(got, ref_opt_logits(p, seq)[len(prompt) - 1:]) < TOL_A
        got, seq = decode(make_engine(p, OPT_VARIANTS[variant](), top=16),
                          prompt, 3)
        err = rel(got, ref_opt_logits(p, seq)[len(prompt) - 1:])
        # measured 0.09 (the head's bias) to 0.98 (post-LN): all above
        # the chip's bar as well (chipbench/reference/opt.py LOGITS_TOL)
        assert err > 100 * TOL_A and err > 2e-2
        return
    if variant == 'renormalised':
        # what renormalising does depends on how far the 8 weights are
        # from summing to 1: a flat router here (they sum to ~0.5; with
        # this file's usual weights to ~0.9, and the change is 0.10)
        params = dict(params)
        for i in range(L):
            params['olmoe_l%d_router_w' % i] = \
                params['olmoe_l%d_router_w' % i] * 0.1
    eng = make_engine(params, VARIANTS[variant](), top=16)
    prompt = np.random.default_rng(7).integers(1, V, 14)
    got, seq = decode(eng, prompt, 3)
    err = rel(got, ref_logits(params, seq)[len(prompt) - 1:])
    assert err > 100 * TOL_A
    if variant in ('renormalised', 'no_rotation'):
        assert err > ref.LOGITS_TOL     # (B) must see these two as well


@pytest.mark.parametrize('fault', ['wrong_page', 'position_off_by_one'])
def test_a_wrong_cache_read_fails_the_chip_tolerance(params, engine, fault):
    rng = np.random.default_rng(8)
    prompt, other = rng.integers(1, V, 21), rng.integers(1, V, 8)
    pages, others = engine.cache.alloc(4), engine.cache.alloc(1)
    engine.prefill_into(other, others)
    tok = int(np.argmax(engine.prefill_into(prompt, pages)))
    t, pt, c = one_slot(engine, 1, tok, pages, len(prompt))
    if fault == 'wrong_page':
        # (swapping two of the request's own pages would change nothing:
        # a cached key carries its position in its rotation)
        pt[1, 1] = others[0]
    else:
        c[1] += 1
    got = engine.step(t, pt, c)[1][1]
    engine.cache.free(pages + others)
    want = ref_logits(params, list(prompt) + [tok])[-1]
    assert rel(got, want) > ref.LOGITS_TOL


# 7 -------------------------------------------------------------------------

def test_bf16_weights_and_pools():
    """The published precision: bf16 weights and pools, bf16 matmul
    inputs, f32 accumulation, against the reference on the SAME bf16
    values.  The system rounds each matmul's activations to bf16 (2^-9
    relative), the reference does not: at these widths (64, where
    little averages out) that is 4e-3 to 1.5e-2 of the logits' scale a
    position.  With 16 experts the 8th and 9th router scores lie within
    that rounding for a few tokens in a hundred; the system then takes
    another 8th expert than the reference, legitimately, and with the
    expert branch 5 x the attention branch here that one position moves
    by 0.03-0.15 (seed 11 has one).  So the bar is on the MEDIAN position
    (2e-2: f32 against bf16 arithmetic, measured 6.6e-3 and 1.06e-2),
    and every position stays under 0.25, where a wrong page or a missing
    rotation (0.3-1.4) does not."""
    for seed in (11, 12):
        p = make_params(seed, jnp.bfloat16)
        eng = make_engine(p, dtype=jnp.bfloat16, top=16)
        assert eng.cache.k[0].dtype == jnp.bfloat16
        prompt = np.random.default_rng(seed).integers(1, V, 11)
        got, seq = decode(eng, prompt, 8)
        want = ref_logits(p, seq)[len(prompt) - 1:]
        per = np.max(np.abs(got - want), axis=1) / np.max(np.abs(want))
        assert 1e-4 < np.median(per) < 2e-2, per
        assert per.max() < 0.25, per


# 8 -------------------------------------------------------------------------

def test_build_logits_through_the_executor(engine):
    scope = fluid.Scope()
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 7
    T = 24
    with fluid.program_guard(main_p, startup):
        src, logits, counts = olmoe.build_logits(
            V, T, L, D, H, E, F, 8, init_std=0.05, expert_init_std=0.2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    toks = np.random.default_rng(9).integers(1, V, (2, T)).astype(np.int64)
    out = exe.run(main_p, feed={'src': toks}, fetch_list=[logits] + counts,
                  scope=scope)
    block = OlmoeBlock(H)
    p = extract_params(scope, L, block)
    assert sorted(p) == sorted(olmoe.param_names(L))
    for b in range(2):
        assert rel(out[0][b], ref_logits(p, toks[b])) < TOL_A
    assert all(int(c.sum()) == 2 * T * 8 for c in out[1:])
    # and the engine on the same scope's parameters
    eng = make_engine(p, block, top=16)
    got, seq = decode(eng, toks[0][:10], 4)
    assert rel(got, ref_logits(p, seq)[9:]) < TOL_A
    assert rel(got[0], out[0][0][9]) < TOL_A


# 9 -------------------------------------------------------------------------

def _weight_shaped_constants(compiled, params):
    """The constants of a weight matrix's shape in a program's text."""
    shapes = {tuple(v.shape) for v in params.values() if v.ndim > 1}
    found = []
    for line in compiled.as_text().splitlines():
        if 'constant(' in line:
            dims = re.search(r'= \w+\[([\d,]*)\]', line)
            shape = tuple(int(d) for d in dims.group(1).split(',')
                          if d) if dims else ()
            if shape in shapes:
                found.append(line)
    return found


@pytest.mark.parametrize('model', ['olmoe', 'opt'])
def test_how_the_weights_enter_each_program(params, ring, model):
    """Under every description the weights are an operand of step, chunk
    and prefill (``argument_bytes`` counts them), no program's text
    holds a constant of a weight's shape, and a compiled step computes
    with the weights it is handed.  ``pack`` reads none."""
    if model == 'opt':
        params, other, block = make_opt_params(0), make_opt_params(21), \
            OptBlock(H)
        reference = ref_opt_logits
    else:
        other, block, reference = make_params(21), OlmoeBlock(H), ref_logits
    ring.clear()
    eng = make_engine(params, block, top=16)
    eng.warmup()
    chunked = make_engine(params, block, top=16, prefill_chunk_tokens=PAGE)
    chunked.warmup()
    weight_bytes = sum(v.nbytes for v in params.values())
    seen = {}
    for e in spans(ring, 'decode.compile'):
        seen.setdefault(e['args']['program'], []).append(e['args'])
    assert {'step', 'prefill', 'chunk', 'pack'} == set(seen)
    for program in ('step', 'chunk', 'prefill'):
        for a in seen[program]:
            assert a['argument_bytes'] >= weight_bytes, (program, a)
    for a in seen['pack']:
        assert a['argument_bytes'] < weight_bytes
    for compiled in [eng._step, chunked._step] \
            + list(chunked._chunk.values()) + list(eng._prefill.values()):
        assert not _weight_shaped_constants(compiled, params)
    # one engine's compiled step, handed another engine's weights,
    # computes with those
    eng_b = make_engine(other, block, top=16)
    prompt = np.random.default_rng(10).integers(1, V, 12)
    pages = eng_b.cache.alloc(2)
    tok = int(np.argmax(eng_b.prefill_into(prompt, pages)))
    t, pt, c = one_slot(eng_b, 1, tok, pages, len(prompt))
    seq = list(prompt) + [tok]
    assert rel(eng_b.step(t, pt, c)[1][1], reference(other, seq)[-1]) \
        < TOL_A
    # (the pools are donated: eng_b is spent after this call)
    out = eng._step(eng_b.params, eng_b.cache.k, eng_b.cache.v, t, pt, c)
    got = np.asarray(out[2])[1]
    assert rel(got, reference(other, seq)[-1]) < TOL_A
    assert rel(got, reference(params, seq)[-1]) > 0.1


# 10 ------------------------------------------------------------------------

@pytest.mark.parametrize('chunked', [False, True])
def test_spans_and_server_stats(params, ring, chunked):
    ring.clear()
    eng = make_engine(params, top=16,
                      prefill_chunk_tokens=PAGE if chunked else 0)
    placed = spans(ring, 'decode.weights')[-1]['args']
    assert placed == {'bytes': sum(v.nbytes for v in params.values()),
                      'tensors': len(params), 'dtype': 'float32'}
    server = DecodeServer(eng)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, V, n) for n in (5, 12, 9)]
    try:
        streams = [server.submit(p, max_new_tokens=6) for p in prompts]
        for st in streams:
            st.result(timeout=120.0)
        stats = server.stats()
    finally:
        server.close()
    name = 'decode.prefill_chunk' if chunked else 'decode.prefill_into'
    pre = spans(ring, name)
    steps = [e for e in spans(ring, 'decode.step')
             if e['args'].get('moe_assignments')]
    # a chunk counts its prompt tokens and the decode rows it carries
    carried = sum(e['args'].get('step_rows', 0) for e in pre)
    assert (carried > 0) is chunked
    assert carried == stats['carried_rows']
    assert sum(e['args']['moe_assignments'] for e in pre) \
        == 8 * L * (sum(len(p) for p in prompts) + carried)
    assert all(1 <= e['args']['moe_touched'] <= E
               and e['args']['moe_max_load'] >= 1 for e in pre + steps)
    total = sum(e['args']['moe_assignments'] for e in pre + steps)
    assert stats['moe_assignments'] == total
    assert stats['moe_assignments'] \
        == 8 * L * (sum(len(p) for p in prompts) + 3 * 5)
    assert stats['moe_max_load'] == max(
        e['args']['moe_max_load'] for e in pre + steps)
    assert 8.0 <= stats['moe_touched_mean'] <= E


@pytest.mark.parametrize('chunked', [False, True])
def test_step_spans_count_kv_pages_from_the_hosts_ctx_lens(
        params, ring, chunked):
    """``kv_live_pages`` is what attention has to read (each running
    slot's pages up to and with the row written this step),
    ``kv_table_pages`` what a gather of whole page tables reads: both
    follow from the arrays the server hands the engine."""
    ring.clear()
    eng = make_engine(params, top=16,
                      prefill_chunk_tokens=PAGE if chunked else 0)
    handed, step, prefill_chunk = [], eng.step, eng.prefill_chunk

    def spy(tokens, page_tables, ctx_lens):
        handed.append((np.array(page_tables), np.array(ctx_lens)))
        return step(tokens, page_tables, ctx_lens)

    def spy_chunk(*args):
        # a chunk that carries the tick's decode step is handed the
        # step's three arrays after its own
        if len(args) > 3:
            handed.append((np.array(args[4]), np.array(args[5])))
        return prefill_chunk(*args)
    eng.step, eng.prefill_chunk = spy, spy_chunk
    server = DecodeServer(eng)
    rng = np.random.default_rng(13)
    try:
        streams = [server.submit(rng.integers(1, V, n), max_new_tokens=m)
                   for n, m in ((5, 6), (15, 4), (9, 9))]
        for st in streams:
            st.result(timeout=120.0)
        stats = server.stats()
    finally:
        server.close()
    steps = sorted(
        spans(ring, 'decode.step')
        + [e for e in spans(ring, 'decode.prefill_chunk')
           if e['args']['step_rows']], key=lambda e: e['ts'])
    assert len(steps) == len(handed) >= 8
    assert ('decode.prefill_chunk' in {e['name'] for e in steps}) \
        is chunked
    live = []
    for e, (pts, ctx) in zip(steps, handed):
        running = pts[:, 0] != eng.cache.trash
        assert running.any()
        live.append(sum(-(-(int(c) + 1) // PAGE) for c in ctx[running]))
        assert e['args']['kv_live_pages'] == live[-1]
        assert e['args']['kv_table_pages'] == pts.size \
            == STREAMS * MAX_SEQ // PAGE
        assert 0 < live[-1] <= pts.size
    # the 15-token prompt crosses into its third page while it decodes
    assert len(set(live)) > 1
    assert stats['kv_live_pages'] == sum(live)
    assert stats['kv_table_pages'] == len(steps) * STREAMS * MAX_SEQ // PAGE
    # the step's compile span names the attention its shapes take: off
    # the TPU the gathered span, whatever the head size
    comp = [e['args'] for e in spans(ring, 'decode.compile')]
    assert [a['attention'] for a in comp if a['program'] == 'step'] \
        == ['xla_gather']
    assert not any('attention' in a for a in comp if a['program'] != 'step')


def test_opt_engine_reports_no_routing(ring):
    """The engine's default description, ``OptBlock``, has no experts:
    its spans carry no ``moe_*`` argument and the server's routing
    totals stay zero.  Everything else it reports as any block does:
    the weights' set-up span, ``argument_bytes`` and the step's
    ``attention`` on ``decode.compile``, and the KV pages of every
    step, counted from the arrays the server hands the engine.  Its
    ``max_seq`` defaults to the position table's rows and may not
    exceed them."""
    scope = fluid.Scope()
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 7
    with fluid.program_guard(main_p, startup):
        transformer.build(vocab_size=64, seq_len=64, n_layers=2,
                          d_model=32, n_heads=4)
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    ring.clear()
    p = extract_params(scope, 2)
    assert sorted(p) == sorted(transformer.param_names(2))
    eng = DecodeEngine(p, n_layers=2, n_heads=4, page_size=8,
                       max_streams=2, prefill_bucket=16,
                       prefix_cache=False, prefill_chunk_tokens=0)
    assert eng.max_seq == 64 and isinstance(eng.block, OptBlock)
    with pytest.raises(ValueError, match='position table'):
        DecodeEngine(eng.params, n_layers=2, n_heads=4, max_seq=128)
    with pytest.raises(ValueError, match='heads'):
        DecodeEngine(eng.params, n_layers=2, n_heads=4, block=OptBlock(2))
    assert spans(ring, 'decode.weights')[-1]['args'] == {
        'bytes': sum(v.nbytes for v in p.values()), 'tensors': len(p),
        'dtype': 'float32'}
    handed, step = [], eng.step

    def spy(tokens, page_tables, ctx_lens):
        handed.append((np.array(page_tables), np.array(ctx_lens)))
        return step(tokens, page_tables, ctx_lens)
    eng.step = spy
    server = DecodeServer(eng)
    try:
        server.submit(np.arange(1, 8), max_new_tokens=4).result(timeout=60)
        stats = server.stats()
    finally:
        server.close()
    assert all('argument_bytes' in e['args']
               for e in spans(ring, 'decode.compile'))
    steps = spans(ring, 'decode.step')
    assert len(steps) == len(handed) == 3
    live = [sum(-(-(int(c) + 1) // 8)
                for c in ctx[pts[:, 0] != eng.cache.trash])
            for pts, ctx in handed]
    assert live == [1, 2, 2]    # position 7 is page 0's last row
    # two slots' ids came back, no counts; the first step's three arrays
    # went in from the host, the next two steps' none
    for e, n, sent in zip(steps, live, (3, 0, 0)):
        assert e['args'] == {'kv_live_pages': n, 'kv_table_pages': 2 * 8,
                             'fetched_bytes': 2 * 4, 'host_operands': sent}
    assert all(set(e['args']) == {'tokens', 'bucket', 'fetched_bytes'}
               for e in spans(ring, 'decode.prefill_into'))
    assert (stats['moe_assignments'], stats['moe_max_load'],
            stats['moe_touched_mean']) == (0, 0, 0.0)
    assert (stats['kv_live_pages'], stats['kv_table_pages']) \
        == (sum(live), 3 * 2 * 8)
    assert [e['args']['attention'] for e in spans(ring, 'decode.compile')
            if e['args']['program'] == 'step'] == ['xla_gather']


# 11 ------------------------------------------------------------------------
# A prefill chunk carries the tick's decode rows: one program for both,
# for either block description.

MODELS = {'olmoe': (make_params, OlmoeBlock, ref_logits),
          'opt': (make_opt_params, OptBlock, ref_opt_logits)}
CARRY_TOL = 1e-5


def chunked_engine(model, seed=0, **kw):
    """(weights, a warmed engine that prefills in chunks of a page, the
    reference) for one of the two block descriptions."""
    make, block, reference = MODELS[model]
    p = make(seed)
    eng = make_engine(p, block(H), prefill_chunk_tokens=PAGE, **kw)
    eng.warmup()
    return p, eng, reference


def pool_rows(eng, pages, n):
    """K and V of positions 0..n-1 behind ``pages``: [2, L, n, D]."""
    at = ([pages[i // PAGE] for i in range(n)], [i % PAGE for i in range(n)])
    return np.stack([np.stack([np.asarray(x)[at] for x in pool])
                     for pool in (eng.cache.k, eng.cache.v)])


def decoding_state(eng, prompts):
    """Prefill ``prompts`` in chunks into slots 0, 2, ...: the operands
    of the step that decodes them all next, and their pages."""
    pt = np.full((STREAMS, eng.pages_per_stream), eng.cache.trash, np.int32)
    toks, ctx = np.zeros(STREAMS, np.int32), np.zeros(STREAMS, np.int32)
    pages = []
    for slot, prompt in zip(range(0, STREAMS, 2), prompts):
        pages.append(eng.cache.alloc(-(-(len(prompt) + 8) // PAGE)))
        for lo, hi in eng.chunk_spans(len(prompt)):
            logits = eng.prefill_chunk(prompt[lo:hi], pages[-1], lo)
        pt[slot, :len(pages[-1])] = pages[-1]
        toks[slot], ctx[slot] = int(np.argmax(logits)), len(prompt)
    return (toks, pt, ctx), pages


@pytest.mark.parametrize('model', sorted(MODELS))
def test_a_chunk_alone_returns_what_it_did(model):
    """(a) ``prefill_chunk`` with its three arguments carries no decode
    row: last-row logits of the reference, and in the pools the rows a
    monolithic prefill packs."""
    p, eng, reference = chunked_engine(model)
    mono = make_engine(p, MODELS[model][1](H))
    prompt = np.random.default_rng(40).integers(1, V, 27)
    pages, mono_pages = eng.cache.alloc(4), mono.cache.alloc(4)
    for lo, hi in eng.chunk_spans(len(prompt)):
        logits = eng.prefill_chunk(prompt[lo:hi], pages, lo)
    assert isinstance(logits, np.ndarray) and logits.shape == (V,)
    assert rel(logits, reference(p, prompt)[-1]) < CARRY_TOL
    assert rel(logits, mono.prefill_into(prompt, mono_pages)) < CARRY_TOL
    assert rel(pool_rows(eng, pages, 27),
               pool_rows(mono, mono_pages, 27)) < CARRY_TOL
    assert eng.compiles_after_warmup == 0


@pytest.mark.parametrize('model', sorted(MODELS))
def test_carried_rows_equal_a_step_after_the_chunk(model, ring):
    """(b) On the same state, a chunk handed a step's operands gives the
    running slots the tokens and logits ``step`` gives them after the
    chunk, the chunk its own last-row logits, and the pools the same
    rows: over every chunk of a prompt, two streams decoding beside it."""
    rng = np.random.default_rng(41)
    running = [rng.integers(1, V, 7), rng.integers(1, V, 19)]
    prompt = rng.integers(1, V, 27)
    sides = []
    for fused in (False, True):
        p, eng, _ = chunked_engine(model)
        (toks, pt, ctx), pages = decoding_state(eng, running)
        mine = eng.cache.alloc(4)
        ring.clear()
        out = []
        for lo, hi in eng.chunk_spans(len(prompt)):
            if fused:
                last, nxt, logits = eng.prefill_chunk(
                    prompt[lo:hi], mine, lo, toks, pt, ctx)
                assert isinstance(nxt, np.ndarray)
            else:
                last = eng.prefill_chunk(prompt[lo:hi], mine, lo)
                nxt, logits = eng.step(toks, pt, ctx)
            out.append((last, nxt, np.asarray(logits)))
            live = sum(-(-(int(c) + 1) // PAGE) for c in ctx[::2])
            toks, ctx = np.where(ctx, nxt, 0).astype(np.int32), \
                ctx + (ctx > 0)
            if fused:
                args = spans(ring, 'decode.prefill_chunk')[-1]['args']
                assert args['tokens'] == hi - lo and args['bucket'] == PAGE
                assert args['step_rows'] == 2
                assert args['kv_live_pages'] == live
                if model == 'olmoe':
                    assert args['moe_assignments'] == 8 * L * (hi - lo + 2)
        assert bool(spans(ring, 'decode.step')) is not fused
        sides.append((out, [pool_rows(eng, pg, n) for pg, n in
                            zip(pages + [mine], (7 + 4, 19 + 4, 27))]))
        assert eng.compiles_after_warmup == 0
    (alone, rows_alone), (carried, rows_carried) = sides
    for (last_a, nxt_a, lg_a), (last_c, nxt_c, lg_c) in zip(alone, carried):
        assert rel(last_c, last_a) < CARRY_TOL
        assert np.array_equal(nxt_c[::2], nxt_a[::2])
        assert rel(lg_c[::2], lg_a[::2]) < CARRY_TOL
    for a, c in zip(rows_alone, rows_carried):
        assert rel(c, a) < CARRY_TOL


def served(model, lengths, n_new, hold_steps):
    """Serve prompts of ``lengths`` through a chunking server, the later
    ones submitted while the first decodes.  Returns what a replay by
    hand gives each alone (chunks, then steps), the streams, the stats,
    and the engine calls the server made."""
    p, eng, _ = chunked_engine(model, seed=3)
    rng = np.random.default_rng(42)
    prompts = [rng.integers(1, V, n) for n in lengths]

    def chunks(prompt, pages):
        for lo, hi in eng.chunk_spans(len(prompt)):
            logits = eng.prefill_chunk(prompt[lo:hi], pages, lo)
        return logits

    want = [[int(np.argmax(r)) for r in decode(eng, pr, n, prefill=chunks)[0]]
            for pr, n in zip(prompts, n_new)]
    # the first stream's first step waits for the other prompts
    calls, others_sent = [], hold_steps(eng)
    for name in ('prefill_chunk', 'step'):
        def spy(*args, _call=getattr(eng, name), _name=name):
            calls.append(_name)
            return _call(*args)
        setattr(eng, name, spy)
    timeline.ring().clear()
    server = DecodeServer(eng, warmup=False)
    try:
        streams = [server.submit(pr, max_new_tokens=n)
                   for pr, n in zip(prompts[:1], n_new[:1])]
        while not streams[0].tokens:     # its prompt is in the pages
            streams[0]._done.wait(0.001)
        streams += [server.submit(pr, max_new_tokens=n)
                    for pr, n in zip(prompts[1:], n_new[1:])]
        others_sent()
        for st in streams:
            st.result(timeout=120.0)
        stats = server.stats()
    finally:
        server.close()
    return want, streams, stats, calls


@pytest.mark.parametrize('model', sorted(MODELS))
def test_server_tokens_equal_a_replay_by_hand(model, ring, hold_steps):
    """(c) Prompts that arrive while another stream decodes ride with its
    decode rows; every request still gets the tokens its own chunks and
    steps give it alone."""
    n_new = (14, 5, 9)
    want, streams, stats, _ = served(model, (11, 13, 21), n_new, hold_steps)
    assert [st.tokens for st in streams] == want
    assert 1 <= stats['prefill_chunks_carrying'] <= stats['prefill_chunks']
    assert stats['carried_rows'] >= stats['prefill_chunks_carrying']
    assert stats['prefill_chunks'] == 2 + 2 + 3
    assert stats['generated_tokens'] == sum(n_new)
    assert stats['completed'] == 3 and stats['compiles_after_warmup'] == 0
    assert stats['free_pages'] == 40


@pytest.mark.parametrize('model', sorted(MODELS))
def test_a_tick_with_a_prompt_pending_is_one_engine_call(model, ring,
                                                         hold_steps):
    """(d) With one chunk a tick (prompts of whole chunks), every tick
    that has work makes ONE call into the engine: a chunk that carries
    the running slots, under one ``decode.prefill_chunk`` span and no
    ``decode.step``, or a plain step."""
    n_new = (12, 4, 6)
    want, streams, stats, calls = served(model, (8, 16, 24), n_new,
                                         hold_steps)
    assert [st.tokens for st in streams] == want
    evs = [e for e in ring.events(cat='span') if 'id' in e]
    by_id = {e['id']: e for e in evs}

    def tick_of(e):
        while e['name'] != 'server.tick':
            e = by_id[e['parent']]
        return e['id']

    made = {}
    for e in evs:
        if e['name'] in ('decode.prefill_chunk', 'decode.step'):
            made.setdefault(tick_of(e), []).append(e)
    ticks = [e for e in evs if e['name'] == 'server.tick']
    assert all(len(v) == 1 for v in made.values())
    assert len(calls) == len(made) == sum(
        1 for t in ticks if t['args']['running'] or t['id'] in made)
    chunk_ticks = [(by_id[t], v[0]) for t, v in made.items()
                   if v[0]['name'] == 'decode.prefill_chunk']
    assert len(chunk_ticks) == stats['prefill_chunks'] == 1 + 2 + 3
    for tick, call in chunk_ticks:
        assert call['args']['step_rows'] == tick['args']['running']
    carrying = [c for _, c in chunk_ticks if c['args']['step_rows']]
    # the first prompt ran alone; every later chunk found it decoding
    assert len(carrying) == stats['prefill_chunks_carrying'] == 5
    assert stats['carried_rows'] == sum(c['args']['step_rows']
                                        for c in carrying)
    assert calls.count('prefill_chunk') == 6
    assert stats['decode_steps'] == calls.count('step') + 5
    assert stats['generated_tokens'] == sum(n_new)

"""dots.vlm1's language model through DecodeEngine / DecodeServer
against the plain reference (tests/reference_dots_vlm.py, the copy of
chipbench/reference/dots_vlm.py), on the CPU at toy widths, float32:
hidden 64, 4 heads (nope 8 | rope 8, v 8), q rank 24, latent rank 16,
1 dense layer of 96 + 2 expert layers whose router is 32 wide (8 groups
of 4; 8 a token out of 4 groups) and of which THIS share holds experts
0 and 1 (a sixteenth, as the deployment's chip), a shared expert, page
8, 64 positions.  Every comparison is on LOGITS.

Both sides are true float32 here, so what is left is the order of
summation and the absorbed form's regrouping of two products: TOL is
2e-5, as for OLMoE.  The chip comparison carries the looser bar bf16
needs (chipbench/reference/dots_vlm.py, ``LOGITS_TOL``); a wrong block
has to move the logits beyond THAT bar to count as caught.

YaRN's 4096 original positions would leave every frequency of an
8-lane rotation plain over 64 positions, so the toy context is 16
original positions (``YARN_ORIGINAL_MAX``, here and in the reference):
the pair index ramp then cuts through the four pairs as it cuts through
the 32 of the published model.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core.registry import get_op_impl
from paddle_tpu.inference.blocks import DotsVlmBlock
from paddle_tpu.inference.decode import (DecodeEngine, DecodeServer,
                                         extract_params)
from paddle_tpu.models import dots_vlm
from paddle_tpu.observability import timeline
from paddle_tpu.ops import attention as att
from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas.paged_attention import latent_paged_attention
from paddle_tpu.transpiler import cost_model

import reference_dots_vlm as ref

TOL = 2e-5
V, L, D, H = 97, 3, 64, 4
QR, KR, NOPE, ROPE, VD = 24, 16, 8, 8, 8
DENSE, ROUTER, HELD, F = 96, 32, 2, 16
PAGE, STREAMS, MAX_SEQ = 8, 4, 64
YARN = dict(dots_vlm.YARN, original_max=16)
SHAPES = {
    'in_norm_w': (D,), 'qa_w': (D, QR), 'q_norm_w': (QR,),
    'qb_w': (QR, H * (NOPE + ROPE)), 'kva_w': (D, KR + ROPE),
    'kv_norm_w': (KR,), 'kvb_w': (KR, H * (NOPE + VD)),
    'o_w': (H * VD, D), 'post_norm_w': (D,), 'router_w': (D, ROUTER),
    'router_bias': (ROUTER,), 'shared_gate_w': (D, F),
    'shared_up_w': (D, F), 'shared_down_w': (F, D)}


@pytest.fixture(autouse=True)
def toy_context(monkeypatch):
    monkeypatch.setattr(ref, 'YARN_ORIGINAL_MAX', YARN['original_max'])


def make_block(cls=DotsVlmBlock, **kw):
    kw = dict(dict(qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
                   v_head_dim=VD, yarn=YARN), **kw)
    return cls(H, **kw)


def make_params(seed=0, held=HELD, dtype=jnp.float32):
    """Seeded weights: every branch (attention, dense, routed, shared)
    adds a few tenths to a unit stream, norm weights around 1 so that a
    norm in the wrong place shows, a router and a bias that spread the
    choice over groups and experts."""
    rng, p = np.random.default_rng(seed), {}
    for n in dots_vlm.param_names(L):
        key = n.split('_', 2)[2] if n.startswith('dots_l') else n
        dense = n.startswith('dots_l0_')
        shape = {'dots_embed': (V, D), 'dots_head_w': (D, V),
                 'dots_norm_f_w': (D,),
                 'gate_w': (D, DENSE) if dense else (held, D, F),
                 'up_w': (D, DENSE) if dense else (held, D, F),
                 'down_w': (DENSE, D) if dense else (held, F, D)
                 }.get(key) or SHAPES[key]
        if key == 'router_bias':
            w = 0.3 * rng.normal(size=shape)
        elif len(shape) == 1:
            w = 1 + 0.1 * rng.normal(size=shape)
        elif key == 'dots_embed':
            w = rng.normal(size=shape)
        elif key == 'router_w':
            w = 0.25 * rng.normal(size=shape)
        else:
            w = rng.normal(size=shape) * (0.5 if len(shape) == 3 else 0.25)
        p[n] = jnp.asarray(w, jnp.float32 if len(shape) == 1
                           or key == 'router_w' else dtype)
    return p


def make_engine(p, block=None, top=32, **kw):
    kw.setdefault('prefix_cache', False)
    kw.setdefault('prefill_chunk_tokens', 0)
    return DecodeEngine(p, n_layers=L, n_heads=H, page_size=PAGE,
                        num_pages=40, max_streams=STREAMS,
                        prefill_bucket=top, max_seq=MAX_SEQ,
                        block=block or make_block(), **kw)


def ref_logits(p, seq):
    return np.asarray(ref.logits(p, jnp.asarray(seq, jnp.int32), L, H))


def rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def one_slot(eng, slot, tok, pages, ctx):
    pt = np.full((STREAMS, eng.pages_per_stream), eng.cache.trash, np.int32)
    pt[slot, :len(pages)] = pages
    t, c = np.zeros(STREAMS, np.int32), np.zeros(STREAMS, np.int32)
    t[slot], c[slot] = tok, ctx
    return t, pt, c


def chunked_prefill(eng, prompt, pages):
    for lo, hi in eng.chunk_spans(len(prompt)):
        out = eng.prefill_chunk(prompt[lo:hi], pages, lo)
    return out


def decode(eng, prompt, n_new, slot=1):
    """Prefill (the engine's way) then ``n_new - 1`` greedy steps
    through the pages: the logits of every position produced, and the
    whole sequence."""
    pages = eng.cache.alloc(-(-(len(prompt) + n_new) // PAGE))
    rows = [chunked_prefill(eng, prompt, pages) if eng.chunked
            else eng.prefill_into(prompt, pages)]
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


@pytest.fixture
def ring(monkeypatch):
    monkeypatch.delenv('PADDLE_TPU_TRACE_DIR', raising=False)
    timeline.reset()
    yield timeline.ring()
    timeline.reset()


def spans(ring, name):
    return [e for e in ring.events(cat='span') if e['name'] == name]


# 1 -------------------------------------------------------------------------

@pytest.mark.parametrize('n', [3, 8, 13, 16, 29])
def test_prefill_of_every_bucket(params, n):
    """A whole-prompt prefill (expanded form), prompts that fill a
    bucket and prompts padded up to one."""
    eng = make_engine(params)
    prompt = np.random.default_rng(n).integers(1, V, n)
    pages = eng.cache.alloc(-(-n // PAGE))
    got = eng.prefill_into(prompt, pages)
    assert rel(got, ref_logits(params, prompt)[-1]) < TOL


# 2 -------------------------------------------------------------------------

@pytest.mark.parametrize('seed', [1, 2])
def test_prefill_then_decode_through_the_pages(seed):
    """Prefill writes the latent rows, every decode step attends over
    them in the absorbed form and appends its own: all 10 positions
    agree with the full-context pass, across a page boundary."""
    p = make_params(seed)
    eng = make_engine(p)
    prompt = np.random.default_rng(seed).integers(1, V, 11)
    got, seq = decode(eng, prompt, 10)
    want = ref_logits(p, seq)[len(prompt) - 1:]
    assert rel(got, want) < TOL


# 3 -------------------------------------------------------------------------

@pytest.mark.parametrize('chunk_pages', [1, 2])
def test_chunked_prefill_with_carried_rows(params, chunk_pages):
    """A prompt prefilled in chunks of one or two pages while another
    stream decodes in the same programs: the chunk's rows and the
    carried decode rows (both absorbed) each agree with the reference."""
    eng = make_engine(params, prefill_chunk_tokens=chunk_pages * PAGE)
    eng.warmup()
    rng = np.random.default_rng(5)
    a = rng.integers(1, V, 13)
    pages_a = eng.cache.alloc(4)
    seq_a = list(a) + [int(np.argmax(chunked_prefill(eng, a, pages_a)))]
    b = rng.integers(1, V, 21)
    pages_b = eng.cache.alloc(4)
    for lo, hi in eng.chunk_spans(len(b)):
        # stream a decodes one token in each of b's chunks
        t, pt, c = one_slot(eng, 2, seq_a[-1], pages_a, len(seq_a) - 1)
        last, nxt, step_logits = eng.prefill_chunk(
            b[lo:hi], pages_b, lo, t, pt, c)
        assert rel(np.asarray(step_logits)[2],
                   ref_logits(params, seq_a)[-1]) < TOL
        seq_a.append(int(nxt[2]))
    assert rel(last, ref_logits(params, b)[-1]) < TOL
    assert eng.compiles_after_warmup == 0


# 4 -------------------------------------------------------------------------

def test_two_streams_equal_each_alone(params):
    eng = make_engine(params)
    rng = np.random.default_rng(7)
    prompts = {0: rng.integers(1, V, 9), 3: rng.integers(1, V, 17)}
    alone = {s: decode(make_engine(params), pr, 5, slot=s)
             for s, pr in prompts.items()}
    pages, seqs = {}, {}
    for s, pr in prompts.items():
        pages[s] = eng.cache.alloc(4)
        seqs[s] = list(pr) + [int(np.argmax(eng.prefill_into(pr, pages[s])))]
    for j in range(1, 5):
        pt = np.full((STREAMS, eng.pages_per_stream), eng.cache.trash,
                     np.int32)
        t, c = np.zeros(STREAMS, np.int32), np.zeros(STREAMS, np.int32)
        for s in prompts:
            pt[s, :4], t[s], c[s] = pages[s], seqs[s][-1], len(seqs[s]) - 1
        nxt, logits = eng.step(t, pt, c)
        for s in prompts:
            assert rel(logits[s], alone[s][0][j]) < TOL
            seqs[s].append(int(nxt[s]))


# 5 -------------------------------------------------------------------------

def test_absorbed_equals_expanded(params):
    """The two forms of one layer's attention over the same cached
    rows: keys and values rebuilt for every head, or the key
    up-projection folded into the query and the value up-projection
    applied to the attended latent."""
    blk = make_block()
    rng = np.random.default_rng(3)
    t = 12
    x = jnp.asarray(rng.normal(size=(t, D)), jnp.float32)
    pos = jnp.arange(t)
    q, rows = blk.qkv(params, x, 1, pos)
    assert rows.shape == (t, 128) and not np.any(np.asarray(rows[:, 24:]))
    causal = pos[:, None] >= pos[None, :]
    expanded = blk._attend_expanded(params, 1, q, rows, causal)
    pool = jnp.zeros((3, PAGE, 128)).at[:2].set(
        jnp.pad(rows, ((0, 4), (0, 0))).reshape(2, PAGE, 128))
    absorbed = blk.attend_chunk(params, 1, q, [pool],
                                jnp.asarray([0, 1, 2, 2]), jnp.int32(0))
    assert rel(np.asarray(absorbed), np.asarray(expanded)) < TOL
    step = blk.attend_step(params, 1, q[-1:], [pool],
                           jnp.asarray([[0, 1, 2, 2]]), jnp.asarray([t]))
    assert rel(np.asarray(step)[0], np.asarray(expanded)[-1]) < TOL


# 6 -------------------------------------------------------------------------

class _PlainFrequencies(DotsVlmBlock):
    def rotate(self, u, positions):
        return moe.rotary_math(u, positions, self.theta, None,
                               interleaved=True)


class _HalfSplit(DotsVlmBlock):
    def rotate(self, u, positions):
        return moe.rotary_math(u, positions, self.theta, self.yarn)


class _SoftmaxRouter(DotsVlmBlock):
    def route(self, h, router_w, bias):
        w, idx = moe.moe_route(h, router_w, self.top_k, True)
        return w * self.routed_scale, idx


class _NoBiasInTheChoice(DotsVlmBlock):
    def route(self, h, router_w, bias):
        return super().route(h, router_w, jnp.zeros_like(bias))


class _NoMscale(DotsVlmBlock):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.softmax_scale = (self.nope + self.rope) ** -0.5


class _LatentBeforeItsNorm(DotsVlmBlock):
    def latent_row(self, c_raw, r_raw, w, positions):
        return jnp.concatenate(
            [c_raw, self.rotate(r_raw[:, None, :], positions)[:, 0]], -1)


class _RopeKeyUnrotated(DotsVlmBlock):
    def latent_row(self, c_raw, r_raw, w, positions):
        return jnp.concatenate([self.norm(c_raw, w), r_raw], -1)


def _no_shared(p):
    return {n: jnp.zeros_like(v) if n.endswith('shared_down_w') else v
            for n, v in p.items()}


WRONG = {
    'no_yarn': (_PlainFrequencies, {}),
    'half_split_pairing': (_HalfSplit, {}),
    'softmax_router': (_SoftmaxRouter, {}),
    'no_bias_in_the_choice': (_NoBiasInTheChoice, {}),
    'no_group_limit': (DotsVlmBlock, {'topk_group': 8}),
    'seven_experts': (DotsVlmBlock, {'top_k': 7}),
    'no_routed_scaling': (DotsVlmBlock, {'routed_scaling_factor': 1.0}),
    'no_renormalisation': (DotsVlmBlock, {'renormalize': False}),
    'shared_expert_missing': (DotsVlmBlock, {}),
    'sigma_without_mscale': (_NoMscale, {}),
    'latent_cached_before_its_norm': (_LatentBeforeItsNorm, {}),
    'rope_key_cached_unrotated': (_RopeKeyUnrotated, {}),
    'dense_layer_routed_as_experts_are': (DotsVlmBlock, {'first_expert': 2}),
}


_RIGHT = {}


def _right(seed):
    """(weights, prompt) of a seed, the right block proven on them."""
    if seed not in _RIGHT:
        # a share of 8 experts (a quarter of the toy router), so that
        # most tokens meet a held expert
        p = make_params(seed, held=8)
        prompt = np.random.default_rng(seed).integers(1, V, 20)
        got, seq = decode(make_engine(p), prompt, 6)
        assert rel(got, ref_logits(p, seq)[len(prompt) - 1:]) < TOL
        _RIGHT[seed] = p, prompt
    return _RIGHT[seed]


@pytest.mark.parametrize('wrong', sorted(WRONG))
def test_a_wrong_block_moves_the_logits(wrong):
    """Each way of getting the layer wrong moves prefill-then-decode
    logits beyond the CHIP's tolerance (over seeds: a routing variant
    shows only where a held expert is among a token's), while the right
    block stays at float32 rounding on the same prompts."""
    cls, kw = WRONG[wrong]
    worst = 0.0
    for seed in (0, 1):
        p, prompt = _right(seed)
        served = _no_shared(p) if wrong == 'shared_expert_missing' else p
        got, seq = decode(make_engine(served, make_block(cls, **kw)),
                          prompt, 6)
        worst = max(worst, rel(got, ref_logits(p, seq)[len(prompt) - 1:]))
    assert worst > 2 * ref.LOGITS_TOL, worst


# 7 -------------------------------------------------------------------------

def test_the_sixteen_shares_add_up():
    """The share test: the routed parts that the 16 shares of 2 experts
    each compute, plus the shared expert once, equal the uncut layer
    (all 32 experts held in one place), in the op and in the reference;
    and the counts of the shares add up to every assignment."""
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(24, D)), jnp.float32)
    full = make_params(9, held=ROUTER)
    n = 'dots_l1_'
    shared = tuple(full[n + 'shared_%s_w' % s] for s in ('gate', 'up',
                                                          'down'))
    blk = make_block()
    w, idx = blk.route(h, full[n + 'router_w'], full[n + 'router_bias'])
    experts = [full[n + s] for s in ('gate_w', 'up_w', 'down_w')]
    whole = moe.moe_experts(h, w, idx, *experts, shared=shared)
    parts = moe.swiglu_math(h, *shared)
    counted = np.zeros(ROUTER, np.int64)
    for share in range(16):
        lo = share * HELD
        mine = [e[lo:lo + HELD] for e in experts]
        parts = parts + moe.moe_experts(h, w, idx, *mine, first=lo)
        c = np.asarray(moe.moe_counts(idx, HELD, None, first=lo))
        counted[lo:lo + HELD] = c[:HELD]
        assert c.sum() == idx.size            # held + held elsewhere
    assert rel(np.asarray(parts), np.asarray(whole)) < TOL
    assert np.array_equal(counted,
                          np.asarray(moe.moe_counts(idx, ROUTER)))
    # the reference, given the same shares
    whole_ref, _ = ref.ffn(full, n, h, first_expert=0)
    parts_ref = ref._swiglu(h, *shared)
    for share in range(16):
        lo = share * HELD
        mine = dict(full, **{n + s: full[n + s][lo:lo + HELD]
                             for s in ('gate_w', 'up_w', 'down_w')})
        parts_ref = parts_ref + ref.ffn(mine, n, h, first_expert=lo,
                                        with_shared=False)[0]
    assert rel(np.asarray(parts_ref), np.asarray(whole_ref)) < TOL
    assert rel(np.asarray(whole), np.asarray(whole_ref)) < TOL


def test_grouped_router_matches_the_reference(params):
    h = jnp.asarray(np.random.default_rng(2).normal(size=(40, D)),
                    jnp.float32)
    rw, b = params['dots_l1_router_w'], params['dots_l1_router_bias']
    w, idx = make_block().route(h, rw, b)
    w_ref, idx_ref, s = ref.route(h, rw, b)
    assert np.array_equal(np.asarray(idx), np.asarray(idx_ref))
    assert rel(np.asarray(w), np.asarray(w_ref)) < 1e-6
    assert np.allclose(np.asarray(w).sum(-1), 2.5, atol=1e-5)
    # 8 experts out of exactly 4 of the 8 groups, and the choice is the
    # bias's too: without it some token takes another set
    assert all(len(set(r // 4)) <= 4 for r in np.asarray(idx))
    _, plain = make_block().route(h, rw, jnp.zeros_like(b))
    assert not np.array_equal(np.sort(idx, -1), np.sort(plain, -1))


def test_yarn_frequencies_match_the_published_numbers():
    """At the published sizes (64 lanes, 4096 original positions, factor
    40, beta 32 / 1): pairs 0-10 keep their frequency, pairs 23-31 turn
    40 times slower, a linear ramp between; mscale 1.36889."""
    f = np.asarray(moe.yarn_inv_freq(64, 10000.0, 40.0))
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    assert np.allclose(f[:11], plain[:11], rtol=1e-6)
    assert np.allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all(f[11:23] < plain[11:23]) and \
        np.all(f[11:23] > plain[11:23] / 40)
    assert abs(moe.yarn_mscale(40.0) - 1.36889) < 1e-5
    blk = DotsVlmBlock(128, yarn=dots_vlm.YARN)
    assert abs(blk.softmax_scale - 0.135234) < 1e-6


# 8 -------------------------------------------------------------------------

def build_program(seed=3):
    scope = fluid.Scope()
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = seed
    with fluid.program_guard(main_p, startup):
        src, logits, counts = dots_vlm.build_logits(
            vocab_size=V, seq_len=16, n_layers=L, first_dense=1, d_model=D,
            n_heads=H, q_lora_rank=QR, kv_lora_rank=KR,
            qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=VD,
            dense_size=DENSE, router_width=ROUTER, n_experts=HELD,
            expert_size=F, init_std=0.25, expert_init_std=0.5,
            router_bias_std=0.3, embed_init_std=1.0, yarn=YARN)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    return exe, scope, main_p, logits, counts


def test_program_through_executor_equals_the_engine():
    """models/dots_vlm.py builds the same block from registered ops: its
    startup program seeds the engine's weights, its logits are the
    engine's and the reference's, and its counts are the held experts'
    then the rest."""
    exe, scope, main_p, logits, counts = build_program()
    block = make_block()
    p = extract_params(scope, L, block)
    assert sorted(p) == sorted(dots_vlm.param_names(L))
    assert p['dots_l1_gate_w'].shape == (HELD, D, F)
    assert p['dots_l0_gate_w'].shape == (D, DENSE)
    assert float(jnp.std(p['dots_l1_router_bias'])) > 0.1
    seq = np.random.default_rng(8).integers(1, V, 16)
    out = exe.run(main_p, feed={'src': seq[None].astype(np.int64)},
                  fetch_list=[logits] + counts, scope=scope)
    want = ref_logits(p, seq)
    assert rel(np.asarray(out[0])[0], want) < TOL
    eng = make_engine(p, block)
    pages = eng.cache.alloc(2)
    assert rel(eng.prefill_into(seq, pages), want[-1]) < TOL
    for c in out[1:]:
        assert c.shape == (HELD + 1,) and c.sum() == 16 * 8


# 9 -------------------------------------------------------------------------

def test_spans_counters_and_server(params, ring):
    """A server over the chunked engine: tokens equal the reference's
    greedy choice; ``decode.compile`` says what a position caches and
    which attention each program takes; steps and carried chunks count
    the latent positions they read and the held experts' assignments
    beside all of them; the dense layer routes nothing."""
    eng = make_engine(params, prefill_chunk_tokens=PAGE)
    server = DecodeServer(eng)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, V, n) for n in (5, 19, 12)]
    try:
        streams = [server.submit(pr, max_new_tokens=6) for pr in prompts]
        toks = [st.result(timeout=120.0) for st in streams]
        stats = server.stats()
    finally:
        server.close()
    for pr, got in zip(prompts, toks):
        seq = list(pr)
        for tok in got:
            assert tok == int(np.argmax(ref_logits(params, seq)[-1]))
            seq.append(tok)
    comp = [e['args'] for e in spans(ring, 'decode.compile')]
    assert {a['program'] for a in comp} == {'chunk', 'step'}
    for a in comp:
        assert a['cache_rows'] == {'latent': 128}
        assert a['cache_bytes_per_position'] == 128 * 4
        assert a['attention_path'] == {
            'step': 'xla_gather', 'chunk': 'xla_gather+xla_gather'
        }[a['program']]
        assert a['alias_bytes'] == a['pool_bytes'] == eng.resident_bytes() \
            == L * 41 * PAGE * 128 * 4
    assert [n for n, _w in eng.cache.rows] == ['latent']
    assert eng.cache.latent is eng.cache.pools[0]
    steps = spans(ring, 'decode.step') + [
        e for e in spans(ring, 'decode.prefill_chunk')
        if e['args']['step_rows']]
    assert steps and any(e['name'] == 'decode.prefill_chunk' for e in steps)
    for e in steps:
        a = e['args']
        assert a['kv_latent_live_positions'] >= a['kv_live_pages']
        assert 0 <= a['moe_held_assignments'] <= a['moe_all_assignments']
        assert 0 <= a['moe_held_touched'] <= HELD
        assert 'moe_assignments' not in a
    # every active row routes 8 ways in each of the 2 expert layers (the
    # dense layer is not counted)
    for e in spans(ring, 'decode.step'):
        assert e['args']['moe_all_assignments'] % (8 * 2) == 0
    assert stats['moe_all_assignments'] > stats['moe_assignments'] > 0


@pytest.mark.parametrize('pending, first, want', [
    # (stream's (prefill position, prompt length)), round-robin start ->
    # the tick's chunks as (stream, lo, hi), chunk grid = budget = 16
    ([(32, 38), (0, 40)], 0, [(0, 32, 38)]),    # a ragged 6, NOT + a whole 16
    ([(32, 38), (0, 7)], 0, [(0, 32, 38), (1, 0, 7)]),   # 6 + 7 fit in 16
    ([(32, 38), (0, 12)], 0, [(0, 32, 38)]),    # 6 + 12 do not
    ([(0, 40), (32, 38)], 0, [(0, 0, 16)]),     # a whole chunk fills the tick
    ([(0, 40), (32, 38)], 1, [(1, 32, 38)]),    # round-robin: the other first
    ([(0, 30)], 0, [(0, 0, 16)]),               # one stream: one chunk a tick
])
def test_a_tick_keeps_to_its_prefill_budget(pending, first, want):
    """``_plan_prefill_chunks``: every chunk is one pass over the
    weights, so a tick runs one chunk and a further one only if both fit
    in ``prefill_chunk_tokens`` together (until PR 32 a ragged remainder
    was followed by another prompt's whole chunk: two passes a tick)."""
    import types
    from paddle_tpu.inference.decode import DecodeServer
    streams = [types.SimpleNamespace(_prefill_pos=lo, _prompt_eff=[0] * t)
               for lo, t in pending]
    server = types.SimpleNamespace(
        engine=types.SimpleNamespace(chunk_tokens=16, chunk_grid=16),
        _chunk_rr=first)
    plan = DecodeServer._plan_prefill_chunks(server, streams)
    assert [(streams.index(st), lo, hi) for st, lo, hi in plan] == want
    assert sum(hi - lo for _st, lo, hi in plan) <= 16
    # no budget: every pending prompt whole, now
    server.engine.chunk_tokens = 0
    whole = DecodeServer._plan_prefill_chunks(server, streams)
    assert sum(hi - lo for _st, lo, hi in whole) \
        == sum(t - lo for lo, t in pending)


# 10 ------------------------------------------------------------------------

def _latent_case(seed, s=3, mpp=6, n=20, w=128, h=4):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(n, PAGE, w)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(s, h, w)), jnp.float32)
    pt = jnp.asarray(rng.permutation(n)[:s * mpp].reshape(s, mpp),
                     jnp.int32)
    return q, pool, pt


def test_latent_ops_and_the_live_pages_kernel():
    """The two registered latent ops against a hand-written softmax over
    the gathered rows, and the Pallas kernel (interpreted here) against
    the ops' math: a decode step (one token a slot, ragged lengths, a
    length of 1) and a chunk (rows in groups that share a pass)."""
    q, pool, pt = _latent_case(0)
    ctx_len = jnp.asarray([1, 17, 48], jnp.int32)
    got = get_op_impl('latent_paged_attention').compute(
        None, {'Q': [q], 'Pool': [pool], 'PT': [pt], 'CtxLen': [ctx_len]},
        {'scale': 0.2, 'value_dim': 96})['Out'][0]
    for i in range(3):
        rows = np.asarray(pool)[np.asarray(pt)[i]].reshape(-1, 128)[
            :int(ctx_len[i])]
        sc = np.einsum('hw,tw->ht', np.asarray(q)[i], rows) * 0.2
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        assert np.allclose(np.asarray(got)[i], pr @ rows[:, :96], atol=1e-5)
    kern = latent_paged_attention(q, pool, pt, ctx_len, 0.2, 96,
                                  interpret=True)
    assert np.allclose(np.asarray(kern), np.asarray(got), atol=1e-5)
    # a chunk of 16 rows at positions 13.. over one stream's pages
    qc = jnp.asarray(np.random.default_rng(1).normal(size=(16, 4, 128)),
                     jnp.float32)
    chunk = get_op_impl('latent_chunked_prefill_attention').compute(
        None, {'Q': [qc], 'Pool': [pool], 'PT': [pt[0]],
               'Pos0': [jnp.int32(13)]},
        {'scale': 0.2, 'value_dim': 96})['Out'][0]
    step_wise = att.latent_paged_attention_math(
        qc, pool, jnp.broadcast_to(pt[0], (16, 6)), 14 + jnp.arange(16),
        0.2, 96)
    assert np.allclose(np.asarray(chunk), np.asarray(step_wise), atol=1e-5)
    grouped = latent_paged_attention(
        qc, pool, jnp.broadcast_to(pt[0], (2, 6)),
        13 + (jnp.arange(2) + 1) * 8, 0.2, 96, group=8, interpret=True)
    assert np.allclose(np.asarray(grouped), np.asarray(chunk), atol=1e-5)
    # other blocks than the default's (8 and 32 pages), over page tables
    # with runs of pages that follow one another in the pool
    rng = np.random.default_rng(2)
    pool = jnp.asarray(rng.normal(size=(200, PAGE, 128)), jnp.float32)
    pt = jnp.asarray(np.stack([
        np.arange(10, 50),
        np.concatenate([np.arange(100, 120), rng.permutation(90)[:20]]),
        np.concatenate([np.arange(150, 165), [3], np.arange(60, 84)])]),
        jnp.int32)
    q3 = jnp.asarray(rng.normal(size=(3, 4, 128)), jnp.float32)
    ctx3 = jnp.asarray([317, 260, 320], jnp.int32)
    want = att.latent_paged_attention_math(q3, pool, pt, ctx3, 0.2, 96)
    for block in (64, 256):
        got = latent_paged_attention(q3, pool, pt, ctx3, 0.2, 96,
                                     block_positions=block, interpret=True)
        assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert att.latent_attention_path('cpu', 16, jnp.bfloat16) == 'xla_gather'
    assert att.latent_attention_path('tpu', 16, jnp.bfloat16) \
        == 'pallas_latent'
    assert att.latent_attention_path('tpu', 8, jnp.bfloat16) == 'xla_gather'


def test_cost_model_counts_the_latent_ops():
    """MACs: every query row against the gathered span, W lanes for the
    scores and value_dim for the values; bytes: the span once, not the
    pool."""
    q = ((64, 128, 640), 'float32')
    pool = ((16385, 16, 640), 'bfloat16')
    ins = {'Q': [q], 'Pool': [pool], 'PT': [((64, 256), 'int32')],
           'CtxLen': [((64,), 'int32')]}
    outs = {'Out': [((64, 128, 512), 'float32')]}
    unknown = [0]
    macs = cost_model.MAC_FORMULAS['latent_paged_attention'](
        ins, outs, {'value_dim': 512}, unknown)
    assert macs == 64 * 128 * 4096 * (640 + 512)
    nbytes = cost_model.BYTES_FORMULAS['latent_paged_attention'](
        ins, outs, {}, unknown)
    assert 64 * 4096 * 640 * 2 < nbytes < 1.2 * 64 * 4096 * 640 * 2
    ins = {'Q': [((256, 128, 640), 'float32')], 'Pool': [pool],
           'PT': [((256,), 'int32')], 'Pos0': [((), 'int32')]}
    macs = cost_model.MAC_FORMULAS['latent_chunked_prefill_attention'](
        ins, {'Out': [((256, 128, 512), 'float32')]}, {'value_dim': 512},
        unknown)
    assert macs == 256 * 128 * 4096 * (640 + 512)
    assert not unknown[0]

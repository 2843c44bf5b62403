"""Laguna-S-2.1's layer through DecodeEngine / DecodeServer against the
plain reference (tests/reference_laguna.py, the copy of
chipbench/reference/laguna.py), on the CPU at toy widths, float32:
hidden 64, 2 K/V heads of 16 lanes under 4 query heads on the layers
that read everything and 6 on those that read a window of 8 positions,
layers (full + dense, window, window, full), a router 16 wide that takes
4 a token and of which THIS share holds experts 4..7, a shared expert,
page 4, 64 positions, contexts of 40 and more.  Every comparison is on
LOGITS.

Both sides are true float32 here, so what is left is the order of
summation: TOL is 2e-5, as for OLMoE and dots.  The window layers'
pages are a group of their own, a ring of 5 pages a stream when nothing
is chunked and of 6 under chunks of 8 (7 + 8 positions, and a page to
spare), against 16 pages for a stream's whole context.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.inference.blocks import LagunaBlock
from paddle_tpu.inference.decode import (DecodeEngine, DecodeServer,
                                         extract_params)
from paddle_tpu.models import laguna
from paddle_tpu.observability import timeline
from paddle_tpu.ops import moe

import reference_laguna as ref
from test_decode_calls import (CHUNK_WRITE_CASES,
                               chunk_scatters_a_page_an_update,
                               chunk_writes_match_row_by_row, host_operands,
                               write_row_by_row)

TOL = 2e-5
V, D, HKV, DH = 97, 64, 2, 16
KINDS = ('full', 'window', 'window', 'full')
HEADS = tuple({'full': 4, 'window': 6}[k] for k in KINDS)
L, WINDOW = len(KINDS), 8
DENSE, ROUTER, HELD, FIRST, F, TOP_K, SCALE = 96, 16, 4, 4, 16, 4, 2.5
PAGE, STREAMS, MAX_SEQ = 4, 3, 64
ROPE = {'full': {'theta': 5e5, 'lanes': DH // 2, 'factor': 1.2,
                 'yarn': {'factor': 4.0, 'beta_fast': 32.0,
                          'beta_slow': 1.0, 'original_max': 16}},
        'window': {'theta': 1e4}}
SPEC = {'kinds': KINDS, 'window': WINDOW, 'top_k': TOP_K, 'scale': SCALE,
        'first_expert': FIRST, 'kv_heads': HKV, 'rope': ROPE}


def make_block(cls=LagunaBlock, **kw):
    kw = dict(dict(heads=HEADS, n_kv_heads=HKV, head_dim=DH, kinds=KINDS,
                   window=WINDOW, rope=ROPE, top_k=TOP_K,
                   routed_scaling_factor=SCALE, first_expert=FIRST), **kw)
    return cls(**kw)


def make_params(seed=0, held=HELD, dtype=jnp.float32):
    """Seeded weights: every branch (attention, its gate, dense, routed,
    shared) adds a few tenths to a unit stream, norm weights around 1,
    a router that spreads the choice over the experts."""
    rng, p = np.random.default_rng(seed), {}
    for n in laguna.param_names(L):
        key = n.split('_', 2)[2] if n.startswith('laguna_l') else n
        i = int(n.split('_')[1][1:]) if n.startswith('laguna_l') else 0
        dense, h = i == 0, HEADS[i]
        shape = {
            'laguna_embed': (V, D), 'laguna_head_w': (D, V),
            'laguna_norm_f_w': (D,), 'in_norm_w': (D,),
            'post_norm_w': (D,), 'q_w': (D, h * DH), 'k_w': (D, HKV * DH),
            'v_w': (D, HKV * DH), 'g_w': (D, h), 'o_w': (h * DH, D),
            'router_w': (D, ROUTER), 'shared_gate_w': (D, F),
            'shared_up_w': (D, F), 'shared_down_w': (F, D),
            'gate_w': (D, DENSE) if dense else (held, D, F),
            'up_w': (D, DENSE) if dense else (held, D, F),
            'down_w': (DENSE, D) if dense else (held, F, D)}[key]
        if len(shape) == 1:
            w = 1 + 0.1 * rng.normal(size=shape)
        elif key == 'laguna_embed':
            w = rng.normal(size=shape)
        else:
            w = rng.normal(size=shape) * (0.5 if len(shape) == 3 else 0.25)
        p[n] = jnp.asarray(w, jnp.float32 if len(shape) == 1
                           or key == 'router_w' else dtype)
    return p


def make_engine(p, block=None, top=32, num_pages=48, **kw):
    kw.setdefault('prefix_cache', False)
    kw.setdefault('prefill_chunk_tokens', 0)
    return DecodeEngine(p, n_layers=L, n_heads=max(HEADS), page_size=PAGE,
                        num_pages=num_pages, max_streams=STREAMS,
                        prefill_bucket=top, max_seq=MAX_SEQ,
                        block=block or make_block(), **kw)


_REF = jax.jit(lambda p, toks: ref.logits(p, toks, L, SPEC))


def ref_logits(p, seq):
    """The reference over ``seq``, compiled once: padded to the longest
    context (a causal model's rows do not see what follows them)."""
    toks = np.zeros((MAX_SEQ,), np.int32)
    toks[:len(seq)] = seq
    return np.asarray(_REF(p, jnp.asarray(toks)))[:len(seq)]


def rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def claim(eng, span):
    """A stream's pages of both groups, as the engine's calls take them."""
    return (eng.cache.alloc(-(-span // PAGE)),
            eng.cache.window.alloc(eng.ring_for(span)))


def give_back(eng, pages):
    eng.cache.free(pages[0])
    eng.cache.window.free(pages[1])


def one_slot(eng, slot, tok, pages, ctx):
    pt = np.tile(eng.idle_row, (STREAMS, 1))
    pt[slot] = eng.table_row(pages)
    t, c = np.zeros(STREAMS, np.int32), np.zeros(STREAMS, np.int32)
    t[slot], c[slot] = tok, ctx
    return t, pt, c


def chunked_prefill(eng, prompt, pages):
    for lo, hi in eng.chunk_spans(len(prompt)):
        out = eng.prefill_chunk(prompt[lo:hi], pages, lo)
    return out


def decode(eng, prompt, n_new, slot=1, before_call=None):
    """Prefill (the engine's way) then ``n_new - 1`` greedy steps
    through the pages: the logits of every position produced, and the
    whole sequence."""
    pages = claim(eng, len(prompt) + n_new)
    rows = [chunked_prefill(eng, prompt, pages) if eng.chunked
            else eng.prefill_into(prompt, pages)]
    seq = list(prompt)
    for _ in range(n_new - 1):
        seq.append(int(np.argmax(rows[-1])))
        if before_call:
            before_call(eng, pages, len(seq) - 1)
        rows.append(np.asarray(eng.step(*one_slot(
            eng, slot, seq[-1], pages, len(seq) - 1))[1][slot]))
    give_back(eng, pages)
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


def test_the_two_groups_are_sized_apart(params):
    eng = make_engine(params)
    assert eng.ring_pages == 3          # 7 + 1 positions, and one to spare
    assert eng.cache.window.num_pages == STREAMS * 3
    eng = make_engine(params, prefill_chunk_tokens=2 * PAGE)
    assert eng.ring_pages == 5          # 7 + 8 positions, and one to spare
    cache = eng.cache
    assert cache.num_pages == 48 and cache.window.num_pages == STREAMS * 5
    assert [b.shape[0] for b in cache.k] == [49, 16, 16, 49]
    assert cache.group_bytes() == {
        'full': 2 * 49 * PAGE * 2 * HKV * DH * 4,
        'window': 2 * 16 * PAGE * 2 * HKV * DH * 4}
    assert eng.resident_bytes() == sum(cache.group_bytes().values())
    assert [w for _n, w in cache.rows] == [HKV * DH] * 2


@pytest.mark.parametrize('n', [3, 8, 13, 29, 41])
def test_prefill_of_every_bucket(params, n):
    eng = make_engine(params, top=64)
    prompt = np.random.default_rng(n).integers(1, V, n)
    pages = claim(eng, n)
    got = eng.prefill_into(prompt, pages)
    give_back(eng, pages)
    assert rel(got, ref_logits(params, prompt)[-1]) < TOL


@pytest.mark.parametrize('seed', [1, 2])
def test_prefill_then_decode_through_the_pages(seed):
    p = make_params(seed)
    eng = make_engine(p, top=64)
    prompt = np.random.default_rng(seed).integers(1, V, 37)
    got, seq = decode(eng, prompt, 12)
    want = ref_logits(p, seq)[len(prompt) - 1:]
    assert rel(got, want) < TOL
    assert eng.cache.free_pages() == 48
    assert eng.cache.window.free_pages() == eng.cache.window.num_pages


@pytest.mark.parametrize('chunk_pages', [1, 2])
def test_chunked_prefill_then_decode(params, chunk_pages):
    eng = make_engine(params, prefill_chunk_tokens=chunk_pages * PAGE)
    prompt = np.random.default_rng(5).integers(1, V, 43)
    got, seq = decode(eng, prompt, 10)
    assert rel(got, ref_logits(params, seq)[len(prompt) - 1:]) < TOL


def test_chunked_prefill_with_carried_rows(params):
    """A chunk that carries another stream's decode rows: the chunk's
    last row and the carried rows' logits are what each gets alone."""
    eng = make_engine(params, prefill_chunk_tokens=2 * PAGE)
    rng = np.random.default_rng(7)
    a, b = rng.integers(1, V, 41), rng.integers(1, V, 21)
    pa, pb = claim(eng, 48), claim(eng, 40)
    seq = list(a) + [int(np.argmax(chunked_prefill(eng, a, pa)))]
    out = None
    for lo, hi in eng.chunk_spans(len(b)):
        # stream a decodes in slot 2 while b's chunks run
        t, pt, c = one_slot(eng, 2, seq[-1], pa, len(seq) - 1)
        out, nxt, rows = eng.prefill_chunk(b[lo:hi], pb, lo, t, pt, c)
        want = ref_logits(params, seq)[-1]
        assert rel(np.asarray(rows[2]), want) < TOL
        assert int(nxt[2]) == int(np.argmax(want))
        seq.append(int(nxt[2]))
    assert rel(out, ref_logits(params, b)[-1]) < TOL
    give_back(eng, pa)
    give_back(eng, pb)


@functools.lru_cache(maxsize=None)
def two_page_chunk_engines():
    """(the tree's engine, the same made to write a chunk's rows one at
    a time), chunks of two pages: a ring of 5 pages of 4."""
    new, old = (make_engine(make_params(0), prefill_chunk_tokens=2 * PAGE)
                for _ in range(2))
    return new, write_row_by_row(old)


@pytest.mark.parametrize('case', sorted(CHUNK_WRITE_CASES))
def test_chunk_rows_written_by_pages_leave_what_row_by_row_left(case):
    """Both page groups: a prompt of 16 tokens and more wraps the ring,
    so the last page's tail is where positions a ring back were."""
    new, old = two_page_chunk_engines()
    assert new.ring_pages == 5
    chunk_writes_match_row_by_row(new, old, V, case)


def test_a_chunk_scatters_pages_for_its_rows_and_rows_for_the_carried():
    chunk_scatters_a_page_an_update(two_page_chunk_engines()[0])


def test_a_chunk_off_the_page_grid_is_refused():
    eng = two_page_chunk_engines()[0]
    pages = claim(eng, 16)
    with pytest.raises(ValueError, match='page grid'):
        eng.prefill_chunk(np.arange(1, 4), pages, PAGE + 1)
    give_back(eng, pages)


def test_two_streams_equal_each_alone(params):
    eng = make_engine(params, top=64)
    rng = np.random.default_rng(9)
    a, b = rng.integers(1, V, 45), rng.integers(1, V, 9)
    pa, pb = claim(eng, 50), claim(eng, 14)
    la, lb = eng.prefill_into(a, pa), eng.prefill_into(b, pb)
    sa, sb = list(a) + [int(np.argmax(la))], list(b) + [int(np.argmax(lb))]
    for _ in range(4):
        pt = np.tile(eng.idle_row, (STREAMS, 1))
        pt[0], pt[2] = eng.table_row(pa), eng.table_row(pb)
        toks = np.array([sa[-1], 0, sb[-1]], np.int32)
        ctx = np.array([len(sa) - 1, 0, len(sb) - 1], np.int32)
        nxt, rows = eng.step(toks, pt, ctx)
        assert rel(np.asarray(rows[0]), ref_logits(params, sa)[-1]) < TOL
        assert rel(np.asarray(rows[2]), ref_logits(params, sb)[-1]) < TOL
        sa.append(int(nxt[0]))
        sb.append(int(nxt[2]))


class _NoWindow(LagunaBlock):
    def window_of(self, i):
        return None


class _NoGate(LagunaBlock):
    def after_attention(self, p, x, ctx, i, active):
        n = 'laguna_l%d_g_w' % i
        return LagunaBlock.after_attention(
            self, dict(p, **{n: jnp.zeros_like(p[n])}), x, 2.0 * ctx, i,
            active)     # sigmoid(0) = 1/2


class _WholeHeadTurnsOnFullLayers(LagunaBlock):
    def rotate(self, u, positions, kind):
        self.rope = dict(ROPE, full=dict(ROPE['full'], lanes=DH))
        return LagunaBlock.rotate(self, u, positions, kind)


class _KvHeadShifted(LagunaBlock):
    def qkv(self, p, x, i, positions):
        q, k, v = LagunaBlock.qkv(self, p, x, i, positions)
        return jnp.roll(q, self.heads[i] // HKV, axis=1), k, v


WRONG = {
    'the window ignored': lambda: make_block(_NoWindow),
    'the gate left out': lambda: make_block(_NoGate),
    'the whole head turned on full layers':
        lambda: make_block(_WholeHeadTurnsOnFullLayers),
    'query heads over the next K/V head': lambda: make_block(_KvHeadShifted),
    'held experts of another share': lambda: make_block(first_expert=0),
    'no scaling factor': lambda: make_block(routed_scaling_factor=1.0),
}


@pytest.mark.parametrize('wrong', sorted(WRONG))
def test_a_wrong_block_moves_the_logits(wrong):
    p = make_params(3)
    prompt = np.random.default_rng(3).integers(1, V, 41)
    want = ref_logits(p, prompt)[-1]
    pages = None
    for block, bar in ((make_block(), None), (WRONG[wrong](), 2.5e-2)):
        eng = make_engine(p, block=block, top=64)
        pages = claim(eng, 41)
        got = eng.prefill_into(prompt, pages)
        err = rel(got, want)
        assert err < TOL if bar is None else err > bar, (wrong, err)


def test_the_four_shares_add_up():
    """Every share's routed part, and the shared expert once, add up to
    the layer with all 16 experts."""
    p = make_params(4, held=ROUTER)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(11, D)), jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(11, HEADS[1], DH)), jnp.float32)
    active = jnp.ones((11,), bool)

    def share(first, held, scale=1.0):
        q = dict(p)
        for n in ('gate_w', 'up_w', 'down_w'):
            n = 'laguna_l1_' + n
            q[n] = p[n][first:first + held] * (scale if 'down' in n else 1)
        return make_block(first_expert=first).after_attention(
            q, x, ctx, 1, active)

    whole, counts = share(0, ROUTER)
    assert int(counts[-1]) == 0 and int(counts.sum()) == 11 * TOP_K
    no_routed = share(0, HELD, scale=0.0)[0]
    total, elsewhere = no_routed, 0
    for first in range(0, ROUTER, HELD):
        y, c = share(first, HELD)
        total = total + (y - no_routed)
        assert int(c.sum()) == 11 * TOP_K
        elsewhere += int(c[-1])
    assert elsewhere == 3 * 11 * TOP_K
    assert rel(np.asarray(total), np.asarray(whole)) < TOL


def test_router_matches_the_reference(params):
    h = jnp.asarray(np.random.default_rng(8).normal(size=(33, D)),
                    jnp.float32)
    w, idx = moe.moe_route(h, params['laguna_l1_router_w'], TOP_K, True,
                           SCALE)
    with jax.default_matmul_precision('highest'):
        rw, ridx, _ = ref.route(h, params['laguna_l1_router_w'], TOP_K,
                                SCALE)
    assert np.array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), SCALE, rtol=1e-5)


def _poison(eng, pages, value=jnp.nan):
    for pool in (eng.cache.k, eng.cache.v):
        for i in eng.cache.window_layers:
            pool[i] = pool[i].at[np.asarray(pages, np.int32)].set(value)


def test_nothing_behind_the_window_is_read(params):
    """Before every step, every page of the window group but the ring's
    live columns is NaN — the other streams' pages, the trash page, and
    the ring's own columns whose page lies behind the window: the
    logits do not move."""
    prompt = np.random.default_rng(12).integers(1, V, 42)

    def poison(eng, pages, ctx):
        ring = pages[1]
        live = {ring[j % len(ring)] for j in
                range(max(ctx + 1 - WINDOW, 0) // PAGE, ctx // PAGE + 1)}
        _poison(eng, [g for g in range(eng.cache.window.num_pages + 1)
                      if g not in live])

    eng = make_engine(params, prefill_chunk_tokens=2 * PAGE)
    want, seq = decode(eng, prompt, 12)
    got, seq2 = decode(make_engine(params, prefill_chunk_tokens=2 * PAGE),
                       prompt, 12, before_call=poison)
    assert seq == seq2 and np.all(np.isfinite(got))
    np.testing.assert_array_equal(got, want)
    assert eng.kv_pages['window_recycled'] == -(-(42 + 11) // PAGE) - 5


def serve(eng, prompts, n_new, poison=False):
    if poison:
        free = eng.cache.window.free

        def poisoned_free(pages):
            # a page given back is never read.  (1e30 and not NaN: a
            # chunk's last rows may be padding, and the positions only
            # they would see are unwritten in the pages a stream has
            # just claimed; every real row gives them probability 0,
            # and 0 * NaN is not 0.  Read with any weight, 1e30 moves
            # the tokens as surely.)
            _poison(eng, pages, 1e30)
            free(pages)
        eng.cache.window.free = poisoned_free
    server = DecodeServer(eng)
    try:
        streams = [server.submit(pr, max_new_tokens=n)
                   for pr, n in zip(prompts, n_new)]
        toks = [st.result(timeout=300.0) for st in streams]
        return toks, server.stats()
    finally:
        server.close()


def test_a_ring_given_back_is_claimed_again_while_others_decode(params):
    """A window group of two rings serves three streams: the third
    waits, queued, for the first to give its ring back (poisoned on the
    way), and claims it while the second still decodes.
    Tokens equal those of a run with a ring a stream, and the
    reference's greedy choice; both groups' free counts return to their
    start."""
    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, V, n) for n in (9, 30, 17)]
    n_new = (4, 30, 12)
    big = make_engine(params, prefill_chunk_tokens=2 * PAGE)
    want, _ = serve(big, prompts, n_new)
    small = make_engine(params, prefill_chunk_tokens=2 * PAGE,
                        window_pages=2 * 5)
    got, stats = serve(small, prompts, n_new, poison=True)
    assert got == want
    for pr, toks in zip(prompts, got):
        seq = list(pr)
        for tok in toks:
            assert tok == int(np.argmax(ref_logits(params, seq)[-1]))
            seq.append(tok)
    assert stats['free_pages'] == 48 and stats['window_free_pages'] == 10
    assert stats['window_pages_recycled'] > 0 and stats['dropped'] == 0
    assert stats['completed'] == 3 and stats['compiles_after_warmup'] == 0


def test_preemption_frees_both_groups(params):
    """A pool of whole-context pages too small for two streams' growth:
    one is preempted, gives back its pages AND its ring, is admitted
    again and ends with the tokens of the unconstrained run."""
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, V, 16) for _ in range(2)]
    want, _ = serve(make_engine(params, prefill_chunk_tokens=2 * PAGE),
                    prompts, (24, 24))
    eng = make_engine(params, prefill_chunk_tokens=2 * PAGE, num_pages=14)
    got, stats = serve(eng, prompts, (24, 24), poison=True)
    assert got == want
    assert stats['preempted'] >= 1 and stats['dropped'] == 0
    assert stats['free_pages'] == 14
    assert stats['window_free_pages'] == eng.cache.window.num_pages


def test_prefix_cache_over_window_layers_raises(params):
    with pytest.raises(ValueError, match='read a window'):
        make_engine(params, prefix_cache=True)


def test_one_head_count_an_engine_still_holds_for_other_blocks(params):
    from paddle_tpu.inference.blocks import OlmoeBlock
    with pytest.raises(ValueError, match='heads'):
        DecodeEngine({}, n_layers=1, n_heads=4, block=OlmoeBlock(8),
                     max_seq=32)


def build_scope(seed=3, dtype='float32'):
    scope = fluid.Scope()
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = seed
    with fluid.program_guard(main_p, startup):
        names = laguna.build_logits(
            vocab_size=V, heads=HEADS, n_kv_heads=HKV, head_dim=DH,
            d_model=D, dense_size=DENSE, router_width=ROUTER,
            n_experts=HELD, expert_size=F, dtype=dtype, init_std=0.2)
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    return scope, names


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_the_builders_weights_serve(dtype):
    """models/laguna.py's seeded parameters, by layer kind and in the
    weights' dtype, through the engine against the reference."""
    scope, names = build_scope(dtype=dtype)
    block = make_block()
    assert names == block.names(L)
    p = extract_params(scope, L, block)
    assert p['laguna_l1_q_w'].shape == (D, 6 * DH)
    assert p['laguna_l3_q_w'].shape == (D, 4 * DH)
    assert p['laguna_l1_k_w'].shape == (D, HKV * DH)
    assert p['laguna_l0_gate_w'].shape == (D, DENSE)
    assert p['laguna_l2_gate_w'].shape == (HELD, D, F)
    assert str(p['laguna_l2_gate_w'].dtype) == dtype
    assert p['laguna_l2_router_w'].dtype == jnp.float32
    assert np.all(np.asarray(p['laguna_l2_in_norm_w']) == 1.0)
    eng = make_engine(p, top=64, dtype=dtype)
    prompt = np.random.default_rng(1).integers(1, V, 37)
    got, seq = decode(eng, prompt, 4)
    want = ref_logits(p, seq)[len(prompt) - 1:]
    assert rel(got, want) < (TOL if dtype == 'float32' else 0.1)


def test_spans_counters_and_server(params, ring):
    """A server over the chunked engine: ``decode.compile`` says the
    attention, the heads and the cache by kind and the pools by group;
    steps and carried chunks count both groups' live pages beside the
    held experts' assignments."""
    eng = make_engine(params, prefill_chunk_tokens=2 * PAGE)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, V, n) for n in (5, 33, 12)]
    toks, stats = serve(eng, prompts, (6, 6, 6))
    comp = [e['args'] for e in spans(ring, 'decode.compile')]
    assert {a['program'] for a in comp} == {'chunk', 'step'}
    row = 2 * HKV * DH * 4
    for a in comp:
        assert a['heads'] == {'full': 4, 'window': 6}
        assert a['kv_heads'] == HKV and a['window'] == WINDOW
        assert a['cache_bytes_per_position'] == {'full': row, 'window': row}
        gather = {'step': 'xla_gather', 'chunk': 'xla_gather+xla_gather'
                  }[a['program']]
        assert a['attention'] == {'full': gather, 'window': gather}
        assert a['pool_bytes'] == eng.cache.group_bytes()
        assert a['alias_bytes'] == eng.resident_bytes()
    steps = spans(ring, 'decode.step') + [
        e for e in spans(ring, 'decode.prefill_chunk')
        if e['args']['step_rows']]
    assert steps and any(e['name'] == 'decode.prefill_chunk' for e in steps)
    for e in steps:
        a = e['args']
        assert a['kv_full_live_pages'] == a['kv_live_pages']
        assert 0 < a['kv_window_live_pages'] <= a['kv_full_live_pages']
        assert a['kv_window_live_pages'] <= 3 * STREAMS
        assert 0 <= a['moe_held_assignments'] <= a['moe_all_assignments']
        assert 0 <= a['moe_held_touched'] <= HELD
    # every active row routes 4 ways in each of the 3 expert layers
    for e in spans(ring, 'decode.step'):
        assert e['args']['moe_all_assignments'] % (TOP_K * 3) == 0
    assert stats['moe_all_assignments'] > stats['moe_assignments'] > 0
    assert 0 < stats['kv_window_live_pages'] < stats['kv_live_pages']


@pytest.mark.parametrize('chunk_pages', [0, 2])
def test_steps_reuse_what_the_device_holds_of_both_groups(
        params, ring, chunk_pages):
    """By hand over a table of pages AND ring: the first step uploads
    its three arrays, the steps after it none, the ring turning under
    them (context 20 to 29 over a ring of 5 or 6 pages of 4: the table
    stays, the column a position lands in is the program's arithmetic);
    a second stream's admission uploads all three.  Every row's logits
    are the reference's, and an engine that keeps nothing on the device
    gives the same bits."""
    runs = []
    for always_upload in (False, True):
        eng = make_engine(params, prefill_chunk_tokens=chunk_pages * PAGE)
        if always_upload:
            eng._hold = lambda *a: None
        rng = np.random.default_rng(33)
        first, second = rng.integers(1, V, 20), rng.integers(1, V, 6)
        pages = {0: claim(eng, 40), 2: claim(eng, 16)}
        rows = {}

        def prefill(slot, prompt):
            out = chunked_prefill(eng, prompt, pages[slot]) if eng.chunked \
                else eng.prefill_into(prompt, pages[slot])
            rows[slot] = [out]
            return list(prompt) + [int(np.argmax(out))]
        seq = {0: prefill(0, first)}
        ring.clear()
        for n in range(14):
            if n == 10:
                seq[2] = prefill(2, second)
            pt = np.tile(eng.idle_row, (STREAMS, 1))
            t, c = np.zeros(STREAMS, np.int64), np.zeros(STREAMS, np.int32)
            for i in seq:
                pt[i] = eng.table_row(pages[i])
                t[i], c[i] = seq[i][-1], len(seq[i]) - 1
            ids, logits = eng.step(t, pt, c)
            for i in seq:
                rows[i].append(np.asarray(logits[i]))
                seq[i].append(int(ids[i]))
        runs.append((host_operands(ring), rows, seq))
        if not always_upload:
            assert eng.kv_pages['window_recycled'] > 0
            assert eng.calls == {'step_calls': 14, 'step_host_operands': 6}
    (sent, rows, seq), (sent_u, rows_u, seq_u) = runs
    assert sent == [3] + [0] * 9 + [3] + [0] * 3 and sent_u == [3] * 14
    assert seq == seq_u
    for i, prompt_len in ((0, 20), (2, 6)):
        want = ref_logits(params, seq[i][:-1])[prompt_len - 1:]
        assert len(rows[i]) == len(want)
        for got, got_u, w in zip(rows[i], rows_u[i], want):
            assert np.array_equal(got, got_u) and rel(got, w) < TOL


@pytest.mark.parametrize('chunk_pages', [0, 2])
def test_a_server_reuses_and_serves_what_an_uploading_one_serves(
        params, ring, chunk_pages):
    """Three requests through the server: the ids of a server whose
    engine uploads every array every call, with fewer arrays sent; a
    chunked server claims a page a stream every fourth position, and
    sends the page tables alone there."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, V, n) for n in (5, 29, 12)]
    runs = []
    for always_upload in (False, True):
        eng = make_engine(params, prefill_chunk_tokens=chunk_pages * PAGE)
        if always_upload:
            eng._hold = lambda *a: None
        ring.clear()
        toks, stats = serve(eng, prompts, (12, 6, 9))
        runs.append((toks, stats, host_operands(ring)))
    (toks, stats, sent), (toks_u, stats_u, sent_u) = runs
    assert toks == toks_u
    assert set(sent_u) == {3} and sent[0] == 3 and 0 in sent
    assert stats['step_calls'] == len(sent) and \
        stats['step_host_operands'] == sum(sent) < 3 * len(sent)
    assert stats_u['step_host_operands'] == 3 * stats_u['step_calls']
    assert (1 in sent) is bool(chunk_pages)
    assert stats['compiles_after_warmup'] == 0

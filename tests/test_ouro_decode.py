"""A stack of layers run several times over one set of weights, served
through ``DecodeEngine`` (``OuroBlock``): the engine against the plain
float32 reference (tests/reference_ouro.py) on seeded weights at tiny
sizes on the CPU, for a whole-prompt prefill, a chunked prefill whose
chunks carry another stream's decode rows, and decoding through the
``ut_steps x layers`` cache slots; what the cache and the programs count;
the controls the comparison has to fail; the exit gate; the refusal of a
threshold under 1; and the server's pages."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import reference_ouro as ref
from paddle_tpu.inference.blocks import OuroBlock
from paddle_tpu.inference.decode import (DecodeEngine, DecodeServer,
                                         PagedKVCache)
from paddle_tpu.models.ouro import NORMS, param_names
from paddle_tpu.observability import timeline
from paddle_tpu.transpiler.memory_model import (page_pool_bytes,
                                                prefix_cached_bytes)
from test_decode_calls import (CHUNK_WRITE_CASES,
                               chunk_scatters_a_page_an_update,
                               chunk_writes_match_row_by_row,
                               write_row_by_row)

D, F, H, V = 32, 48, 4, 61
PAGE, STREAMS, PAGES, SEQ = 8, 3, 24, 64
TOL = 2e-5          # float32 against float32 at ``highest``
SHAPES = [(1, 1), (1, 3), (2, 1), (2, 3), (4, 1), (4, 3)]   # (T, L)


@functools.lru_cache(maxsize=None)
def weights(n_layers, seed=0):
    """Seeded weights under the program's names: unit embedding, norm
    weights around 1 on a branch's way in and around 0.4 on its way out,
    so that every branch and every norm is seen."""
    rng = np.random.default_rng(seed)
    normal = lambda shape, s: (s * rng.standard_normal(shape)).astype(
        np.float32)
    p = {}
    for n in param_names(n_layers):
        tail = n.split('_', 2)[-1]
        if tail in NORMS or n == 'ouro_norm_f_w':
            p[n] = (0.4 if tail.endswith('norm2_w') else 1.0) \
                + normal((D,), 0.1)
        elif n == 'ouro_embed':
            p[n] = normal((V, D), 1.0)
        elif n == 'ouro_head_w':
            p[n] = normal((D, V), 0.2)
        elif n == 'ouro_exit_w':
            p[n] = normal((D,), 0.3)
        elif n == 'ouro_exit_b':
            p[n] = normal((1,), 0.3)
        elif tail in ('gate_w', 'up_w'):
            p[n] = normal((D, F), 0.2)
        elif tail == 'down_w':
            p[n] = normal((F, D), 0.2)
        else:
            p[n] = normal((D, D), 0.2)
    return {k: jnp.asarray(v) for k, v in p.items()}


def engine(T, L, chunk=0, block=None, num_pages=PAGES):
    return DecodeEngine(
        weights(L), n_layers=L, n_heads=H,
        block=block or OuroBlock(H, ut_steps=T), page_size=PAGE,
        num_pages=num_pages, max_streams=STREAMS, max_seq=SEQ,
        prefill_bucket=SEQ, prefill_chunk_tokens=chunk,
        prefix_cache=False)


def step_operands(eng, slots):
    """``step``'s three arrays for {slot: (pages, token, ctx)}."""
    pt = np.tile(eng.idle_row, (STREAMS, 1))
    tok, ctx = np.zeros(STREAMS, np.int32), np.zeros(STREAMS, np.int32)
    for i, (pages, t, c) in slots.items():
        pt[i], tok[i], ctx[i] = eng.table_row(pages), t, c
    return tok, pt, ctx


def want(L, T, seq, **hooks):
    """The reference's (logits, exit distribution) for ``seq``, with
    some of its functions swapped (``hooks``)."""
    plain = {k: getattr(ref, k) for k in hooks}
    for k, v in hooks.items():
        setattr(ref, k, v)
    try:
        logits, g = ref.forward(weights(L), jnp.asarray(seq), L,
                                {'heads': H, 'ut_steps': T})
    finally:
        for k, v in plain.items():
            setattr(ref, k, v)
    return np.asarray(logits), np.asarray(ref.exit_distribution(g))


def rel(got, wanted):
    return float(np.max(np.abs(got - wanted)) / np.max(np.abs(wanted)))


@functools.lru_cache(maxsize=None)
def served(T, L, chunk, block=None, shared_slots=False):
    """One engine through a prompt of 37 tokens and four decode steps
    and, where it prefills in chunks, a second prompt of 21 whose
    chunks carry the first stream's decode rows.  Returns what the
    engine gave and the sequences it gave it for."""
    timeline.reset()
    eng = engine(T, L, chunk, block=block)
    if shared_slots:    # every recurrence into recurrence 0's slots
        eng.cache.shift = lambda pages, t: [p + 0 * t for p in pages]
    rng = np.random.default_rng(5)
    a, b = rng.integers(1, V, 37), rng.integers(1, V, 21)
    pages_a, pages_b = eng.cache.alloc(6), eng.cache.alloc(4)
    out = {'eng': eng}
    if chunk:
        for lo, hi in eng.chunk_spans(len(a)):
            first = eng.prefill_chunk(a[lo:hi], pages_a, lo)
    else:
        first = eng.prefill_into(a, pages_a)
    rows, toks = [np.asarray(first)], [int(np.argmax(first))]
    carried = []
    if chunk:
        # stream b's chunks, each carrying stream a's next decode row
        for lo, hi in eng.chunk_spans(len(b)):
            last_b, nxt, logits = eng.prefill_chunk(
                b[lo:hi], pages_b, lo, *step_operands(
                    eng, {1: (pages_a, toks[-1], len(a) + len(toks) - 1)}))
            rows.append(np.asarray(logits[1]))
            toks.append(int(nxt[1]))
            carried.append(len(rows) - 1)
        out['b_last'] = np.asarray(last_b)
    while len(rows) < 5:
        nxt, logits = eng.step(*step_operands(
            eng, {1: (pages_a, toks[-1], len(a) + len(toks) - 1)}))
        rows.append(np.asarray(logits[1]))
        toks.append(int(nxt[1]))
    out.update(a=a, b=b, rows=np.stack(rows), carried=carried,
               seq=np.concatenate([a, toks[:-1]]),
               spans=[e for e in timeline.ring().events(cat='span')
                      if 'id' in e])
    return out


# -- the engine against the reference -----------------------------------

@pytest.mark.parametrize('T,L', SHAPES)
def test_whole_prompt_prefill_matches_the_reference(T, L):
    s = served(T, L, 0)
    logits, _p = want(L, T, s['seq'])
    assert rel(s['rows'][0], logits[len(s['a']) - 1]) < TOL


@pytest.mark.parametrize('T,L', SHAPES)
def test_decoding_through_the_slots_matches_the_reference(T, L):
    s = served(T, L, 0)
    logits, _p = want(L, T, s['seq'])
    assert rel(s['rows'], logits[len(s['a']) - 1:]) < TOL


@pytest.mark.parametrize('T,L', SHAPES)
def test_chunked_prefill_with_carried_rows_matches_the_reference(T, L):
    s = served(T, L, 16)
    assert s['carried'] == [1, 2]       # two chunks of b, a row each
    logits, _p = want(L, T, s['seq'])
    assert rel(s['rows'], logits[len(s['a']) - 1:]) < TOL
    b_logits, _p = want(L, T, s['b'])
    assert rel(s['b_last'], b_logits[-1]) < TOL


# -- a chunk's rows reach every recurrence's slots a page at a time -------

@functools.lru_cache(maxsize=None)
def two_page_chunk_engines(T, L):
    """(the tree's engine, the same made to write a chunk's rows one at
    a time), chunks of two pages."""
    new, old = (engine(T, L, chunk=2 * PAGE) for _ in range(2))
    return new, write_row_by_row(old)


@pytest.mark.parametrize('case', sorted(CHUNK_WRITE_CASES))
def test_chunk_rows_written_by_pages_leave_what_row_by_row_left(case):
    """Under the scan, with the pools as its carry: every recurrence's
    slots, each through page ids shifted to it."""
    new, old = two_page_chunk_engines(2, 2)
    chunk_writes_match_row_by_row(new, old, V, case)


@pytest.mark.parametrize('T', [1, 2])
def test_a_chunk_scatters_pages_for_its_rows_and_rows_for_the_carried(T):
    chunk_scatters_a_page_an_update(two_page_chunk_engines(T, 2)[0])


def test_a_chunk_off_the_page_grid_is_refused():
    eng = two_page_chunk_engines(2, 2)[0]
    pages = eng.cache.alloc(2)
    with pytest.raises(ValueError, match='page grid'):
        eng.prefill_chunk(np.arange(1, 4), pages, 3)
    eng.cache.free(pages)


# -- the loop is the loop the other blocks run ----------------------------

class NoOutNorm(OuroBlock):
    def out_norm(self, y, w):
        return y


def plain_pre_norm_decoder(p, tokens, n_layers):
    """x + Attn(Norm(x)), x + FFN(Norm(x)), a final norm, the head:
    written out here, with nothing of the reference's loop."""
    with jax.default_matmul_precision('highest'):
        t = tokens.shape[0]
        x, pos = p['ouro_embed'][tokens], jnp.arange(t)
        for i in range(n_layers):
            n = 'ouro_l%d_' % i
            a = ref._rms(x, p[n + 'in_norm_w'])
            q, k, v = (a @ p[n + w] for w in ('q_w', 'k_w', 'v_w'))
            q, k = (ref._rope(u.reshape(t, H, -1), pos) for u in (q, k))
            x = x + ref._attend(q, k, v.reshape(t, H, -1)) @ p[n + 'o_w']
            m = ref._rms(x, p[n + 'post_norm_w'])
            x = x + (jax.nn.silu(m @ p[n + 'gate_w'])
                     * (m @ p[n + 'up_w'])) @ p[n + 'down_w']
        return ref._rms(x, p['ouro_norm_f_w']) @ p['ouro_head_w']


@pytest.mark.parametrize('L', [1, 3])
def test_one_recurrence_without_out_norms_is_a_plain_decoder(L):
    s = served(1, L, 16, block=NoOutNorm(H, ut_steps=1))
    logits = np.asarray(plain_pre_norm_decoder(
        weights(L), jnp.asarray(s['seq']), L))
    assert rel(s['rows'], logits[len(s['a']) - 1:]) < TOL


# -- what the cache and the programs count --------------------------------

@pytest.mark.parametrize('T,L', [(1, 3), (2, 3), (4, 3), (4, 1)])
def test_the_cache_counts_slots_and_the_weights_are_held_once(T, L):
    s = served(T, L, 16)
    eng = s['eng']
    assert eng.cache.slots == T * L and eng.cache.n_layers == L
    assert len(eng.cache.k) == len(eng.cache.v) == L
    assert all(pool.shape == (T * (PAGES + 1), PAGE, D)
               for pool in eng.cache.k + eng.cache.v)
    pool_bytes = T * L * 2 * (PAGES + 1) * PAGE * D * 4
    assert eng.cache.resident_bytes() == pool_bytes
    weight_bytes = sum(int(v.nbytes) for v in weights(L).values())
    placed, = [e['args'] for e in s['spans'] if e['name'] == 'decode.weights']
    assert placed['bytes'] == weight_bytes
    assert placed['tensors'] == len(param_names(L))
    compiles = [e['args'] for e in s['spans']
                if e['name'] == 'decode.compile']
    assert {c['program'] for c in compiles} == {'chunk', 'step'}
    for c in compiles:
        # L layers' weights once, the pools, and a call's few rows
        assert weight_bytes + pool_bytes <= c['argument_bytes'] \
            < weight_bytes + pool_bytes + 4096
        assert c['alias_bytes'] == c['pool_bytes'] == pool_bytes
        assert (c['ut_steps'], c['cache_slots'], c['weight_layers']) \
            == (T, T * L, L)
        assert c['cache_bytes_per_position'] == T * L * 2 * D * 4


def test_a_block_that_runs_its_layers_once_counts_what_it_counted():
    """``PagedKVCache.resident_bytes``, ``prefix_cached_bytes`` and
    ``stats()`` byte for byte as before the cache counted slots."""
    cache = PagedKVCache(n_layers=3, num_pages=8, page_size=4,
                         rows=(('k', 16), ('v', 16)), window_layers=(1,),
                         window_pages=5)
    assert cache.slots == 3 and cache.recurrences == 1
    assert cache.resident_bytes() \
        == 2 * 9 * 4 * 32 * 4 + 1 * 6 * 4 * 32 * 4
    assert [p.shape for p in cache.k] == [(9, 4, 16), (6, 4, 16), (9, 4, 16)]
    pages = [np.arange(3), np.arange(2)]
    assert cache.shift(pages, 0) is pages
    # an OPT engine (``OptBlock``: passes (i, i)) under a server
    import paddle_tpu as fluid
    from paddle_tpu.inference.decode import extract_params
    from paddle_tpu.models import transformer
    n, d, h, v, t = 2, 32, 4, 64, 64
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 7
        with fluid.program_guard(main, startup):
            transformer.build(vocab_size=v, seq_len=t, n_layers=n,
                              d_model=d, n_heads=h)
        fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
        eng = DecodeEngine(extract_params(scope, n), n_layers=n, n_heads=h,
                           page_size=PAGE, max_streams=4, prefill_bucket=16,
                           prefix_cache=True)
    assert not eng.looped and eng.ut_steps == 1
    server = DecodeServer(eng)
    try:
        server.submit(np.arange(1, 20), max_new_tokens=3).result(60.0)
        stats = server.stats()
    finally:
        server.close()
    n_pages = 4 * (t // PAGE) + 1
    assert stats['resident_bytes'] == n * 2 * n_pages * PAGE * d * 4 \
        == page_pool_bytes(n_pages, PAGE, h, d // h, n_layers=n)
    # the finished stream's two full pages are the trie's
    assert stats['cached_pages'] == 2
    assert stats['prefix_cached_bytes'] == n * 2 * 2 * PAGE * d * 4 \
        == prefix_cached_bytes(2, PAGE, h, d // h, n_layers=n)
    assert stats['loop_passes'] == 0


# -- the controls: what the comparison has to fail -------------------------

CONTROLS = {
    'one_recurrence': dict(ut_steps=lambda spec: 1),
    'no_closing_norm_between': dict(
        close=lambda x, w, last: ref._rms(x, w) if last else x),
    'no_out_norms': dict(out_norm=lambda y, w: y),
}


@pytest.mark.parametrize('control', sorted(CONTROLS))
def test_a_reference_told_one_thing_wrong_is_far_from_the_engine(control):
    s = served(4, 3, 16)
    logits, _p = want(3, 4, s['seq'], **CONTROLS[control])
    assert rel(s['rows'], logits[len(s['a']) - 1:]) > 0.05


def test_recurrences_sharing_a_slot_are_far_from_the_reference():
    s = served(4, 3, 16, shared_slots=True)
    logits, _p = want(3, 4, s['seq'])
    assert rel(s['rows'], logits[len(s['a']) - 1:]) > 0.05


# -- the exit gate ----------------------------------------------------------

@pytest.mark.parametrize('T', [1, 2, 4])
def test_the_spans_exit_mass_is_the_references_distribution(T):
    s = served(T, 3, 16)
    _logits, p = want(3, T, s['seq'])
    said = [e for e in s['spans'] if e['name'] in (
        'decode.step', 'decode.prefill_chunk')
        and 'loop_exit_mass' in e['args']]
    assert len(said) == 4     # two carrying chunks, two plain steps
    for j, e in enumerate(said):
        # one running row: the mean over the rows is that row's p[t]
        mass = np.asarray(e['args']['loop_exit_mass'])
        assert mass.shape == (T,) and abs(mass.sum() - 1.0) < 1e-5
        np.testing.assert_allclose(mass, p[:, len(s['a']) + j], atol=1e-5)
        assert e['args']['ut_steps'] == T
        assert e['args']['loop_passes'] == T * 3
        assert e['args']['kv_loop_live_positions'] \
            == T * (len(s['a']) + j + 1)
    if T > 1:
        assert 0.02 < float(mass[-1]) < 0.98     # a gate that ran


def test_a_threshold_under_one_is_refused_and_says_why():
    with pytest.raises(ValueError, match='only 1.0 .* leaving the loop'):
        OuroBlock(H, early_exit_threshold=0.9)
    assert OuroBlock(H, early_exit_threshold=1).ut_steps == 4


# -- the server: one table, one free list ----------------------------------

@pytest.mark.parametrize('T', [1, 4])
def test_the_server_frees_every_slots_pages_with_one_free_list(T):
    eng = engine(T, 3, 16, num_pages=9)
    assert eng.cache.groups == [eng.cache]      # one group: one free list
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, V, n) for n in (19, 23, 9)]
    server = DecodeServer(eng)
    try:
        streams = [server.submit(p, max_new_tokens=30) for p in prompts]
        got = [st.result(timeout=120.0) for st in streams]
        stats = server.stats()
    finally:
        server.close()
    # the first two grow to 6 and 7 pages side by side and 9 hold them
    # not: the pool ran out in mid-decode, a stream was preempted and
    # came back
    assert stats['preempted'] >= 1 and stats['completed'] == 3
    assert stats['free_pages'] == 9 and eng.cache.free_pages() == 9
    assert stats['resident_bytes'] == T * 3 * 2 * 10 * PAGE * D * 4
    assert stats['loop_passes'] == T * 3 * stats['step_calls']
    for prompt, toks in zip(prompts, got):
        logits, _p = want(3, T, np.concatenate([prompt, toks[:-1]]))
        assert toks == [int(t) for t in np.argmax(
            logits[len(prompt) - 1:], axis=-1)]

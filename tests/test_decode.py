"""PR-19 — autoregressive decode engine: paged KV cache parity and
continuous batching.

The numerical contract under test: a decode step served from the
paged, device-resident KV cache produces the same next-token logits
as recomputing the full context from scratch — per step, within
float32 ulp noise — including streams that join mid-decode, leave
early, and end on a ragged (partially filled) last page.  The
serving contract: continuous batching admits at step granularity,
drops nothing, and compiles nothing after warmup.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import registry
from paddle_tpu.inference.decode import (DecodeEngine, DecodeServer,
                                         PagedKVCache, decode_buckets,
                                         extract_params)
from paddle_tpu.models import transformer

from reference_opt import forward as _forward

L, D, H, V, T = 2, 32, 4, 64, 64
PAGE, STREAMS, PREFILL_TOP = 8, 4, 32
ULP_BAR = 2e-6   # f32 logits are O(1); a few ulps of reassociation


@pytest.fixture(scope='module')
def params():
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 7
        startup.random_seed = 7
        with fluid.program_guard(main, startup):
            transformer.build(vocab_size=V, seq_len=T, n_layers=L,
                              d_model=D, n_heads=H)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        return extract_params(scope, L)


@pytest.fixture(scope='module')
def engine(params):
    eng = DecodeEngine(params, n_layers=L, n_heads=H, page_size=PAGE,
                       max_streams=STREAMS, prefill_bucket=PREFILL_TOP)
    eng.warmup()
    return eng


def _ref_logits(params, tokens):
    """Full-context recompute — the engine must match this per step."""
    lg, _, _ = _forward(params, jnp.asarray([tokens], jnp.int32), L, H)
    return np.asarray(lg)[0]


def _ref_greedy(params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        toks.append(int(np.argmax(_ref_logits(params, toks)[-1])))
    return toks[len(prompt):]


def test_decode_buckets_ladder():
    assert decode_buckets(8, 32) == [8, 16, 32]
    assert decode_buckets(16, 128) == [16, 32, 64, 128]
    with pytest.raises(ValueError):
        decode_buckets(16, 40)   # top not a multiple of page size


def test_warmup_compiles_all_buckets_once(engine):
    # 3 prefill + 3 pack (one per bucket) + 1 step, never recompiled
    assert engine.buckets == [8, 16, 32]
    assert engine.compiles_total == 2 * len(engine.buckets) + 1
    engine.warmup()
    assert engine.compiles_after_warmup == 0


def test_prefill_parity_bucket_exact(params, engine):
    """A prompt that exactly fills its bucket takes the padding-free
    path.  The compiled prefill and a jit of the reference forward
    (tests/reference_opt.py) are two traces of the same equations: the
    engine's goes through the block description and computes the head
    for the last row only, the reference's computes [T, V] logits and
    takes the row, so XLA may associate the sums differently.  They
    agree to ULP_BAR; bitwise equality is asserted only between two
    of the engine's own paths."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, V, size=16).tolist()   # == bucket 16
    pages = engine.cache.alloc(-(-len(prompt) // PAGE))
    try:
        got = engine.prefill_into(np.asarray(prompt, np.int64), pages)
        ref_fn = jax.jit(lambda p, t: _forward(p, t, L, H)[0])
        ref = np.asarray(ref_fn(params,
                                jnp.asarray([prompt], jnp.int32)))[0, -1]
        assert np.max(np.abs(got - ref)) <= ULP_BAR, \
            "bucket-exact prefill is outside ULP_BAR of the reference"
    finally:
        engine.cache.free(pages)
    assert engine.compiles_after_warmup == 0


def test_decode_step_parity_ragged_last_page(params, engine):
    """Per-step logits parity on a prompt whose context straddles a
    ragged last page (len 11, page 8), decoded far enough to fill it
    and claim the next page."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, V, size=11).tolist()
    pages = engine.cache.alloc(-(-(len(prompt) + 8) // PAGE))
    logits0 = engine.prefill_into(np.asarray(prompt, np.int64), pages)
    assert np.allclose(logits0, _ref_logits(params, prompt)[-1],
                       atol=ULP_BAR)
    toks = list(prompt) + [int(np.argmax(logits0))]
    mpp = engine.pages_per_stream
    for _ in range(8):
        pt = np.full((STREAMS, mpp), engine.cache.trash, np.int32)
        pt[0, :len(pages)] = pages
        tok = np.zeros((STREAMS,), np.int64)
        tok[0] = toks[-1]
        ctx = np.zeros((STREAMS,), np.int32)
        ctx[0] = len(toks) - 1
        nxt, lg = engine.step(tok, pt, ctx)
        ref = _ref_logits(params, toks)[-1]
        assert np.max(np.abs(lg[0] - ref)) <= ULP_BAR
        assert int(nxt[0]) == int(np.argmax(ref))
        toks.append(int(nxt[0]))
    engine.cache.free(pages)
    assert engine.compiles_after_warmup == 0
    assert engine.cache.free_pages() == engine.cache.num_pages


@pytest.fixture(scope='module')
def chunked_engine(params):
    # (twice the pages the slots can hold: a chunk of 32 rows that also
    # carries a decode step gathers more than the smaller pool holds)
    eng = DecodeEngine(params, n_layers=L, n_heads=H, page_size=PAGE,
                       max_streams=STREAMS, prefill_bucket=PREFILL_TOP,
                       num_pages=2 * STREAMS * T // PAGE,
                       prefix_cache=False,
                       prefill_chunk_tokens=PREFILL_TOP)
    eng.warmup()
    return eng


@pytest.mark.parametrize('program,bucket', [
    ('step', None), ('pack', 8), ('pack', 16), ('pack', 32),
    ('chunk', 8), ('chunk', 16), ('chunk', 32)])
def test_pool_updated_in_place(engine, chunked_engine, program, bucket):
    """Every program that writes the KV pool aliases all of it to its
    outputs and declares no scratch of a pool's size: a pack under one
    layer's K buffer, a step under what its attention gathers and
    scores, a chunk (which carries a step's rows) under both its own and
    the step's.  CPU layouts are not the chip's: this guards the
    structure (the parent's pack declared two whole pools here), the
    chip's trace is the proof."""
    eng = chunked_engine if program == 'chunk' else engine
    compiled = eng._step if program == 'step' else \
        getattr(eng, '_' + program)[bucket]
    mem = compiled.memory_analysis()
    pool = eng.resident_bytes()
    assert mem.alias_size_in_bytes == pool
    item = eng.cache.dtype.itemsize
    span = 2 * eng.max_seq * eng.d_model * item   # one slot's K and V
    if program == 'pack':
        limit = pool // (2 * L)
    elif program == 'step':
        limit = 1.1 * eng.max_streams * span
    else:   # a chunk also holds its rows' scores and probabilities,
        # and what the decode rows it carries gather
        limit = 1.1 * ((1 + eng.max_streams) * span
                       + 2 * bucket * H * eng.max_seq * 4)
    assert mem.temp_size_in_bytes < limit < pool


def _pool_rows(cache):
    """The pools as numpy [L, pages + 1, P, H * Dh], K and V."""
    return (np.stack([np.asarray(x) for x in cache.k]),
            np.stack([np.asarray(x) for x in cache.v]))


def test_pool_placement_exact(params, engine):
    """Where the writers put K/V: after two prefills and six steps with
    slots 1 and 3 inactive, a prompt position holds bit-for-bit what
    the prefill program returned, a decoded position the K/V of the
    full-context forward, an unclaimed page what it held before, and
    only the trash page took the inactive slots' and the padding's
    writes; a second warm-up then changes no resident page."""
    rng = np.random.default_rng(31)
    cache, mpp, n_steps = engine.cache, engine.pages_per_stream, 6
    prompts = {0: rng.integers(0, V, size=11), 2: rng.integers(0, V, size=5)}
    for pools in (cache.k, cache.v):    # a mark the trash page can lose
        pools[:] = [x.at[cache.trash].set(7.0) for x in pools]
    k0, v0 = _pool_rows(cache)
    pages, toks, want = {}, {}, {}
    for slot, prompt in prompts.items():
        pages[slot] = cache.alloc(-(-(len(prompt) + n_steps) // PAGE))
        logits = engine.prefill_into(prompt, pages[slot])
        toks[slot] = list(prompt) + [int(np.argmax(logits))]
        bucket = engine.bucket_for(len(prompt))
        padded = np.zeros((bucket,), np.int32)
        padded[:len(prompt)] = prompt
        _, k, v = engine._prefill[bucket](
            engine.params, jnp.asarray(padded), jnp.int32(len(prompt) - 1))
        want[slot] = [np.asarray(x).reshape(L, bucket, D) for x in (k, v)]
    for _ in range(n_steps):
        pt = np.full((STREAMS, mpp), cache.trash, np.int32)
        tok = np.zeros((STREAMS,), np.int64)
        ctx = np.zeros((STREAMS,), np.int32)
        for slot in prompts:
            pt[slot, :len(pages[slot])] = pages[slot]
            tok[slot] = toks[slot][-1]
            ctx[slot] = len(toks[slot]) - 1
        nxt, _ = engine.step(tok, pt, ctx)
        for slot in prompts:
            toks[slot].append(int(nxt[slot]))
    k1, v1 = _pool_rows(cache)
    claimed = sorted(p for ps in pages.values() for p in ps)
    for slot, prompt in prompts.items():
        cached = toks[slot][:-1]     # the last token is not cached yet
        _, k_ref, v_ref = _forward(
            params, jnp.asarray([cached], jnp.int32), L, H)
        refs = [np.asarray(r)[:, 0].reshape(L, len(cached), D)
                for r in (k_ref, v_ref)]
        for pool, before, packed, ref in zip((k1, v1), (k0, v0),
                                             want[slot], refs):
            for pos in range(len(cached)):
                got = pool[:, pages[slot][pos // PAGE], pos % PAGE]
                if pos < len(prompt):
                    assert np.array_equal(got, packed[:, pos]), (slot, pos)
                assert np.max(np.abs(got - ref[:, pos])) <= ULP_BAR, \
                    (slot, pos)
            # the claimed span past the context is as it was
            for pos in range(len(cached), len(pages[slot]) * PAGE):
                page, off = pages[slot][pos // PAGE], pos % PAGE
                assert np.array_equal(pool[:, page, off],
                                      before[:, page, off])
    others = [p for p in range(cache.num_pages) if p not in claimed]
    assert np.array_equal(k1[:, others], k0[:, others])
    assert np.array_equal(v1[:, others], v0[:, others])
    # an inactive slot writes its row at offset 0 of the trash page
    for pool in (k1, v1):
        assert not np.any(pool[:, cache.trash, 0] == 7.0)
        assert np.all(pool[:, cache.trash, 1:] == 7.0)
    engine._compiles_at_warmup = None       # make warmup() run again
    engine.warmup()
    k2, v2 = _pool_rows(cache)
    resident = list(range(cache.num_pages))
    assert np.array_equal(k2[:, resident], k1[:, resident])
    assert np.array_equal(v2[:, resident], v1[:, resident])
    for ps in pages.values():
        cache.free(ps)
    assert engine.compiles_after_warmup == 0


def test_paged_attention_op_matches_contiguous(params):
    """The registered paged_attention op, reading KV through a
    shuffled page table, matches attention over the same KV laid out
    contiguously."""
    rng = np.random.default_rng(11)
    s, h, d, p, n = 3, 2, 8, 4, 16
    mpp = 4
    q = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((n + 1, p, h, d)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((n + 1, p, h, d)),
                         jnp.float32)
    pt = np.asarray([[7, 2, 9, 16], [0, 5, 16, 16], [3, 1, 4, 12]],
                    np.int32)
    ctx = np.asarray([13, 6, 16], np.int32)
    impl = registry.get_op_impl('paged_attention')
    out = impl.compute(None, {'Q': [q], 'KPool': [k_pool],
                              'VPool': [v_pool],
                              'PT': [jnp.asarray(pt)],
                              'CtxLen': [jnp.asarray(ctx)]},
                       {})['Out'][0]
    scale = d ** -0.5
    for i in range(s):
        kv_idx = [int(page) for page in pt[i]]
        k = np.asarray(k_pool)[kv_idx].reshape(mpp * p, h, d)[:ctx[i]]
        v = np.asarray(v_pool)[kv_idx].reshape(mpp * p, h, d)[:ctx[i]]
        sc = np.einsum('hd,thd->ht', np.asarray(q)[i], k) * scale
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        ref = np.einsum('ht,thd->hd', pr, v)
        assert np.allclose(np.asarray(out)[i], ref, atol=1e-5)


def test_page_pool_accounting():
    cache = PagedKVCache(n_layers=1, num_pages=6, page_size=4,
                         n_heads=2, head_dim=8)
    assert cache.free_pages() == 6
    a = cache.alloc(4)
    b = cache.alloc(2)
    assert len(a) == 4 and len(b) == 2 and not set(a) & set(b)
    assert cache.trash not in a + b        # trash page never handed out
    assert cache.alloc(1) is None          # exhausted: refuse, don't drop
    assert cache.free_pages() == 0
    cache.free(a)
    assert cache.free_pages() == 4
    cache.free(b)
    assert sorted(cache.alloc(6)) == sorted(a + b)


def test_server_continuous_batching_mid_decode_joins(params, engine):
    """Streams of mixed lengths join mid-decode at step granularity;
    every stream's greedy tokens match its own full-context recompute
    (no cross-stream contamination), nothing drops, nothing compiles."""
    srv = DecodeServer(engine)
    rng = np.random.default_rng(17)
    plens = [5, 11, 17, 23, 8, 30]
    prompts = [rng.integers(0, V, size=n).tolist() for n in plens]
    streams = []
    try:
        for p in prompts:
            streams.append(srv.submit(np.asarray(p, np.int64),
                                      max_new_tokens=6))
            time.sleep(0.002)   # stagger → joins land mid-decode
        assert srv.drain(timeout=120.0)
        for p, st in zip(prompts, streams):
            got = list(st.result(timeout=5.0))
            assert got == _ref_greedy(params, p, 6), \
                "stream isolation broken for prompt len %d" % len(p)
            assert st.ttft_s is not None and st.ttft_s >= 0.0
            assert len(st.per_token_s()) == 5
        stats = srv.stats()
        assert stats['completed'] == 6
        assert stats['dropped'] == 0
        assert stats['compiles_after_warmup'] == 0
        assert stats['free_pages'] == engine.cache.num_pages
        assert stats['active_streams'] == 0 and stats['queued'] == 0
    finally:
        srv.close()


def test_server_static_batching_baseline(params, engine):
    """The ablation baseline (generation-batch style: admit only when
    every slot is empty) still produces correct tokens — it is slower,
    not wrong."""
    srv = DecodeServer(engine, static_batching=True)
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, V, size=n).tolist() for n in (6, 13, 9)]
    try:
        streams = [srv.submit(np.asarray(p, np.int64), max_new_tokens=4)
                   for p in prompts]
        assert srv.drain(timeout=120.0)
        for p, st in zip(prompts, streams):
            assert list(st.result(timeout=5.0)) == _ref_greedy(params, p, 4)
        stats = srv.stats()
        assert stats['static_batching'] is True
        assert stats['dropped'] == 0
        assert stats['compiles_after_warmup'] == 0
    finally:
        srv.close()


@pytest.fixture(scope='module')
def page_chunks_engine(params):
    """Chunked prefill, a page of prompt a tick."""
    eng = DecodeEngine(params, n_layers=L, n_heads=H, page_size=PAGE,
                       max_streams=STREAMS, prefill_bucket=PREFILL_TOP,
                       prefix_cache=False, prefill_chunk_tokens=PAGE)
    eng.warmup()
    return eng


def test_chunk_carrying_decode_rows_matches_full_context(
        params, page_chunks_engine):
    """A prefill chunk handed a decode step's operands runs that step's
    rows in the same pass: the running stream's logits are the
    full-context recompute's at every one of the prompt's chunks, and
    the prompt's last-row logits are its own recompute's."""
    eng = page_chunks_engine
    rng = np.random.default_rng(37)
    running = rng.integers(0, V, size=11).tolist()
    prompt = rng.integers(0, V, size=29)
    pages, mine = eng.cache.alloc(3), eng.cache.alloc(4)
    for lo, hi in eng.chunk_spans(len(running)):
        first = eng.prefill_chunk(running[lo:hi], pages, lo)
    assert np.max(np.abs(first - _ref_logits(params, running)[-1])) \
        <= ULP_BAR
    toks = running + [int(np.argmax(first))]
    pt = np.full((STREAMS, eng.pages_per_stream), eng.cache.trash, np.int32)
    pt[3, :len(pages)] = pages
    for lo, hi in eng.chunk_spans(len(prompt)):
        tok, ctx = np.zeros(STREAMS, np.int32), np.zeros(STREAMS, np.int32)
        tok[3], ctx[3] = toks[-1], len(toks) - 1
        last, nxt, logits = eng.prefill_chunk(prompt[lo:hi], mine, lo,
                                              tok, pt, ctx)
        ref = _ref_logits(params, toks)[-1]
        assert np.max(np.abs(np.asarray(logits)[3] - ref)) <= ULP_BAR
        assert int(nxt[3]) == int(np.argmax(ref))
        toks.append(int(nxt[3]))
    assert np.max(np.abs(last - _ref_logits(params, prompt.tolist())[-1])) \
        <= ULP_BAR
    eng.cache.free(pages + mine)
    assert eng.compiles_after_warmup == 0
    assert eng.cache.free_pages() == eng.cache.num_pages


def test_server_chunked_prefill_rides_with_decode(params, hold_steps,
                                                  page_chunks_engine):
    """Prompts that arrive while others decode are prefilled a chunk a
    tick, each chunk carrying the running streams' decode step: every
    stream still generates its own full-context recompute's tokens, the
    counters add up, nothing compiles."""
    eng = page_chunks_engine
    rng = np.random.default_rng(39)
    prompts = [rng.integers(0, V, size=n).tolist()
               for n in (9, 21, 5, 30, 14)]
    n_new = (10, 3, 4, 2, 3)
    others_sent = hold_steps(eng)
    srv = DecodeServer(eng)
    try:
        streams = [srv.submit(np.asarray(prompts[0], np.int64),
                              max_new_tokens=n_new[0])]
        while not streams[0].tokens:
            streams[0]._done.wait(0.001)
        for p, n in zip(prompts[1:], n_new[1:]):
            streams.append(srv.submit(np.asarray(p, np.int64),
                                      max_new_tokens=n))
        others_sent()
        assert srv.drain(timeout=120.0)
        for p, n, st in zip(prompts, n_new, streams):
            assert list(st.result(timeout=5.0)) == _ref_greedy(params, p, n)
            assert len(st.per_token_s()) == n - 1
        stats = srv.stats()
        assert stats['prefill_chunks'] == 2 + 3 + 1 + 4 + 2
        # the three prompts admitted beside the first (four slots) find
        # it decoding: at least their first chunks carry its row
        assert 3 <= stats['prefill_chunks_carrying'] \
            <= stats['prefill_chunks']
        assert stats['carried_rows'] >= stats['prefill_chunks_carrying']
        assert stats['carried_rows'] <= STREAMS * stats['prefill_chunks']
        assert stats['generated_tokens'] == sum(n_new)
        assert stats['completed'] == 5 and stats['dropped'] == 0
        assert stats['compiles_after_warmup'] == 0
        assert stats['free_pages'] == eng.cache.num_pages
    finally:
        srv.close()


def test_submit_rejects_oversized(engine):
    srv = DecodeServer(engine, warmup=False)
    try:
        with pytest.raises(ValueError):
            srv.submit(np.zeros((T + 1,), np.int64), max_new_tokens=1)
        with pytest.raises(ValueError):
            # prompt fits, but prompt+new overruns the model context
            srv.submit(np.zeros((30,), np.int64), max_new_tokens=T)
    finally:
        srv.close()


def test_fleet_attach_decode(params, engine, tmp_path):
    """The decode server rides the ServingFleet (the ISSUE-19 wiring):
    ``generate()`` routes to it, its KV pools + weights join the
    fleet residency aggregate, ``stats()`` carries its snapshot, and
    an enforcing HBM budget with no decode headroom rejects the
    attach with the typed admission error — nothing attached."""
    from paddle_tpu.inference import (AdmissionError, ServingFleet,
                                      export_bucketed)
    from paddle_tpu.inference.fleet import _decode_resident

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        pred = fluid.layers.fc(input=x, size=3)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    vdir = str(tmp_path / 'v1')
    export_bucketed(vdir, {'x': (4,)}, [pred], executor=exe,
                    main_program=main, scope=scope, max_batch=2)

    kw = dict(replicas=1, health_interval_ms=0, max_wait_ms=20.0,
              linger_ms=0.5)
    fleet = ServingFleet(vdir, **kw)
    try:
        base = fleet.stats()['resident_bytes']
        srv = DecodeServer(engine)
        fleet.attach_decode(srv)
        need = _decode_resident(srv)
        assert need > engine.resident_bytes() > 0
        st = fleet.stats()
        assert st['resident_bytes'] == base + need
        assert st['resident_bytes_watermark'] >= base + need
        assert 'default' in st['decode']
        assert st['decode']['default']['dropped'] == 0
        rng = np.random.default_rng(29)
        p = rng.integers(0, V, size=9).tolist()
        stream = fleet.generate(np.asarray(p, np.int64),
                                max_new_tokens=4)
        assert list(stream.result(timeout=60.0)) \
            == _ref_greedy(params, p, 4)
        with pytest.raises(ValueError, match='already has a decode'):
            fleet.attach_decode(srv)
        with pytest.raises(ValueError, match='no decode server'):
            fleet.generate([1], tenant='ghost')
    finally:
        fleet.close()

    # no headroom for the pools under enforce: typed rejection,
    # nothing attached, generate() still refuses
    fleet = ServingFleet(vdir, hbm_admission='enforce',
                         hbm_budget_bytes=base + 1000, **kw)
    srv = DecodeServer(engine, warmup=False)
    try:
        with pytest.raises(AdmissionError) as exc:
            fleet.attach_decode(srv)
        assert exc.value.incoming_bytes == _decode_resident(srv)
        assert fleet.stats()['decode'] == {}
        with pytest.raises(ValueError, match='no decode server'):
            fleet.generate([1])
    finally:
        srv.close()
        fleet.close()

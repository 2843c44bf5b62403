"""PR-20 — prefix-cached KV page reuse + chunked prefill scheduling.

The reuse contract under test: a stream whose prompt hits a cached
prefix claims the SAME physical pages a cold stream would have
computed, and because chunked prefill runs on an absolute position
grid, the hit's tail chunks are an exact suffix of the cold chunk
list — so hit-vs-cold prefill logits and generated tokens agree
BITWISE, partial-page tails and mid-decode joins included.  The
safety contract: eviction under pool pressure never frees a
referenced page, refcounts round-trip to zero on retire, and
incremental allocation preempts (requeue + recompute) instead of
wedging on exhaustion.  The compatibility contract: with the prefix
cache off and chunking off, the engine is the PR-19 monolithic path
verbatim.
"""
import time

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.inference.decode import (DecodeEngine, DecodeServer,
                                         PrefixCache,
                                         PromptTooLongError,
                                         extract_params)
from paddle_tpu.models import transformer

from reference_opt import forward as _forward

L, D, H, V, T = 2, 32, 4, 64, 64
PAGE, STREAMS, PREFILL_TOP = 8, 4, 32
ULP_BAR = 2e-6


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
def prefix_engine(params):
    eng = DecodeEngine(params, n_layers=L, n_heads=H, page_size=PAGE,
                       max_streams=STREAMS,
                       prefill_bucket=PREFILL_TOP, prefix_cache=True)
    eng.warmup()
    return eng


def _ref_logits(params, tokens):
    lg, _, _ = _forward(params, jnp.asarray([tokens], jnp.int32), L, H)
    return np.asarray(lg)[0]


def _ref_greedy(params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        toks.append(int(np.argmax(_ref_logits(params, toks)[-1])))
    return toks[len(prompt):]


def _trie_refs(prefix):
    """Every node's refcount, flattened."""
    refs, stack = [], list(prefix._root.children.values())
    while stack:
        n = stack.pop()
        refs.append(n.refs)
        stack.extend(n.children.values())
    return refs


def _run_chunks(eng, prompt, pages, start):
    """Drive the chunk executables over [start, len(prompt)) exactly
    as the worker does; returns the final chunk's logits."""
    logits = None
    for lo, hi in eng.chunk_spans(len(prompt), start=start):
        logits = eng.prefill_chunk(prompt[lo:hi], pages, lo)
    return logits


def test_disabled_flags_pin_pr19_path(params):
    """PADDLE_TPU_DECODE_PREFIX_CACHE=0 + chunking off IS the PR-19
    engine: monolithic prefill executables, no chunk executables, no
    trie, whole-span page claim at admission."""
    eng = DecodeEngine(params, n_layers=L, n_heads=H, page_size=PAGE,
                       max_streams=STREAMS,
                       prefill_bucket=PREFILL_TOP)
    assert eng.chunked is False and eng.prefix is None
    assert eng.chunk_grid is None and eng.chunk_buckets == []
    eng.warmup()
    assert eng._chunk == {} and len(eng._prefill) == len(eng.buckets)
    # same compile census PR-19 pinned: prefill + pack per bucket + step
    assert eng.compiles_total == 2 * len(eng.buckets) + 1
    srv = DecodeServer(eng)
    try:
        st = srv.submit(np.arange(11, dtype=np.int64), max_new_tokens=5)
        assert st.result(timeout=60.0) == _ref_greedy(
            params, list(range(11)), 5)
        # whole-span claim (not incremental), returned in full
        stats = srv.stats()
        assert stats['prefix_cache'] is False
        assert stats['chunked_prefill'] is False
        assert stats['prefill_chunks'] == 0
        assert stats['free_pages'] == eng.cache.num_pages
        assert stats['compiles_after_warmup'] == 0
    finally:
        srv.close()


def test_prefix_hit_bitwise_vs_cold_partial_tail(params, prefix_engine):
    """The tentpole's numerical core: prefill from a cached prefix is
    the SAME execution suffix as cold prefill — logits bitwise equal,
    on a prompt with a ragged (partial-page) tail."""
    eng = prefix_engine
    rng = np.random.default_rng(41)
    prompt = rng.integers(0, V, size=21).astype(np.int32)  # 2 full + tail
    cold_pages = eng.cache.alloc(3)
    tail_pages = eng.cache.alloc(1)
    try:
        cold = _run_chunks(eng, prompt, cold_pages, start=0)
        # hit: positions [0, 16) served by the cold run's pages, tail
        # recomputed into a DIFFERENT physical page
        hit_pt = list(cold_pages[:2]) + list(tail_pages)
        hit = _run_chunks(eng, prompt, hit_pt, start=16)
        assert np.array_equal(cold, hit), \
            "prefix-hit prefill is not bitwise vs cold"
        assert np.max(np.abs(cold - _ref_logits(params, prompt)[-1])) \
            <= ULP_BAR
    finally:
        eng.cache.free(cold_pages)
        eng.cache.free(tail_pages)
    assert eng.compiles_after_warmup == 0


def test_server_hit_tokens_match_cold_and_reference(params,
                                                    prefix_engine):
    """End to end: the second stream with an identical prompt hits the
    trie (zero prefill MACs for the shared span) and generates exactly
    the cold stream's tokens; a third stream sharing only one page
    also matches its own recompute."""
    eng = prefix_engine
    srv = DecodeServer(eng)
    rng = np.random.default_rng(43)
    prompt = rng.integers(0, V, size=20).tolist()
    sibling = prompt[:8] + rng.integers(0, V, size=9).tolist()
    try:
        cold = srv.submit(np.asarray(prompt, np.int64),
                          max_new_tokens=6)
        cold_toks = cold.result(timeout=60.0)
        h0 = srv.stats()['prefix_hit_tokens']
        hit = srv.submit(np.asarray(prompt, np.int64),
                         max_new_tokens=6)
        sib = srv.submit(np.asarray(sibling, np.int64),
                         max_new_tokens=6)
        assert hit.result(timeout=60.0) == cold_toks
        assert sib.result(timeout=60.0) == _ref_greedy(
            params, sibling, 6)
        assert cold_toks == _ref_greedy(params, prompt, 6)
        stats = srv.stats()
        # identical prompt: 16 of 20 tokens cached (grid-capped at
        # t-1); sibling: first page at minimum
        assert stats['prefix_hit_tokens'] - h0 >= 16 + 8
        assert stats['compiles_after_warmup'] == 0
        assert stats['dropped'] == 0
        # refcount round-trip: every retired stream released its refs
        assert all(r == 0 for r in _trie_refs(eng.prefix))
        assert stats['cached_pages'] > 0
        assert stats['prefix_cached_bytes'] > 0
        # shared pages counted once: the trie subset is inside the
        # pool residency, never on top of it
        assert stats['prefix_cached_bytes'] < stats['resident_bytes']
    finally:
        srv.close()


def test_mid_decode_join_on_shared_prefix(params, prefix_engine):
    """A stream submitted while the donor is still DECODING hits the
    donor's prompt pages (published at prefill-complete) and both
    match the full-context recompute."""
    eng = prefix_engine
    srv = DecodeServer(eng)
    rng = np.random.default_rng(47)
    prompt = rng.integers(0, V, size=17).tolist()
    try:
        donor = srv.submit(np.asarray(prompt, np.int64),
                           max_new_tokens=20)
        deadline = time.perf_counter() + 60.0
        while not donor.tokens and time.perf_counter() < deadline:
            time.sleep(0.001)   # wait for prefill-complete publish
        assert donor.tokens, "donor never finished prefill"
        h0 = srv.stats()['prefix_hit_tokens']
        joiner = srv.submit(np.asarray(prompt, np.int64),
                            max_new_tokens=6)
        ref = _ref_greedy(params, prompt, 20)
        assert donor.result(timeout=60.0) == ref
        assert joiner.result(timeout=60.0) == ref[:6]
        assert srv.stats()['prefix_hit_tokens'] - h0 >= 16
        assert all(r == 0 for r in _trie_refs(eng.prefix))
        assert srv.stats()['compiles_after_warmup'] == 0
    finally:
        srv.close()


def test_only_a_ticks_last_chunk_carries_the_decode_rows(params, hold_steps):
    """With no per-tick budget (the prefix cache's default) a tick runs
    every pending chunk; the decode step of the streams already running
    rides the LAST of them, and a hit submitted mid-decode still gives
    the cold stream's tokens."""
    from paddle_tpu.observability import timeline
    eng = DecodeEngine(params, n_layers=L, n_heads=H, page_size=PAGE,
                       max_streams=STREAMS,
                       prefill_bucket=PREFILL_TOP, prefix_cache=True)
    assert eng.chunk_tokens == 0 and eng.chunk_grid == PAGE
    others_sent = hold_steps(eng)    # the donor's first step waits
    srv = DecodeServer(eng)
    rng = np.random.default_rng(49)
    prompt = rng.integers(0, V, size=27).tolist()
    other = rng.integers(0, V, size=22).tolist()
    timeline.reset()
    try:
        donor = srv.submit(np.asarray(prompt, np.int64), max_new_tokens=8)
        while not donor.tokens:
            donor._done.wait(0.001)
        hit = srv.submit(np.asarray(prompt, np.int64), max_new_tokens=3)
        cold = srv.submit(np.asarray(other, np.int64), max_new_tokens=3)
        others_sent()
        ref = _ref_greedy(params, prompt, 8)
        assert donor.result(timeout=60.0) == ref
        assert hit.result(timeout=60.0) == ref[:3]
        assert cold.result(timeout=60.0) == _ref_greedy(params, other, 3)
        stats = srv.stats()
    finally:
        srv.close()
    evs = [e for e in timeline.ring().events(cat='span') if 'id' in e]
    timeline.reset()
    by_id = {e['id']: e for e in evs}
    per_tick = {}
    for e in evs:
        if e['name'] == 'decode.prefill_chunk':
            tick = by_id[by_id[e['parent']]['parent']]
            per_tick.setdefault(tick['id'], []).append(e)
    # the donor's four chunks in one tick, none carrying (nothing ran);
    # then the hit's tail and the cold prompt's three in one tick
    counts = sorted(len(v) for v in per_tick.values())
    assert counts[-1] >= 3 and sum(counts) == stats['prefill_chunks']
    for chunks in per_tick.values():
        chunks.sort(key=lambda e: e['ts'])
        assert all(e['args']['step_rows'] == 0 for e in chunks[:-1])
    assert stats['prefill_chunks_carrying'] == sum(
        1 for v in per_tick.values() if v[-1]['args']['step_rows'])
    assert stats['prefill_chunks_carrying'] >= 1
    assert stats['compiles_after_warmup'] == 0
    assert all(r == 0 for r in _trie_refs(eng.prefix))


def test_eviction_never_frees_referenced_pages():
    """PrefixCache unit contract: LRU eviction only touches
    unreferenced leaves; releasing refs makes pages reclaimable
    (refcount round-trip), deepest-first."""
    pc = PrefixCache(page_size=4)
    toks = list(range(12))
    nodes, adopted = pc.insert(toks, [10, 11, 12], acquire=True)
    assert adopted == [0, 1, 2] and pc.cached_pages == 3
    assert [n.refs for n in nodes] == [1, 1, 1]
    # everything referenced: pressure evicts NOTHING
    assert pc.evict(3) == [] and pc.cached_pages == 3
    # a second holder, then a full release by the first
    pages, held = pc.match(toks)
    assert pages == [10, 11, 12] and [n.refs for n in held] == [2, 2, 2]
    pc.release(nodes)
    assert pc.evict(3) == []      # still held by the second match
    pc.release(held)
    assert all(r == 0 for r in _trie_refs(pc))
    # now reclaimable, leaves first (an interior page never frees
    # while a descendant exists)
    assert pc.evict(2) == [12, 11]
    assert pc.evict(5) == [10] and pc.cached_pages == 0
    # dedup: inserting an already-cached page is a skip, not an adopt
    pc.insert(toks[:4], [20])
    nodes2, adopted2 = pc.insert(toks, [21, 22, 23])
    assert adopted2 == [1, 2]     # page 21 NOT adopted: caller keeps it
    assert nodes2[0].page == 20


def test_chunked_parity_vs_monolithic_every_ladder_size(params):
    """Chunked prefill at every chunk size in the bucket ladder lands
    within ulps of the monolithic bucket prefill, and the greedy
    tokens are identical."""
    mono = DecodeEngine(params, n_layers=L, n_heads=H, page_size=PAGE,
                        max_streams=STREAMS,
                        prefill_bucket=PREFILL_TOP)
    mono.warmup()
    rng = np.random.default_rng(53)
    prompt = rng.integers(0, V, size=27).astype(np.int32)  # ragged
    pages = mono.cache.alloc(4)
    ref = mono.prefill_into(prompt, pages)
    mono.cache.free(pages)
    ref_toks = _ref_greedy(params, prompt.tolist(), 5)
    for chunk in mono.buckets:                    # [8, 16, 32]
        eng = DecodeEngine(params, n_layers=L, n_heads=H,
                           page_size=PAGE, max_streams=STREAMS,
                           prefill_bucket=PREFILL_TOP,
                           prefill_chunk_tokens=chunk)
        assert eng.chunked and eng.prefix is None
        assert eng.chunk_grid == chunk
        eng.warmup()
        pages = eng.cache.alloc(4)
        got = _run_chunks(eng, prompt, pages, start=0)
        eng.cache.free(pages)
        assert np.max(np.abs(got - ref)) <= ULP_BAR, \
            "chunk size %d drifted from monolithic prefill" % chunk
        srv = DecodeServer(eng)
        try:
            st = srv.submit(np.asarray(prompt, np.int64),
                            max_new_tokens=5)
            assert st.result(timeout=60.0) == ref_toks
            assert srv.stats()['prefill_chunks'] >= 1
            assert srv.stats()['compiles_after_warmup'] == 0
        finally:
            srv.close()


def test_submit_prompt_too_long_typed(params, prefix_engine):
    """Oversize prompts fail FAST in the submitting thread with the
    typed error (a ValueError subclass, so pre-existing handlers keep
    working); the chunked path has no top-bucket ceiling."""
    mono = DecodeEngine(params, n_layers=L, n_heads=H, page_size=PAGE,
                        max_streams=STREAMS,
                        prefill_bucket=PREFILL_TOP)
    srv = DecodeServer(mono, warmup=False)
    try:
        # over the top bucket but under max_seq: monolithic rejects...
        with pytest.raises(PromptTooLongError):
            srv.submit(np.zeros((PREFILL_TOP + 1,), np.int64),
                       max_new_tokens=1)
        with pytest.raises(PromptTooLongError):
            srv.submit(np.zeros((30,), np.int64), max_new_tokens=T)
        assert issubclass(PromptTooLongError, ValueError)
        assert srv.stats()['submitted'] == 0
    finally:
        srv.close()
    # ...while the chunked engine serves it (chunks cover any prompt
    # up to the model context)
    srv = DecodeServer(prefix_engine)
    rng = np.random.default_rng(59)
    long_prompt = rng.integers(0, V, size=PREFILL_TOP + 8).tolist()
    try:
        st = srv.submit(np.asarray(long_prompt, np.int64),
                        max_new_tokens=4)
        assert st.result(timeout=60.0) == _ref_greedy(
            params, long_prompt, 4)
        with pytest.raises(PromptTooLongError):
            srv.submit(np.zeros((T + 1,), np.int64), max_new_tokens=1)
    finally:
        srv.close()


def test_incremental_alloc_preempts_and_recovers(params):
    """A pool too small for every stream's whole span still serves
    all of them: admission claims only the prompt tail, decode grows
    claim-as-context-grows, and on exhaustion a stream preempts
    (requeue + deterministic recompute) instead of wedging — with
    tokens identical to the unconstrained run."""
    eng = DecodeEngine(params, n_layers=L, n_heads=H, page_size=PAGE,
                       max_streams=2, num_pages=7,
                       prefill_bucket=PREFILL_TOP,
                       prefill_chunk_tokens=PAGE)
    eng.warmup()
    srv = DecodeServer(eng)
    rng = np.random.default_rng(61)
    prompts = [rng.integers(0, V, size=16).tolist() for _ in range(2)]
    try:
        # each span = 16 + 24 = 40 tokens = 5 pages; two concurrent
        # streams want 10 of the pool's 7 — growth must collide
        streams = [srv.submit(np.asarray(p, np.int64),
                              max_new_tokens=24) for p in prompts]
        assert srv.drain(timeout=120.0)
        for p, st in zip(prompts, streams):
            assert list(st.result(timeout=5.0)) == _ref_greedy(
                params, p, 24), "preemption changed the generation"
        stats = srv.stats()
        assert stats['preempted'] >= 1
        assert stats['dropped'] == 0
        assert stats['free_pages'] == eng.cache.num_pages
        assert stats['compiles_after_warmup'] == 0
    finally:
        srv.close()

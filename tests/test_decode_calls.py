"""What an engine call takes from the host and hands back to it, under
every block description at toy size (the widths, weights and references
of tests/test_olmoe_decode.py and tests/test_dots_vlm_decode.py): the
host's numpy arrays go into the executable as they are, ONE copy brings
back what the caller reads (next-token ids, routing counts, a prompt's
last row: the span's ``fetched_bytes``), the decode rows' ``[S, V]``
logits stay on the device, and nothing of that compiles a program after
``warmup()``.  Of a step's three arrays, a call uploads those the device
does not already hold (the span's ``host_operands``): the program sees
bit for bit what the host holds either way.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference.blocks import OptBlock
from paddle_tpu.inference.decode import (DecodeEngine, DecodeServer,
                                         DecodeStream)
from paddle_tpu.observability import timeline

import reference_dots_vlm
import test_dots_vlm_decode as dots_t
import test_olmoe_decode as olmoe_t

# what chipbench's harness.CompileCounter counts in a cell's window
BACKEND_COMPILE = '/jax/core/compile/backend_compile_duration'
TOL = 2e-5

# model -> (the module with its toy sizes, weights from a seed, its
# block or None for the module's own, the plain reference's logits)
MODELS = {
    'opt': (olmoe_t, olmoe_t.make_opt_params,
            lambda: OptBlock(olmoe_t.H), olmoe_t.ref_opt_logits),
    'olmoe': (olmoe_t, olmoe_t.make_params, lambda: None,
              olmoe_t.ref_logits),
    'dots': (dots_t, dots_t.make_params, lambda: None, dots_t.ref_logits),
}
# prompt lengths (tokens from seed 31) of the requests served below,
# and the ids the tree BEFORE this change served for them, chunked or
# not (weights of seed 0, greedy, 6 new tokens a request; recorded on
# the CPU at the parent commit)
SERVED_PROMPTS = (5, 12, 9, 14)
SERVED_IDS = {
    'opt': [[65, 53, 203, 85, 2, 20], [20, 20, 65, 203, 97, 143],
            [203, 20, 97, 77, 2, 97], [97, 65, 201, 2, 65, 65]],
    'olmoe': [[121, 11, 134, 127, 200, 206], [74, 110, 186, 48, 117, 176],
              [175, 189, 28, 155, 199, 56], [62, 99, 87, 116, 135, 72]],
    'dots': [[74, 21, 51, 79, 70, 70], [74, 6, 21, 57, 49, 48],
             [56, 67, 49, 26, 13, 42], [69, 64, 44, 5, 89, 76]],
}


@pytest.fixture(autouse=True)
def toy_context(monkeypatch):
    # tests/test_dots_vlm_decode.py's own fixture: YaRN over 64 positions
    monkeypatch.setattr(reference_dots_vlm, 'YARN_ORIGINAL_MAX',
                        dots_t.YARN['original_max'])


@pytest.fixture
def ring(monkeypatch):
    monkeypatch.delenv('PADDLE_TPU_TRACE_DIR', raising=False)
    timeline.reset()
    yield timeline.ring()
    timeline.reset()


@pytest.fixture
def backend_compiles():
    """The backend compiles of this process while the test runs."""
    seen = []

    def on(name, seconds, **_kw):
        if name == BACKEND_COMPILE:
            seen.append(seconds)
    jax.monitoring.register_event_duration_secs_listener(on)
    yield seen
    jax.monitoring.unregister_event_duration_listener(on)


spans = olmoe_t.spans


def engine(model, chunked=False):
    mod, make_params, make_block, reference = MODELS[model]
    params = make_params(0)
    eng = mod.make_engine(params, make_block(), top=16,
                          prefill_chunk_tokens=mod.PAGE if chunked else 0)
    return mod, params, eng, reference


def prefill(eng, prompt, pages):
    return dots_t.chunked_prefill(eng, prompt, pages) if eng.chunked \
        else eng.prefill_into(prompt, pages)


def serve(eng, prompts, n_new):
    server = DecodeServer(eng, warmup=False)
    try:
        streams = [server.submit(p, max_new_tokens=n_new) for p in prompts]
        return [list(st.result(timeout=120.0)) for st in streams]
    finally:
        server.close()


def serve_at_once(eng, prompts, n_new):
    """Every request in the queue before the worker's first tick, so
    that two runs make the same calls with the same rows (``submit``
    races the worker: a later request may find the first tick gone)."""
    server = DecodeServer(eng, warmup=False)
    try:
        streams = [DecodeStream('r%d' % i, p, n)
                   for i, (p, n) in enumerate(zip(prompts, n_new))]
        with server._cv:
            server._queue.extend(streams)
            server._submitted += len(streams)
            server._cv.notify()
        return [list(st.result(timeout=120.0)) for st in streams], \
            server.stats()
    finally:
        server.close()


def record_calls(eng):
    """Keep, of every ``step`` and ``prefill_chunk`` the engine is
    asked for: the ids and the logits of the rows that ran (a chunk's
    last row first)."""
    seen, trash = [], eng.cache.trash
    step, chunk = eng.step, eng.prefill_chunk

    def wrapped_step(tokens, page_tables, ctx_lens):
        ids, rows = step(tokens, page_tables, ctx_lens)
        run = page_tables[:, 0] != trash
        seen.append((ids[run], np.asarray(rows)[run]))
        return ids, rows

    def wrapped_chunk(*args):
        out = chunk(*args)
        if len(args) == 3:
            seen.append((np.asarray(out),))
        else:
            run = args[4][:, 0] != trash
            seen.append((out[0], out[1][run], np.asarray(out[2])[run]))
        return out
    eng.step, eng.prefill_chunk = wrapped_step, wrapped_chunk
    return seen


def count_bytes(model, mod):
    """The routing counts a program returns: [layers that route, experts
    (+ 1 column for those held elsewhere)] int32, none without experts."""
    return {'opt': 0, 'olmoe': mod.L * olmoe_t.E * 4,
            'dots': (mod.L - 1) * (dots_t.HELD + 1) * 4}[model]


def host_operands(ring):
    """``host_operands`` of the calls that ran decode rows, in order."""
    evs = [e for e in ring.events(cat='span')
           if e['name'] in ('decode.step', 'decode.prefill_chunk')
           and 'host_operands' in e['args']]
    return [e['args']['host_operands']
            for e in sorted(evs, key=lambda e: e['ts'])]


def rows_of(eng, rows):
    """A step's three arrays for ``rows`` {slot: (token, the stream's
    pages so far, cached positions)}, built anew as a server's tick
    builds them; every other slot idle.  (The pages as the engine's
    calls take them: with window layers the pair (pages, ring).)"""
    S = eng.max_streams
    t, c = np.zeros(S, np.int32), np.zeros(S, np.int32)
    pt = np.tile(eng.idle_row, (S, 1))
    for slot, (tok, pages, ctx) in rows.items():
        t[slot], c[slot], pt[slot] = tok, ctx, eng.table_row(pages)
    return t, pt, c


def on_device(x):
    return isinstance(x, jax.Array) and not isinstance(x, np.ndarray)


@pytest.mark.parametrize('chunked', [False, True])
@pytest.mark.parametrize('model', sorted(MODELS))
def test_step_returns_numpy_ids_and_device_logits_equal_to_the_reference(
        model, chunked, backend_compiles):
    mod, params, eng, reference = engine(model, chunked)
    eng.warmup()
    assert backend_compiles     # the listener hears this engine's compiles
    del backend_compiles[:]
    prompt = np.random.default_rng(31).integers(1, mod.V, 12)
    pages, slot = eng.cache.alloc(2), 1
    first = prefill(eng, prompt, pages)
    assert isinstance(first, np.ndarray) and first.shape == (mod.V,)
    seq = list(prompt) + [int(np.argmax(first))]
    t, pt, c = mod.one_slot(eng, slot, seq[-1], pages, len(prompt))
    results = [eng.step(t, pt, c)]
    if chunked:
        # a chunk that carries the same step gives the same, the same way
        last, *carried = eng.prefill_chunk(
            np.arange(1, 6), eng.cache.alloc(1), 0, t, pt, c)
        assert isinstance(last, np.ndarray) and last.shape == (mod.V,)
        results.append(carried)
    # numpy in, one copy out: no program was compiled to hand a call its
    # operands or to read its results (the reference below compiles)
    assert backend_compiles == [] and eng.compiles_after_warmup == 0
    want = reference(params, seq)
    assert mod.rel(first, want[-2]) < TOL
    for ids, rows in results:
        assert isinstance(ids, np.ndarray) \
            and ids.shape == (mod.STREAMS,) and ids.dtype == np.int32
        assert on_device(rows) and rows.shape == (mod.STREAMS, mod.V)
        # whoever wants a row indexes the device array
        assert mod.rel(rows[slot], want[-1]) < TOL
        assert int(ids[slot]) == int(np.argmax(want[-1]))


@pytest.mark.parametrize('model', sorted(MODELS))
def test_fetched_bytes_are_the_ids_the_counts_and_a_chunks_last_row(
        model, ring):
    mod, params, eng, _ = engine(model, chunked=True)
    S, V = mod.STREAMS, mod.V
    counts = count_bytes(model, mod)
    prompt = np.random.default_rng(5).integers(1, V, 12)
    pages = eng.cache.alloc(2)
    tok = int(np.argmax(prefill(eng, prompt, pages)))
    t, pt, c = mod.one_slot(eng, 1, tok, pages, len(prompt))
    ring.clear()
    eng.step(t, pt, c)
    eng.prefill_chunk(np.arange(1, 6), eng.cache.alloc(1), 0)
    eng.prefill_chunk(np.arange(1, 6), eng.cache.alloc(1), 0, t, pt, c)
    step, = spans(ring, 'decode.step')
    alone, carrying = spans(ring, 'decode.prefill_chunk')
    assert step['args']['fetched_bytes'] == 4 * S + counts
    assert alone['args']['fetched_bytes'] == 4 * V + counts
    assert carrying['args']['fetched_bytes'] == 4 * V + 4 * S + counts
    assert carrying['args']['step_rows'] == 1
    # the engine's totals still get the counts that came back that way
    assert (eng.routing['assignments'] > 0) is bool(counts)


@pytest.mark.parametrize('model', ['olmoe', 'opt'])
def test_a_step_fetches_under_a_hundredth_of_its_logits(model, ring):
    """At 32 slots, a cell's batch: what a step copies to the host is
    under 1% of the ``[S, V]`` float32 logits it used to copy."""
    mod, make_params, make_block, _ = MODELS[model]
    S = 32
    eng = DecodeEngine(
        make_params(0), n_layers=mod.L, n_heads=mod.H, page_size=mod.PAGE,
        num_pages=40, max_streams=S, prefill_bucket=16,
        max_seq=mod.MAX_SEQ, prefix_cache=False, prefill_chunk_tokens=0,
        block=make_block() or olmoe_t.OlmoeBlock(mod.H))
    ring.clear()
    ids, rows = eng.step(
        np.zeros(S, np.int32),
        np.full((S, eng.pages_per_stream), eng.cache.trash, np.int32),
        np.zeros(S, np.int32))
    step, = spans(ring, 'decode.step')
    assert ids.nbytes <= step['args']['fetched_bytes'] \
        < 0.01 * rows.nbytes == 0.01 * S * mod.V * 4


@pytest.mark.parametrize('model', sorted(MODELS))
def test_a_chunk_call_has_a_dispatch_and_a_fetch_half(model, ring):
    mod, params, eng, _ = engine(model, chunked=True)
    eng.warmup()
    ring.clear()
    rng = np.random.default_rng(2)
    serve(eng, [rng.integers(1, mod.V, n) for n in (5, 20, 9)], 4)
    evs = ring.events(cat='span')
    chunks = [e for e in evs if e['name'] == 'decode.prefill_chunk']
    assert len(chunks) >= 4
    assert any(e['args']['step_rows'] for e in chunks)
    for parent, halves in (('decode.prefill_chunk', chunks),
                           ('decode.step', spans(ring, 'decode.step'))):
        for e in halves:
            kids = sorted((k for k in evs if k['parent'] == e['id']),
                          key=lambda k: k['ts'])
            assert [k['name'] for k in kids] \
                == [parent + '.dispatch', parent + '.fetch']
            # one after the other, inside the call
            assert e['ts'] <= kids[0]['ts'] and \
                kids[0]['ts'] + kids[0]['dur'] <= kids[1]['ts'] + 1e-9
            assert kids[1]['ts'] + kids[1]['dur'] \
                <= e['ts'] + e['dur'] + 1e-9
            assert sum(k['dur'] for k in kids) <= e['dur'] + 1e-9
            assert e['args']['fetched_bytes'] > 0


@pytest.mark.parametrize('chunked', [False, True])
@pytest.mark.parametrize('model', sorted(MODELS))
def test_the_server_compiles_nothing_and_serves_the_greedy_ids(
        model, chunked, backend_compiles):
    """More than one request in flight, through the server: no backend
    compile after ``warmup()``, and each request's ids are the greedy
    continuation that the engine's own logits give one slot at a time,
    which the plain reference confirms."""
    mod, params, eng, reference = engine(model, chunked)
    eng.warmup()
    del backend_compiles[:]
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, mod.V, n) for n in SERVED_PROMPTS]
    served = serve(eng, prompts, 6)
    assert backend_compiles == [] and eng.compiles_after_warmup == 0
    assert served == SERVED_IDS[model]  # the same ids as before
    for prompt, toks in zip(prompts, served):
        assert len(toks) == 6
        rows = reference(params, list(prompt) + toks[:-1])[len(prompt) - 1:]
        assert [int(t) for t in np.argmax(rows, -1)] == toks


@pytest.mark.parametrize('chunked', [False, True])
@pytest.mark.parametrize('model', sorted(MODELS))
def test_a_server_that_uploads_every_array_serves_the_same_bits(
        model, chunked, ring, monkeypatch):
    """The same requests through two servers, one as it is and one whose
    engine keeps nothing on the device (every call uploads its three
    arrays, as before this change): the same calls, the same ids, and
    bit for bit the same logits on every row that ran."""
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, MODELS[model][0].V, n)
               for n in SERVED_PROMPTS]
    n_new = (6, 3, 6, 5)     # two retire while the others still decode
    runs = []
    for always_upload in (False, True):
        mod, params, eng, _ = engine(model, chunked)
        eng.warmup()
        if always_upload:
            monkeypatch.setattr(eng, '_hold', lambda *a: None)
        seen = record_calls(eng)
        ring.clear()
        served, stats = serve_at_once(eng, prompts, n_new)
        runs.append((served, seen, host_operands(ring), stats))
    (served, seen, sent, stats), (served_u, seen_u, sent_u, stats_u) = runs
    assert served == served_u \
        == [ids[:n] for ids, n in zip(SERVED_IDS[model], n_new)]
    assert len(seen) == len(seen_u) and len(sent) == len(sent_u)
    for call, call_u in zip(seen, seen_u):
        assert len(call) == len(call_u)
        for got, want in zip(call, call_u):
            assert got.dtype == want.dtype and np.array_equal(got, want)
    assert set(sent_u) == {3} and sent[0] == 3 and 0 in sent
    assert stats['step_calls'] == stats_u['step_calls'] == len(sent)
    assert stats['step_host_operands'] == sum(sent) < sum(sent_u) \
        == stats_u['step_host_operands'] == 3 * len(sent)
    if not chunked:
        # every page claimed at admission: the table moves only where a
        # stream retires, and then its ids and context lengths with it
        assert sent == [3, 0, 3, 0, 3]


@pytest.mark.parametrize('model', sorted(MODELS))
def test_host_operands_count_what_the_device_does_not_hold(
        model, ring, backend_compiles):
    """By hand, as a server that claims pages as contexts grow: 3 on the
    first call, 0 between two page crossings, 1 (the page tables) on a
    crossing, all three where a stream is admitted or retires (its row's
    id and context length change with its table row)."""
    mod, params, eng, reference = engine(model)
    eng.warmup()
    del backend_compiles[:]
    rng = np.random.default_rng(52)
    P = mod.PAGE
    first, second = rng.integers(1, mod.V, 12), rng.integers(1, mod.V, 3)
    pages = {1: eng.cache.alloc(4), 2: eng.cache.alloc(2)}
    seq = {1: list(first) + [int(np.argmax(prefill(eng, first, pages[1])))]}
    ring.clear()

    def step(slots):
        ids, _ = eng.step(*rows_of(eng, {
            i: (seq[i][-1], pages[i][:(len(seq[i]) - 1) // P + 1],
                len(seq[i]) - 1) for i in slots}))
        for i in slots:
            seq[i].append(int(ids[i]))

    for _ in range(6):          # contexts 12 .. 17: a crossing at 16
        step([1])
    seq[2] = list(second) + [int(np.argmax(prefill(eng, second, pages[2])))]
    for slots in ([1, 2], [1, 2], [2], [2]):    # admitted, then 1 retires
        step(slots)
    assert host_operands(ring) == [3, 0, 0, 0, 1, 0, 3, 0, 3, 0]
    assert eng.calls == {'step_calls': 10, 'step_host_operands': 10}
    # neither the uploads nor the reuse compiled anything, and a call
    # that uploads nothing copies back what any step does
    assert backend_compiles == [] and eng.compiles_after_warmup == 0
    for e in spans(ring, 'decode.step'):
        assert e['args']['fetched_bytes'] \
            == 4 * mod.STREAMS + count_bytes(model, mod)
    # and the ids are the greedy continuation, reuse or not
    for i, prompt in ((1, first), (2, second)):
        rows = reference(params, seq[i][:-1])[len(prompt) - 1:]
        assert [int(t) for t in np.argmax(rows, -1)] == seq[i][len(prompt):]


@pytest.mark.parametrize('model', sorted(MODELS))
def test_an_idle_row_is_zero_trash_zero_on_the_device(model, ring):
    """After a stream retired, what the device holds for its row is
    what the host would upload, token 0, an all-trash table row, context
    length 0 (what the live-pages kernels read as "reads nothing"), and
    stays so over the calls that upload nothing."""
    mod, params, eng, _ = engine(model)
    rng = np.random.default_rng(7)
    pages, toks = {}, {}
    for slot, n in ((0, 9), (2, 5)):
        pages[slot] = eng.cache.alloc(2)
        toks[slot] = int(np.argmax(prefill(
            eng, rng.integers(1, mod.V, n), pages[slot])))
    ctx = {0: 9, 2: 5}
    ring.clear()
    for slots in ([0, 2], [0, 2], [2], [2], [2]):
        ids, _ = eng.step(*rows_of(
            eng, {i: (toks[i], pages[i], ctx[i]) for i in slots}))
        for i in slots:
            toks[i], ctx[i] = int(ids[i]), ctx[i] + 1
    assert host_operands(ring) == [3, 0, 3, 0, 0]
    host, held = eng._held
    want = rows_of(eng, {2: (toks[2], pages[2], ctx[2])})
    for got_host, handle, w in zip(host, held, want):
        assert on_device(handle) and handle.dtype == np.int32
        assert np.array_equal(np.asarray(handle), w)
        assert np.array_equal(got_host, w)
    idle = [0, 1, 3]
    assert not np.asarray(held[0])[idle].any()
    assert (np.asarray(held[1])[idle] == eng.cache.trash).all()
    assert not np.asarray(held[2])[idle].any()


@pytest.mark.parametrize('model', sorted(MODELS))
def test_an_idle_slot_attends_over_no_position(model, monkeypatch):
    """A step's read (a plain step's and a carrying chunk's) hands the
    block's ``attend_step`` ``pos + 1`` positions for a running slot
    and 0 for an idle one, the context the live-pages kernels skip: an
    idle slot used to attend over position 0 of the trash page, a copy
    of K and V a layer for every slot that held nothing."""
    _, params, eng, _ = engine(model)
    monkeypatch.setattr(eng.block, 'attend_step',
                        lambda p, i, q, pools, pt, ctx_len: ctx_len)
    read = eng._step_read(params, jnp.asarray([4, 0, 0, 7]),
                          jnp.asarray([True, False, True, False]))
    got = read(0, None, [], [None] * (max(eng._group) + 1))
    assert np.array_equal(np.asarray(got), [5, 0, 1, 0])


@pytest.mark.parametrize('chunked', [False, True])
@pytest.mark.parametrize('model', sorted(MODELS))
def test_a_replay_by_hand_with_int64_ids_reuses_and_meets_the_reference(
        model, chunked, ring):
    """chipbench's ``Served.replay``: one running row, ids as int64, a
    table built by hand, every call from new arrays.  After the first
    step nothing is uploaded, and every position's logits are the
    reference's."""
    mod, params, eng, reference = engine(model, chunked)
    prompt = np.random.default_rng(13).integers(1, mod.V, 11)
    n_new = 9
    pages = eng.cache.alloc(-(-(len(prompt) + n_new) // mod.PAGE))
    rows = [prefill(eng, prompt, pages)]
    toks = [int(np.argmax(rows[0]))]
    ring.clear()
    for j in range(n_new - 1):
        pt = np.full((eng.max_streams, eng.pages_per_stream),
                     eng.cache.trash, np.int32)
        pt[0, :len(pages)] = pages
        t_in = np.zeros((eng.max_streams,), np.int64)
        t_in[0] = toks[-1]
        ctx = np.zeros((eng.max_streams,), np.int32)
        ctx[0] = len(prompt) + j
        rows.append(np.asarray(eng.step(t_in, pt, ctx)[1][0]))
        toks.append(int(np.argmax(rows[-1])))
    assert host_operands(ring) == [3] + [0] * (n_new - 2)
    want = reference(params, list(prompt) + toks[:-1])[len(prompt) - 1:]
    for got, w in zip(rows, want):
        assert mod.rel(got, w) < TOL


# -- a chunk's rows reach the pools a page at a time ----------------------
# (the cases here for a K/V block and the latent row; a ring in
# tests/test_laguna_decode.py, a block that loops in
# tests/test_ouro_decode.py, through ``chunk_writes_match_row_by_row``)

def write_row_by_row(eng):
    """``eng`` made to cache a chunk's rows as the tree did before it
    wrote them by pages, the reference of the cases below: every row of
    the bucket at (its page, its offset), a padded row at the trash
    page."""
    P, by_page = eng.page_size, eng._chunk_rows

    def chunk_rows(bucket, pt, pos0, n_valid):
        pos, valid, page_ids = by_page(bucket, pt, pos0, n_valid)
        return pos, valid, [jnp.where(valid, jnp.repeat(ids, P), trash)
                            for ids, trash in zip(page_ids, eng._trashes())]

    def pages_then(n, attend):
        def by_row(i, q, rows, pools, at, row_at, *tables):
            ids = row_at[eng._group[i]]
            for pool, r in zip(pools, rows):
                pool[i] = pool[i].at[ids, jnp.arange(len(ids)) % P].set(
                    r[n:])
            return attend(i, q, [r[:n] for r in rows], pools, at, *tables)
        return by_row
    eng._chunk_rows, eng._pages_then = chunk_rows, pages_then
    return eng


def claim_pages(eng, span):
    """A stream's pages as the engine's calls take them: with window
    layers the pair (pages, ring)."""
    pages = eng.cache.alloc(-(-span // eng.page_size))
    return (pages, eng.cache.window.alloc(eng.ring_for(span))) \
        if eng.ring_pages else pages


def give_pages_back(eng, pages):
    if eng.ring_pages:
        eng.cache.window.free(pages[1])
        pages = pages[0]
    eng.cache.free(pages)


# the tokens of a prompt's last chunk, past two whole chunks of two pages
# (P the page size), and whether the chunks carry another stream's rows
CHUNK_WRITE_CASES = {
    'whole_chunks': (lambda P: 0, False),
    'one_token': (lambda P: 1, False),
    'a_page_less_one': (lambda P: P - 1, False),
    'a_page_and_one': (lambda P: P + 1, False),
    'carrying_decode_rows': (lambda P: P + 1, True),
}


def chunk_writes_match_row_by_row(new, old, vocab, case):
    """One prompt through ``new`` (the tree's engine) and ``old`` (the
    same engine under ``write_row_by_row``), in chunks of two pages:
    afterwards every pool of every layer holds bit for bit the same,
    off the trash pages, but for the rows of the prompt's last page
    past the prompt, which are finite; 2 x P greedy steps then give the
    same ids, and overwrite those rows, after which nothing differs."""
    ragged, carry = CHUNK_WRITE_CASES[case]
    P, G = new.page_size, new.chunk_grid
    assert G == 2 * P == old.chunk_grid
    rng = np.random.default_rng(58)
    prompt = rng.integers(1, vocab, 2 * G + ragged(P))
    other = rng.integers(1, vocab, G)      # whole pages: no tail of its own
    n, runs = len(prompt), []
    for eng in (new, old):
        eng.cache.pools = [[jnp.zeros_like(b) for b in pool]
                           for pool in eng.cache.pools]
        mine = claim_pages(eng, n + 2 * P + 1)
        theirs = claim_pages(eng, len(other) + 4)
        for lo, hi in eng.chunk_spans(len(other)):
            first = eng.prefill_chunk(other[lo:hi], theirs, lo)
        tok, ctx = int(np.argmax(first)), len(other)
        for lo, hi in eng.chunk_spans(n):
            if carry:
                last, nxt, _ = eng.prefill_chunk(
                    prompt[lo:hi], mine, lo,
                    *rows_of(eng, {0: (tok, theirs, ctx)}))
                tok, ctx = int(nxt[0]), ctx + 1
            else:
                last = eng.prefill_chunk(prompt[lo:hi], mine, lo)
        after_prompt = [[np.asarray(b) for b in pool]
                        for pool in eng.cache.pools]
        ids = [int(np.argmax(last))]
        for k in range(2 * P):
            nxt, _ = eng.step(*rows_of(eng, {1: (ids[-1], mine, n + k)}))
            ids.append(int(nxt[1]))
        runs.append((mine, after_prompt, ids, [
            [np.asarray(b) for b in pool] for pool in eng.cache.pools]))
        give_pages_back(eng, mine)
        give_pages_back(eng, theirs)
    (mine, got, ids, got_end), (mine_old, want, ids_old, want_end) = runs
    assert mine == mine_old                 # the same pages on both sides
    cache, last_page, tails = new.cache, (n - 1) // P, 0
    for r in range(len(cache.rows)):
        for i in range(cache.n_layers):
            a, b = got[r][i], want[r][i]
            group, ring = cache.group_of(i), new._group[i]
            page = mine[1][last_page % new.ring_pages] if ring \
                else (mine[0] if new.ring_pages else mine)[last_page]
            same = np.ones(a.shape[:2], bool)
            for t in range(cache.recurrences):
                at = t * (group.num_pages + 1)
                same[at + group.trash] = False
                if n % P:
                    same[at + page, n % P:] = False
                    tail = a[at + page, n % P:]
                    assert np.isfinite(tail.astype(np.float32)).all()
                    # written with its page, where a row at a time
                    # left what was there
                    assert not np.array_equal(tail, b[at + page, n % P:])
                    tails += 1
            assert np.array_equal(a[same], b[same])
            same[:] = True
            same[group.trash::group.num_pages + 1] = False
            assert np.array_equal(got_end[r][i][same], want_end[r][i][same])
    assert tails == (len(cache.rows) * cache.slots if n % P else 0)
    assert ids == ids_old and len(ids) == 2 * P + 1


def pool_scatters(eng, bucket):
    """The updates of each scatter onto a page pool in the ``chunk``
    program of ``bucket``, as its jaxpr has them (a scanned body
    counted once)."""
    shapes = {b.shape for pool in eng.cache.pools for b in pool}
    sizes = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == 'scatter' \
                    and eqn.invars[0].aval.shape in shapes:
                sizes.append(eqn.invars[1].aval.shape[0])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, 'jaxpr', sub)
                    if hasattr(sub, 'eqns'):
                        walk(sub)
    walk(jax.make_jaxpr(eng._chunk_fn(bucket))(
        eng.params, *eng.cache.pools, jnp.zeros((bucket,), jnp.int32),
        jnp.asarray(eng.idle_row), jnp.int32(0), jnp.int32(1),
        *eng._idle_step).jaxpr)
    return sorted(sizes)


def chunk_scatters_a_page_an_update(eng):
    """Every layer's every cache row: one scatter of ``bucket // P``
    pages for the chunk's rows and one of S rows for the decode rows."""
    each = len(eng.cache.rows) * eng.n_layers
    for bucket in eng.chunk_buckets:
        n_pages = bucket // eng.page_size
        assert pool_scatters(eng, bucket) == sorted(
            each * [n_pages] + each * [eng.max_streams])


@functools.lru_cache(maxsize=None)
def two_page_chunk_engines(model):
    """(the module, the tree's engine, the same written row by row),
    chunks of two pages."""
    mod, make_params, make_block, _ = MODELS[model]
    new, old = (mod.make_engine(make_params(0), make_block(), top=16,
                                prefill_chunk_tokens=2 * mod.PAGE)
                for _ in range(2))
    return mod, new, write_row_by_row(old)


@pytest.mark.parametrize('case', sorted(CHUNK_WRITE_CASES))
@pytest.mark.parametrize('model', sorted(MODELS))
def test_chunk_rows_written_by_pages_leave_what_row_by_row_left(
        model, case):
    mod, new, old = two_page_chunk_engines(model)
    chunk_writes_match_row_by_row(new, old, mod.V, case)


@pytest.mark.parametrize('model', sorted(MODELS))
def test_a_chunk_scatters_pages_for_its_rows_and_rows_for_the_carried(
        model):
    chunk_scatters_a_page_an_update(two_page_chunk_engines(model)[1])


@pytest.mark.parametrize('model', sorted(MODELS))
def test_a_chunk_off_the_page_grid_is_refused(model):
    mod, eng, _ = two_page_chunk_engines(model)
    pages = eng.cache.alloc(2)
    for pos0 in (1, mod.PAGE - 1, mod.PAGE + 3):
        with pytest.raises(ValueError, match='page grid'):
            eng.prefill_chunk(np.arange(1, 4), pages, pos0)
    eng.prefill_chunk(np.arange(1, 4), pages, mod.PAGE)     # on it: fine
    eng.cache.free(pages)

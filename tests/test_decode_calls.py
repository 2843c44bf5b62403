"""What an engine call takes from the host and hands back to it, under
every block description at toy size (the widths, weights and references
of tests/test_olmoe_decode.py and tests/test_dots_vlm_decode.py): the
host's numpy arrays go into the executable as they are, ONE copy brings
back what the caller reads (next-token ids, routing counts, a prompt's
last row: the span's ``fetched_bytes``), the decode rows' ``[S, V]``
logits stay on the device, and nothing of that compiles a program after
``warmup()``.
"""
import numpy as np
import pytest

import jax

from paddle_tpu.inference.blocks import OptBlock
from paddle_tpu.inference.decode import DecodeEngine, DecodeServer
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
    # the routing counts a program returns: [layers that route, experts
    # (+ 1 column for those held elsewhere)] int32, none without experts
    counts = {'opt': 0, 'olmoe': mod.L * olmoe_t.E * 4,
              'dots': (mod.L - 1) * (dots_t.HELD + 1) * 4}[model]
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

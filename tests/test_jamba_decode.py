"""Layers that keep a state a STREAM (Mamba) beside layers that cache
K/V a position, served through ``DecodeEngine`` (``JambaBlock``): the
engine against the plain float32 reference (tests/reference_jamba.py) on
seeded weights at tiny sizes on the CPU, logits and not ids, for a
whole-prompt prefill, chunked prefill at two chunk sizes with ragged
last chunks (1, 2 and 3 tokens: under the convolution's reach), chunks
that carry other streams' decode rows, and decoding after each; slots
reused and streams preempted; the three ops and the scan kernel against
each other; the controls the comparison has to fail; and what the cache,
the spans and the server count."""
import functools

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
import reference_jamba as ref
from paddle_tpu.core.registry import get_op_impl
from paddle_tpu.inference.blocks import JambaBlock
from paddle_tpu.inference.decode import (DecodeEngine, DecodeServer,
                                         extract_params)
from paddle_tpu.models import jamba
from paddle_tpu.observability import timeline
from paddle_tpu.ops import ssm
from paddle_tpu.ops.pallas.selective_scan import selective_scan, supported

L, PERIOD, OFFSET = 6, 4, 2         # s s A s s s: runs (0, 2) and (3, 3)
D, F, H, DH, V = 32, 48, 4, 8, 61
DC, N, K, R = 128, 8, 4, 4
PAGE, STREAMS, PAGES, SEQ = 8, 3, 24, 64
SPEC = {'heads': H, 'kv_heads': 1, 'period': PERIOD, 'offset': OFFSET}
TOL = 2e-5          # float32 against float32 at ``highest``
STATE_BYTES = 5 * (N * DC + (K - 1) * DC) * 4      # a stream, 5 layers


@functools.lru_cache(maxsize=None)
def weights(seed=3):
    """The model's own startup program at tiny widths, float32."""
    scope = fluid.Scope()
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = seed
    with fluid.program_guard(main_p, startup):
        jamba.build_logits(
            V, n_layers=L, d_model=D, ffn_size=F, n_heads=H, n_kv_heads=1,
            head_dim=DH, d_inner=DC, d_state=N, d_conv=K, dt_rank=R,
            period=PERIOD, offset=OFFSET, init_std=0.15,
            embed_init_std=1.0)
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    return jamba.finish_init(extract_params(scope, L, block()))


def block(cls=JambaBlock):
    return cls(H, 1, DH, PERIOD, OFFSET)


def engine(chunk=0, blk=None, num_pages=PAGES, **kw):
    return DecodeEngine(
        weights(), n_layers=L, n_heads=H, block=blk or block(),
        page_size=PAGE, num_pages=num_pages, max_streams=STREAMS,
        max_seq=SEQ, prefill_bucket=SEQ, prefill_chunk_tokens=chunk,
        **dict({'prefix_cache': False}, **kw))


def want(seq, **hooks):
    """The reference's logits for ``seq``, with some of its functions
    swapped (``hooks``)."""
    plain = {k: getattr(ref, k) for k in hooks}
    for k, v in hooks.items():
        setattr(ref, k, v)
    try:
        return np.asarray(ref.logits(weights(), jnp.asarray(seq), L, SPEC))
    finally:
        for k, v in plain.items():
            setattr(ref, k, v)


def rel(got, wanted):
    return float(np.max(np.abs(got - wanted)) / np.max(np.abs(wanted)))


def step_operands(eng, slots):
    """``step``'s three arrays for {slot: (pages, token, ctx)}."""
    pt = np.tile(eng.idle_row, (STREAMS, 1))
    tok, ctx = np.zeros(STREAMS, np.int32), np.zeros(STREAMS, np.int32)
    for i, (pages, t, c) in slots.items():
        pt[i], tok[i], ctx[i] = eng.table_row(pages), t, c
    return tok, pt, ctx


def prefill(eng, prompt, pages, slot, carry=()):
    """A prompt into ``slot`` the way the engine prefills; the last
    call's result."""
    if not eng.chunked:
        return eng.prefill_into(prompt, (pages, slot))
    for lo, hi in eng.chunk_spans(len(prompt)):
        out = eng.prefill_chunk(prompt[lo:hi], (pages, slot), lo, *carry)
    return out


def decode(eng, pages, slot, first, start, n):
    """``n`` greedy steps of one stream -> (logits rows, tokens fed)."""
    rows, toks = [], [int(np.argmax(first))]
    for j in range(n):
        nxt, logits = eng.step(*step_operands(
            eng, {slot: (pages, toks[-1], start + j)}))
        rows.append(np.asarray(logits[slot]))
        toks.append(int(nxt[slot]))
    return np.stack(rows), toks[:-1]


def served_alone(chunk, n_prompt, slot=1, blk=None, eng=None, hook=None):
    """One prompt and three decode steps -> (the engine's logits for the
    prompt's last position and the three after, the sequence)."""
    eng = eng or engine(chunk, blk)
    if hook:
        hook(eng)
    rng = np.random.default_rng(n_prompt)
    prompt = rng.integers(1, V, n_prompt)
    pages = eng.cache.alloc(-(-(n_prompt + 3) // PAGE))
    first = prefill(eng, prompt, pages, slot)
    rows, fed = decode(eng, pages, slot, first, n_prompt, 3)
    eng.cache.free(pages)
    return np.concatenate([first[None], rows]), \
        np.concatenate([prompt, fed]).astype(np.int32)


def padded(seq):
    out = np.zeros(SEQ, np.int32)
    out[:len(seq)] = seq
    return out


# -- the engine against the reference ---------------------------------------

@pytest.mark.parametrize('n_prompt', [5, 8, 13, 32, 50])
def test_whole_prompt_prefill_and_decode_match_the_reference(n_prompt):
    got, seq = served_alone(0, n_prompt)
    logits = want(padded(seq))
    assert rel(got, logits[n_prompt - 1:n_prompt + 3]) < TOL


@pytest.mark.parametrize('chunk,n_prompt', [
    (8, 9), (8, 18), (8, 19), (8, 37), (16, 17), (16, 34), (16, 35),
    (16, 48), (16, 7)])
def test_chunked_prefill_and_decode_match_the_reference(chunk, n_prompt):
    """Ragged last chunks of 1, 2 and 3 tokens (the convolution reaches
    back into the chunk before), whole chunks, a prompt inside one
    bucket's padding."""
    got, seq = served_alone(chunk, n_prompt)
    logits = want(padded(seq))
    assert rel(got, logits[n_prompt - 1:n_prompt + 3]) < TOL


@functools.lru_cache(maxsize=None)
def interleaved(chunk=16):
    """Stream a (37 tokens) decodes in slot 2 while stream b (21) goes
    in through chunks that CARRY a's rows; then both decode side by
    side; then a retires and a shorter prompt c (11) takes its slot."""
    timeline.reset()
    eng = engine(chunk)
    rng = np.random.default_rng(5)
    a, b, c = (rng.integers(1, V, n) for n in (37, 21, 11))
    pa, pb = eng.cache.alloc(6), eng.cache.alloc(4)
    first_a = prefill(eng, a, pa, 2)
    rows_a, toks_a, rows_b = [first_a], [int(np.argmax(first_a))], []
    for lo, hi in eng.chunk_spans(len(b)):
        first_b, nxt, logits = eng.prefill_chunk(
            b[lo:hi], (pb, 0), lo,
            *step_operands(eng, {2: (pa, toks_a[-1], len(a)
                                     + len(toks_a) - 1)}))
        rows_a.append(np.asarray(logits[2]))
        toks_a.append(int(nxt[2]))
    rows_b.append(first_b)
    toks_b = [int(np.argmax(first_b))]
    for j in range(3):      # side by side
        nxt, logits = eng.step(*step_operands(eng, {
            2: (pa, toks_a[-1], len(a) + len(toks_a) - 1),
            0: (pb, toks_b[-1], len(b) + len(toks_b) - 1)}))
        rows_a.append(np.asarray(logits[2]))
        rows_b.append(np.asarray(logits[0]))
        toks_a.append(int(nxt[2]))
        toks_b.append(int(nxt[0]))
    eng.cache.free(pa)      # a retires; c takes slot 2 while b decodes
    pc = eng.cache.alloc(3)
    first_c, nxt, logits = eng.prefill_chunk(
        c, (pc, 2), 0, *step_operands(eng, {
            0: (pb, toks_b[-1], len(b) + len(toks_b) - 1)}))
    rows_b.append(np.asarray(logits[0]))
    toks_b.append(int(nxt[0]))
    rows_c, fed_c = decode(eng, pc, 2, first_c, len(c), 2)
    return {
        'eng': eng,
        'spans': [e for e in timeline.ring().events(cat='span')
                  if 'id' in e],
        'a': (np.stack(rows_a), np.concatenate([a, toks_a[:-1]]), len(a)),
        'b': (np.stack(rows_b), np.concatenate([b, toks_b[:-1]]), len(b)),
        'c': (np.concatenate([first_c[None], rows_c]),
              np.concatenate([c, fed_c]), len(c))}


@pytest.mark.parametrize('stream', ['a', 'b', 'c'])
def test_carried_rows_interleaved_streams_and_a_reused_slot(stream):
    """a: decode rows carried by another stream's chunks, then beside
    it; b: chunks that carry rows, then decode beside a, then carried by
    c's chunk; c: a shorter prompt in the slot a left (its state starts
    from zeros whatever a's left there)."""
    rows, seq, n = interleaved()[stream]
    logits = want(padded(seq))
    assert rel(rows, logits[n - 1:n - 1 + len(rows)]) < TOL


def test_the_server_readmits_a_preempted_stream_and_recomputes_its_state():
    eng = engine(16, num_pages=9)
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
    # not: a stream is preempted in mid-decode, loses pages AND state,
    # and comes back through position 0
    assert stats['preempted'] >= 1 and stats['completed'] == 3
    assert stats['state_recomputed'] == stats['preempted']
    assert stats['state_slots_live'] == 0 and stats['free_pages'] == 9
    for prompt, toks in zip(prompts, got):
        logits = want(padded(np.concatenate([prompt, toks[:-1]])))
        assert toks == [int(t) for t in np.argmax(
            logits[len(prompt) - 1:len(prompt) - 1 + len(toks)], axis=-1)]


def test_the_prefix_cache_is_refused_and_says_why():
    with pytest.raises(ValueError, match='state a stream.*prefix cache off'):
        engine(16, prefix_cache=True)


# -- the three ops and the kernel ---------------------------------------------

def scan_operands(t, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    return (f(t, DC), np.log1p(np.exp(f(t, DC) - 2.0)), -np.exp(f(N, DC)),
            f(t, N), f(t, N), f(DC), f(N, DC))


def op(name, **ins):
    return get_op_impl(name).compute(
        None, {k: [jnp.asarray(v)] for k, v in ins.items()}, {})


@pytest.mark.parametrize('t,n_valid', [(16, 16), (16, 5), (32, 1),
                                       (256, 130)])
def test_the_scan_kernel_the_xla_form_and_a_token_loop_agree(t, n_valid):
    v, dt, a, b, c, d, s0 = scan_operands(t)
    assert supported(t, DC, N)
    got = op('selective_scan', X=v, Dt=dt, A=a, B=b, C=c, D=d, State=s0,
             NValid=np.int32(n_valid))
    y_k, s_k = selective_scan(v, dt, a, b, c, d, s0, np.int32(n_valid),
                              interpret=True)
    s, ys = jnp.asarray(s0), []
    for i in range(n_valid):        # the reference's ``token``, by hand
        s, y = ref.token(s, v[i], dt[i], a, b[i], c[i], d)
        ys.append(y)
    for y, s_out in ((got['Out'][0], got['StateOut'][0]), (y_k, s_k)):
        np.testing.assert_allclose(y[:n_valid], np.stack(ys), atol=2e-5)
        np.testing.assert_allclose(s_out, s, atol=2e-5)
    assert np.isfinite(np.asarray(y_k)).all()


def test_a_state_update_is_the_scan_at_one_token_and_idle_rows_stay():
    v, dt, a, b, c, d, _ = scan_operands(3, seed=1)
    s0 = np.random.default_rng(2).standard_normal((3, N, DC)).astype('f')
    live = np.array([True, False, True])
    got = op('selective_state_update', X=v, Dt=dt, A=a, B=b, C=c, D=d,
             State=s0, Live=live)
    for r in range(3):
        y, s = ssm.selective_scan_math(v[r:r + 1], dt[r:r + 1], a,
                                       b[r:r + 1], c[r:r + 1], d, s0[r])
        if live[r]:
            np.testing.assert_allclose(got['Out'][0][r], y[0], atol=1e-5)
            np.testing.assert_allclose(got['StateOut'][0][r], s, atol=1e-5)
        else:
            np.testing.assert_array_equal(got['StateOut'][0][r], s0[r])


def test_the_convolution_carries_its_last_inputs_past_padding_rows():
    rng = np.random.default_rng(3)
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    u, w, bias = f(24, DC), f(K, DC), f(DC)
    whole = np.asarray(ref.conv(jnp.asarray(u[:19]), w, bias))
    zeros = np.zeros((K - 1, DC), np.float32)
    one = op('causal_conv1d', X=u[:16], W=w, Bias=bias, State=zeros)
    # 3 valid tokens in a chunk of 8: the 5 after them are padding
    two = op('causal_conv1d', X=u[16:24], W=w, Bias=bias,
             State=one['StateOut'][0], NValid=np.int32(3))
    np.testing.assert_allclose(one['Out'][0], whole[:16], atol=1e-5)
    np.testing.assert_allclose(two['Out'][0][:3], whole[16:19], atol=1e-5)
    np.testing.assert_array_equal(two['StateOut'][0], u[16:19])
    # one token a row: the same numbers, and an idle row is not shifted
    rows = op('causal_conv1d', X=np.stack([u[19], u[0]]), W=w, Bias=bias,
              State=np.stack([u[16:19], u[16:19]]),
              Live=np.array([True, False]))
    np.testing.assert_allclose(
        rows['Out'][0][0], np.asarray(ref.conv(jnp.asarray(u[:20]), w,
                                               bias))[19], atol=1e-5)
    np.testing.assert_array_equal(rows['StateOut'][0][0], u[17:20])
    np.testing.assert_array_equal(rows['StateOut'][0][1], u[16:19])


def test_the_ops_cost_what_the_equations_move():
    assert ssm.scan_bytes(512, 5120, 16) \
        == 4 * (2 * 16 * 5120 + 512 * (3 * 5120 + 32))
    assert ssm.scan_bytes(1, 5120, 16, rows=64) \
        == 64 * 4 * (2 * 16 * 5120 + 3 * 5120 + 32)
    assert ssm.scan_ops(512, 5120, 16) == 512 * 5120 * 98
    assert ssm.selective_scan_path('cpu', 512, 5120, 16) == 'xla_scan'
    assert ssm.selective_scan_path('tpu', 512, 5120, 16) == 'pallas_scan'
    assert ssm.selective_scan_path('tpu', 12, 5120, 16) == 'xla_scan'


# -- the controls: what the comparison has to fail --------------------------

class PaddingAdvances(JambaBlock):
    def seq_valid(self, n_valid, rows):
        return rows


class NoCarriedInputs(JambaBlock):
    def carried(self, c):
        return jnp.zeros_like(c)


def rotary(q, k, pos):
    from paddle_tpu.ops.moe import rotary_math
    return rotary_math(q, pos, 1e4), rotary_math(k, pos, 1e4)


def never_from_zero(eng):
    eng._from_zero = lambda pos0: pos0 < 0


def always_from_zero(eng):
    eng._from_zero = lambda pos0: pos0 >= 0


ENGINE_CONTROLS = {
    'state_not_carried_between_chunks': dict(hook=always_from_zero),
    'padding_rows_advance_the_state': dict(blk=PaddingAdvances),
    'carried_inputs_of_the_convolution_dropped': dict(blk=NoCarriedInputs),
}
REFERENCE_CONTROLS = {
    'no_small_norms': dict(dt_norm=lambda x, w: x, b_norm=lambda x, w: x,
                           c_norm=lambda x, w: x),
    'no_b_norm': dict(b_norm=lambda x, w: x),
    'no_c_norm': dict(c_norm=lambda x, w: x),
    'attention_given_rotary': dict(positional=rotary),
}


@pytest.mark.parametrize('control', sorted(ENGINE_CONTROLS))
def test_an_engine_with_one_thing_wrong_is_far_from_the_reference(control):
    kw = dict(ENGINE_CONTROLS[control])
    if 'blk' in kw:
        kw['blk'] = block(kw['blk'])
    got, seq = served_alone(16, 35, **kw)
    assert rel(got, want(padded(seq))[34:38]) > 0.05


def test_a_slots_old_state_read_at_position_zero_is_far():
    eng = engine(16)
    never_from_zero(eng)
    served_alone(16, 35, eng=eng)           # leaves its state in slot 1
    got, seq = served_alone(16, 21, eng=eng)
    assert rel(got, want(padded(seq))[20:24]) > 0.05


@pytest.mark.parametrize('control', sorted(REFERENCE_CONTROLS))
def test_a_reference_told_one_thing_wrong_is_far_from_the_engine(control):
    got, seq = served_alone(16, 35)
    logits = want(padded(seq), **REFERENCE_CONTROLS[control])
    assert rel(got, logits[34:38]) > 0.05


# -- what the cache, the spans and the server count -------------------------

def test_the_cache_holds_pages_for_two_kinds_and_a_state_a_slot():
    eng = interleaved()['eng']
    cache = eng.cache
    assert eng.state_runs == [(0, 2), (3, 3)]
    assert cache.state_layers == {0, 1, 3, 4, 5} and cache.slots == 1
    assert [p is None for p in cache.k] == [True, True, False, True, True,
                                            True]
    assert cache.k[2].shape == cache.v[2].shape == (PAGES + 1, PAGE, DH)
    assert [b.shape for b in cache.ssm] == [(2, STREAMS + 1, N, DC),
                                            (3, STREAMS + 1, N, DC)]
    assert [b.shape for b in cache.conv] == [(2, STREAMS + 1, (K - 1) * DC),
                                             (3, STREAMS + 1, (K - 1) * DC)]
    assert cache.state_bytes_per_stream() == STATE_BYTES
    groups = cache.group_bytes()
    assert groups == {'full': 2 * (PAGES + 1) * PAGE * DH * 4,
                      'state': (STREAMS + 1) * STATE_BYTES}
    assert eng.resident_bytes() == sum(groups.values())


def test_the_spans_say_what_the_state_layers_did():
    s = interleaved()
    compiles = [e['args'] for e in s['spans']
                if e['name'] == 'decode.compile']
    assert {c['program'] for c in compiles} == {'chunk', 'step'}
    pools = s['eng'].cache.group_bytes()
    for c in compiles:
        assert c['layer_kinds'] == {'full': 1, 'state': 5}
        assert c['state_rows'] == {'ssm': [N, DC], 'conv': [(K - 1) * DC]}
        assert c['state_bytes_per_stream'] == STATE_BYTES
        assert c['state_pool_bytes'] == pools['state']
        assert c['pool_bytes'] == pools
        assert c['alias_bytes'] == sum(pools.values())
        assert c['ssm'] == ({'xla_scan': 5} if c['program'] == 'chunk'
                            else {})
    chunks = [e['args'] for e in s['spans']
              if e['name'] == 'decode.prefill_chunk']
    # a: 16 + 16 + 5 alone; b: 16 + 5 carrying a; c: 11 carrying b
    assert [(c['ssm_scan_tokens'], c['ssm_from_zero'], c['step_rows'])
            for c in chunks] == [(16, True, 0), (16, False, 0),
                                 (5, False, 0), (16, True, 1), (5, False, 1),
                                 (11, True, 1)]
    for c in chunks:
        assert ('ssm_live_slots' in c) == bool(c['step_rows'])
    steps = [e['args'] for e in s['spans'] if e['name'] == 'decode.step']
    assert [c['ssm_live_slots'] for c in steps] == [2, 2, 2, 1, 1]
    assert all(c['ssm_state_bytes'] == c['ssm_live_slots'] * STATE_BYTES
               for c in steps)
    assert s['eng'].ssm_state_bytes == (3 + 6 + 2) * STATE_BYTES

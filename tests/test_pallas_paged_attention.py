"""Pallas paged-attention kernel vs ``paged_attention_math``, and the
op's dispatch between the two.

Runs interpret=True on the CPU backend — same kernel code that compiles
to Mosaic on TPU (tests/test_tpu_lowering.py compiles it at the decode
engine's shapes).
"""
import functools
import math
from types import SimpleNamespace

import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.core.registry import get_op_impl
from paddle_tpu.ops import pallas as pallas_kernels
from paddle_tpu.ops.attention import (paged_attention_math,
                                      paged_attention_path)
from paddle_tpu.ops.pallas import paged_attention
from paddle_tpu.ops.pallas.paged_attention import supported

P, MPP, N = 16, 10, 24          # max_seq 160: two blocks of 8 pages
MAX_SEQ = P * MPP
CTX_LENS = [1, P - 1, P, P + 1, 77, MAX_SEQ]
# bf16 pools: q and the probabilities are rounded to bf16 for the MXU
# (f32 accumulation), the math multiplies in f32: a few bf16 ulps of an
# output of scale ~0.3.  f32 pools: the order of the sums only.
TOL = {'bfloat16': 1e-2, 'float32': 2e-5}


def make(seed, h, d, dtype, ndim=3, page=P, n=N, s=len(CTX_LENS), mpp=MPP):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(s, h, d), jnp.float32)
    shape = (n, page, h * d) if ndim == 3 else (n, page, h, d)
    k = jnp.asarray(rng.randn(*shape), dtype)
    v = jnp.asarray(rng.randn(*shape), dtype)
    # a slot's pages distinct and scattered over the pool
    pt = jnp.asarray(np.stack([rng.permutation(n)[:mpp]
                               for _ in range(s)]), jnp.int32)
    return q, k, v, pt


def both(q, k, v, pt, ctx, **kw):
    ctx = jnp.asarray(ctx, jnp.int32)
    got = paged_attention(q, k, v, pt, ctx, interpret=True, **kw)
    want = paged_attention_math(q, k, v, pt, ctx, **kw)
    assert got.shape == want.shape == q.shape and got.dtype == q.dtype
    return np.asarray(got), np.asarray(want)


def close(got, want, dtype):
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=TOL[jnp.dtype(dtype).name])


@pytest.mark.parametrize('ndim', [3, 4])
@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize('head_dim', [64, 128, 256])
def test_kernel_matches_math(head_dim, dtype, ndim):
    q, k, v, pt = make(1, 2, head_dim, dtype, ndim)
    close(*both(q, k, v, pt, CTX_LENS), dtype)


@pytest.mark.parametrize('ctx_len', CTX_LENS)
def test_every_context_length_alone(ctx_len):
    # one slot at a time: nothing of a longer neighbour's blocks is left
    # in the buffers or the running sums
    q, k, v, pt = make(2, 2, 128, jnp.float32, s=3)
    close(*both(q, k, v, pt, [ctx_len, 1, ctx_len]), jnp.float32)


def test_the_opt_row_of_32_heads_of_64():
    # two heads to a 128-lane register, 32 query rows against the 2048-
    # wide row; contexts of one position, a page and one, 100 pages, and
    # a slot that holds nothing
    ctx = [1, 17, 100 * P, 0, 5 * P]
    q, k, v, pt = make(9, 32, 64, jnp.float32, n=140, s=len(ctx), mpp=128)
    assert supported(32, 64, P, jnp.float32)
    got, want = both(q, k, v, pt, ctx)
    live = np.array(ctx) > 0
    close(got[live], want[live], jnp.float32)
    # the math averages what an empty slot's table points at: the kernel
    # reads nothing and says zero
    assert np.array_equal(got[~live], np.zeros_like(got[~live]))


def test_f32_pages_of_eight_rows_and_a_scale():
    # a page of one f32 sublane tile, 16 pages a block, three blocks
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(4, 3, 128), jnp.float32)
    k = jnp.asarray(rng.randn(50, 8, 3 * 128), jnp.float32)
    v = jnp.asarray(rng.randn(50, 8, 3 * 128), jnp.float32)
    pt = jnp.asarray(rng.randint(0, 50, (4, 40)), jnp.int32)
    close(*both(q, k, v, pt, [320, 129, 128, 7], scale=0.05), jnp.float32)


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
def test_repeated_trash_and_out_of_range_page_ids(dtype):
    q, k, v, pt = make(4, 2, 128, dtype, s=4)
    pt = np.array(pt)
    pt[0, :] = 5                          # one page, ten times
    pt[1, :] = N - 1                      # an idle slot: all trash
    pt[2, ::2] = [-3, N, N + 40, -1, 10 ** 6]     # clipped as the math
    pt[3, 3:] = N - 1                     # trash past the live pages
    got, want = both(q, k, v, jnp.asarray(pt), [MAX_SEQ, 1, 150, 40])
    close(got, want, dtype)
    # the idle slot attends over one row: that row of V, head by head
    np.testing.assert_allclose(
        got[1].reshape(-1), np.asarray(v[N - 1, 0], np.float32),
        rtol=0, atol=TOL[jnp.dtype(dtype).name])


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
def test_nothing_past_the_live_pages_is_read_or_multiplied(dtype):
    """Every page no slot may read, and every row past ``ctx_len`` in a
    last page, set to NaN: the result is finite and the clean pool's."""
    q, k, v, _ = make(5, 2, 128, dtype)
    ctx = np.array(CTX_LENS)
    pages = -(-ctx // P)
    # live pages distinct over all slots; a table's other entries point
    # at pages nobody may read
    order = np.random.RandomState(5).permutation(N)
    live, dead = order[:pages.sum()], order[pages.sum():]
    assert len(dead) >= 2
    pt = np.resize(dead, (len(ctx), MPP))
    for s, (lo, n) in enumerate(zip(np.cumsum(pages) - pages, pages)):
        pt[s, :n] = live[lo:lo + n]
    pt = jnp.asarray(pt, jnp.int32)
    clean, _ = both(q, k, v, pt, ctx)
    kp, vp = (np.array(x.astype(jnp.float32)) for x in (k, v))
    for pool in (kp, vp):
        pool[dead] = np.nan
        for s, (c, n) in enumerate(zip(ctx, pages)):
            pool[int(pt[s, n - 1]), c - (n - 1) * P:] = np.nan
    poisoned = paged_attention(
        q, jnp.asarray(kp, dtype), jnp.asarray(vp, dtype), pt,
        jnp.asarray(ctx, jnp.int32), interpret=True)
    assert np.all(np.isfinite(np.asarray(poisoned)))
    assert np.array_equal(np.asarray(poisoned), clean)
    # the math masks the same rows after multiplying them: it reads them
    assert not np.all(np.isfinite(np.asarray(paged_attention_math(
        q, jnp.asarray(kp, dtype), jnp.asarray(vp, dtype), pt,
        jnp.asarray(ctx, jnp.int32)))))


def test_a_slot_with_no_context_reads_nothing():
    q, k, v, pt = make(6, 2, 128, jnp.float32, s=2)
    k = k.at[:].set(jnp.nan)
    got = paged_attention(q, k, v, pt, jnp.asarray([0, 0], jnp.int32),
                          interpret=True)
    assert np.array_equal(np.asarray(got), np.zeros(q.shape, np.float32))


# -- the op's dispatch ------------------------------------------------------

def run_op(backend, q, k, v, pt, ctx):
    return np.asarray(get_op_impl('paged_attention').compute(
        SimpleNamespace(backend=backend),
        {'Q': [q], 'KPool': [k], 'VPool': [v], 'PT': [pt],
         'CtxLen': [jnp.asarray(ctx, jnp.int32)]}, {})['Out'][0])


@pytest.mark.parametrize('backend,head_dim,dtype,page', [
    ('tpu', 48, jnp.bfloat16, 16),      # a row of 96 lanes: no whole vreg
    ('tpu', 48, jnp.float32, 16),
    ('cpu', 128, jnp.bfloat16, 16),     # not a TPU
    ('gpu', 128, jnp.float32, 16),
    ('tpu', 128, jnp.bfloat16, 8),      # half a bf16 sublane tile a page
    ('tpu', 256, jnp.float32, 4),
], ids=lambda x: getattr(x, '__name__', str(x)))
def test_dispatch_falls_back_to_the_math_bit_for_bit(
        backend, head_dim, dtype, page):
    assert paged_attention_path(backend, 2, head_dim, page, dtype) \
        == 'xla_gather'
    q, k, v, pt = make(7, 2, head_dim, dtype, page=page, s=3)
    ctx = [1, 3 * page + 1, MPP * page]
    want = np.asarray(paged_attention_math(
        q, k, v, pt, jnp.asarray(ctx, jnp.int32)))
    assert np.array_equal(run_op(backend, q, k, v, pt, ctx), want)


@pytest.mark.parametrize('head_dim,dtype,page', [
    (128, jnp.bfloat16, 16), (128, jnp.bfloat16, 32), (256, jnp.bfloat16, 16),
    (128, jnp.float32, 8), (128, jnp.float32, 16), (256, jnp.float32, 16),
    (64, jnp.bfloat16, 16), (64, jnp.float32, 16),  # two heads to a vreg
], ids=lambda x: getattr(x, '__name__', str(x)))
def test_dispatch_takes_the_kernel_on_a_tpu(monkeypatch, head_dim, dtype,
                                            page):
    assert supported(2, head_dim, page, dtype)
    assert paged_attention_path('tpu', 2, head_dim, page, dtype) \
        == 'pallas_paged'
    # the op calls the package's entry point: here, interpreted
    calls = []

    def kernel(*args, **kw):
        calls.append(kw)
        return paged_attention(*args, interpret=True, **kw)
    monkeypatch.setattr(pallas_kernels, 'paged_attention', kernel)
    q, k, v, pt = make(8, 2, head_dim, dtype, page=page, s=2)
    ctx = [page + 1, MPP * page]
    got = run_op('tpu', q, k, v, pt, ctx)
    assert calls == [{'scale': None}]
    close(got, np.asarray(paged_attention_math(
        q, k, v, pt, jnp.asarray(ctx, jnp.int32))), dtype)


# -- query heads in groups over fewer K/V heads; a window of the newest
# positions, the page table a ring; a prompt chunk's rows ------------------

from paddle_tpu.ops.attention import (chunk_attention_path,  # noqa: E402
                                      chunked_prefill_attention_math)
from paddle_tpu.ops.pallas.paged_attention import (  # noqa: E402
    chunk_blocks, chunk_paged_attention, chunk_supported)

HKV, DG = 2, 32
# a window, the chunk rows that go with it, and the ring that holds both
# and a page to spare
RINGS = {None: (8, 12), 512: (16, 34), 5: (8, 2)}


def grouped(seed, group, window, dtype=jnp.float32, n=60):
    rng = np.random.RandomState(seed)
    rows, mpp = RINGS[window]
    mpp = max(mpp, -(-((window or 1) - 1 + rows) // P) + 1)
    k = jnp.asarray(rng.randn(n, P, HKV * DG), dtype)
    v = jnp.asarray(rng.randn(n, P, HKV * DG), dtype)
    return rng, rows, mpp, k, v


@pytest.mark.parametrize('window', [None, 512, 5])
@pytest.mark.parametrize('group', [1, 6, 9])
def test_step_rows_of_grouped_heads_under_a_window(group, window):
    rng, _rows, mpp, k, v = grouped(11, group, window)
    # with a window a context is any length: the ring holds its newest
    ctx = [1, P, P + 1, 77, mpp * P] if window is None else \
        [1, P, P + 1, 77, mpp * P, 513, 600, 2000]
    s, h = len(ctx), HKV * group
    q = jnp.asarray(rng.randn(s, h, DG), jnp.float32)
    pt = jnp.asarray(np.stack([rng.permutation(60)[:mpp]
                               for _ in range(s)]), jnp.int32)
    kw = {} if window is None else {'window': window}
    close(*both(q, k, v, pt, ctx, **kw), jnp.float32)


@pytest.mark.parametrize('window', [None, 512, 5])
@pytest.mark.parametrize('group', [1, 6, 9])
def test_chunk_rows_of_grouped_heads_under_a_window(group, window):
    rng, rows, mpp, k, v = grouped(12, group, window)
    pt = jnp.asarray(rng.permutation(60)[:mpp], jnp.int32)
    kw = {} if window is None else {'window': window}
    # the first chunk, one in mid-table, and with a window one far behind
    # which the ring has wrapped many times
    for pos0 in [0, 3 * rows] + ([40 * rows] if window else []):
        q = jnp.asarray(rng.randn(rows, HKV * group, DG), jnp.float32)
        want = chunked_prefill_attention_math(q, k, v, pt, jnp.int32(pos0),
                                              **kw)
        # passes of 4 tokens over blocks of 2 pages, and the defaults
        for tile in ({'tokens': 4, 'block_positions': 2 * P}, {})[
                window == 512:]:
            got = chunk_paged_attention(q, k, v, pt, jnp.int32(pos0),
                                        interpret=True, **kw, **tile)
            assert got.shape == want.shape == q.shape
            close(np.asarray(got), np.asarray(want), jnp.float32)


def test_bf16_pools_under_grouped_heads():
    rng, rows, mpp, k, v = grouped(13, 6, 512, jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(60)[:mpp], jnp.int32)
    q = jnp.asarray(rng.randn(rows, HKV * 6, DG), jnp.float32)
    close(np.asarray(chunk_paged_attention(
        q, k, v, pt, jnp.int32(700), window=512, interpret=True)),
        np.asarray(chunked_prefill_attention_math(
            q, k, v, pt, jnp.int32(700), window=512)), jnp.bfloat16)
    ctx = jnp.asarray([3, 700], jnp.int32)
    close(*both(q[:2], k, v, jnp.stack([pt, pt]), ctx, window=512),
          jnp.bfloat16)


def test_a_window_leaves_the_pages_behind_it_unread():
    """Pages wholly before the window, the ring's columns that hold them
    and the pool's other pages are NaN: neither the kernels nor the math
    are moved."""
    rng, rows, mpp, k, v = grouped(14, 6, 5)
    pt = np.asarray(rng.permutation(59)[:mpp])
    ctx, pos0 = 3 * P + 2, 3 * P + 2 - rows
    live = {int(pt[j % mpp]) for j in range((pos0 + 1 - 5) // P,
                                            (ctx - 1) // P + 1)}
    dead = np.array([g for g in range(60) if g not in live])
    kn, vn = (a.at[dead].set(jnp.nan) for a in (k, v))
    q = jnp.asarray(rng.randn(rows, HKV * 6, DG), jnp.float32)
    pt = jnp.asarray(pt, jnp.int32)
    want = chunked_prefill_attention_math(q, k, v, pt, jnp.int32(pos0),
                                          window=5)
    for attend in (chunked_prefill_attention_math,
                   functools.partial(chunk_paged_attention, interpret=True)):
        got = np.asarray(attend(q, kn, vn, pt, jnp.int32(pos0), window=5))
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)
    step = jnp.asarray([ctx], jnp.int32)
    got, want = both(q[:1], kn, vn, pt[None], step, window=5)
    assert np.all(np.isfinite(got)) and np.all(np.isfinite(want))
    close(got, np.asarray(paged_attention_math(
        q[:1], k, v, pt[None], step, window=5)), jnp.float32)


# -- the chunk kernel's blocks: those that every row of a pass sees whole
# (no mask can bite there), those an edge of the mask crosses, and what
# ``chunk_blocks`` counts of both ---------------------------------------------

# passes of 4 tokens over blocks of 2 pages (32 positions), chunks of 8
# rows: (window, pos0, whole blocks of the chunk's two passes)
TILE = {'tokens': 4, 'block_positions': 2 * P}
SPLITS = {
    'first_chunk': (None, 0, 0),            # every block holds the rows
    # the first pass's ``tok0 + 1`` one position before a block's edge,
    # on it, one past it (the second pass's is 4 further on)
    'before_the_edge': (None, 62, 1 + 2),
    'on_the_edge': (None, 63, 2 + 2),
    'past_the_edge': (None, 64, 2 + 2),
    '33_blocks': (None, 33 * 32 - 8, 32 + 32),
    # rings that have wrapped: blocks wholly inside every row's window
    'window_512': (512, 1500, 15 + 15),
    'window_5': (5, 83, 0),
}


def split_case(name, group, dtype=jnp.float32):
    window, pos0, whole = SPLITS[name]
    rng = np.random.RandomState(len(name))
    mpp = 70 if window is None else -(-(window - 1 + 8) // P) + 1
    k = jnp.asarray(rng.randn(80, P, HKV * DG), dtype)
    v = jnp.asarray(rng.randn(80, P, HKV * DG), dtype)
    q = jnp.asarray(rng.randn(8, HKV * group, DG), jnp.float32)
    pt = rng.permutation(79)[:mpp]
    kw = {} if window is None else {'window': window}
    return q, k, v, pt, pos0, kw, whole, mpp


@pytest.mark.parametrize('group', [6, 9])
@pytest.mark.parametrize('name', sorted(SPLITS))
def test_chunk_blocks_whole_and_crossed_by_the_mask(name, group):
    q, k, v, pt, pos0, kw, whole, mpp = split_case(name, group)
    assert chunk_blocks(pos0, 8, kw.get('window'), P, mpp, **TILE)[1] \
        == whole
    pt = jnp.asarray(pt, jnp.int32)
    want = chunked_prefill_attention_math(q, k, v, pt, jnp.int32(pos0), **kw)
    # the tile that puts the edges where the case says, and the defaults
    for tile in (TILE, {}):
        got = chunk_paged_attention(q, k, v, pt, jnp.int32(pos0),
                                    interpret=True, **kw, **tile)
        close(np.asarray(got), np.asarray(want), jnp.float32)


@pytest.mark.parametrize('name', ['33_blocks', 'window_512', 'window_5'])
def test_a_chunk_reads_nothing_outside_its_rows_pages(name):
    """NaN in every page before the first row's oldest position's and
    after the last row's own, and in that last page's rows past the last
    row: the result is finite and the clean pool's, through whole blocks
    and blocks the mask crosses alike."""
    q, k, v, pt, pos0, kw, _whole, mpp = split_case(name, 6)
    window, ctx = kw.get('window'), pos0 + 8
    lo = 0 if window is None else max(pos0 + 1 - window, 0)
    live = {int(pt[j % mpp]) for j in range(lo // P, (ctx - 1) // P + 1)}
    dead = np.array([g for g in range(80) if g not in live])
    kn, vn = (a.at[dead].set(jnp.nan).at[int(pt[(ctx - 1) // P % mpp]),
                                         (ctx - 1) % P + 1:].set(jnp.nan)
              for a in (k, v))
    pt = jnp.asarray(pt, jnp.int32)
    for tile in (TILE, {}):
        clean, got = (np.asarray(chunk_paged_attention(
            q, kk, vv, pt, jnp.int32(pos0), interpret=True, **kw, **tile))
            for kk, vv in ((k, v), (kn, vn)))
        assert np.all(np.isfinite(got))
        assert np.array_equal(got, clean)


@pytest.mark.parametrize('window', [None, 5, 40, 512])
@pytest.mark.parametrize('block_positions', [P, 2 * P, 128])
def test_the_count_of_whole_blocks_is_the_masks_own(window, block_positions):
    """``chunk_blocks`` against the blocks a pass covers and the mask a
    row applies, written out position by position."""
    mpp = 70 if window is None else -(-(window - 1 + 24) // P) + 1
    for rows, tokens in ((8, 4), (24, 8), (16, 128)):
        for pos0 in list(range(0, 70)) + [127, 128, 500, 1001]:
            if window is None and pos0 + rows > mpp * P:
                continue
            blocks = whole = 0
            per, t = math.gcd(rows, tokens), \
                max(1, min(mpp, block_positions // P)) * P
            for tok0 in range(pos0, pos0 + rows, per):
                first = 0 if window is None else \
                    max(tok0 + 1 - window, 0) // P * P
                row = tok0 + np.arange(per)[:, None]
                for at in range(first, tok0 + per, t):
                    pos = at + np.arange(t)[None, :]
                    live = pos <= row
                    if window is not None:
                        live &= pos > row - window
                    blocks, whole = blocks + 1, whole + bool(live.all())
            assert chunk_blocks(pos0, rows, window, P, mpp, tokens=tokens,
                                block_positions=block_positions) \
                == (blocks, whole), (rows, tokens, pos0)


def test_paths_by_the_row_the_group_and_the_table():
    bf16 = jnp.bfloat16
    # the step: the K/V row and the page decide; a group of query heads
    # needs K/V heads that fill sublane tiles
    assert supported(8, 128, 16, bf16, 6) and supported(8, 128, 16, bf16, 9)
    assert not supported(2, 128, 16, bf16, 6)
    assert paged_attention_path('tpu', 8, 128, 16, bf16, 9) == 'pallas_paged'
    assert paged_attention_path('tpu', 2, 128, 16, bf16, 9) == 'xla_gather'
    assert paged_attention_path('cpu', 8, 128, 16, bf16, 9) == 'xla_gather'
    # a chunk's rows: heads of whole registers, and a table wide enough
    # that a row's scores are not worth gathering
    assert chunk_supported(8, 128, 16, bf16)
    assert not chunk_supported(32, 64, 16, bf16)
    for heads, table, want in ((16, 64, 'xla_gather'),      # 1024 positions
                               (72, 65, 'pallas_paged'),    # a ring of 1040
                               (48, 1088, 'pallas_paged')):
        assert chunk_attention_path('tpu', 8, 128, 16, bf16, heads,
                                    table) == want
        assert chunk_attention_path('cpu', 8, 128, 16, bf16, heads,
                                    table) == 'xla_gather'


def test_the_chunk_op_takes_the_kernel_where_the_path_says(monkeypatch):
    import sys
    calls = []

    def kernel(*args, **kw):
        calls.append(kw)
        return chunk_paged_attention(*args, interpret=True, **kw)
    # (the package re-exports a function under the module's name)
    kernels = sys.modules['paddle_tpu.ops.pallas.paged_attention']
    monkeypatch.setattr(kernels, 'chunk_paged_attention', kernel)
    rng = np.random.RandomState(15)
    k = jnp.asarray(rng.randn(80, P, 8 * 128), jnp.float32)
    v = jnp.asarray(rng.randn(80, P, 8 * 128), jnp.float32)
    q = jnp.asarray(rng.randn(8, 72, 128), jnp.float32)
    pt = jnp.asarray(rng.permutation(79)[:65], jnp.int32)
    op = get_op_impl('chunked_prefill_attention')
    ins = {'Q': [q], 'KPool': [k], 'VPool': [v], 'PT': [pt],
           'Pos0': [jnp.int32(600)]}
    got = op.compute(SimpleNamespace(backend='tpu'), ins,
                     {'window': 512})['Out'][0]
    assert calls == [{'scale': None, 'window': 512}]
    want = op.compute(SimpleNamespace(backend='cpu'), ins,
                      {'window': 512})['Out'][0]
    assert calls == [{'scale': None, 'window': 512}]    # the math
    close(np.asarray(got), np.asarray(want), jnp.float32)


# -- slots without context: the step kernels skip them, and say zero --------

from jax.experimental.pallas import tpu as pltpu  # noqa: E402
from paddle_tpu.ops.pallas.paged_attention import (  # noqa: E402
    latent_paged_attention)

# the TPU's interpreter copies blocks as the chip's pipeline does (none
# whose index did not change, an output when its index moves on) and
# starts the output and every buffer as NaN: what a skipped grid step
# leaves unwritten shows, as it does on a chip and never in
# ``interpret=True``'s block-by-block copies
POISONED = pltpu.InterpretParams(uninitialized_memory='nan')
SLOTS = 6
IDLE = {'leading': [0, 0, 0, 1, 1, 1], 'trailing': [1, 1, 1, 0, 0, 0],
        'alternating': [0, 1, 0, 1, 0, 1], 'all_but_one': [0, 0, 0, 0, 1, 0],
        'all': [0] * SLOTS}
# (query heads a K/V head, K/V heads, head width, the pools' dtype); the
# latent kernel: tokens a group
STEP_KERNELS = {'group1_f32': (1, HKV, DG, jnp.float32),
                'group6_f32': (6, HKV, DG, jnp.float32),
                'group9_f32': (9, HKV, DG, jnp.float32),
                'group6_bf16': (6, HKV, DG, jnp.bfloat16),
                'one_kv_head_bf16': (5, 1, 128, jnp.bfloat16),
                'latent_group1': 1, 'latent_group8': 8}
DEAD = slice(50, 60)        # the pool's pages behind the idle slots' tables


def step_kernel(kernel, window):
    """-> (run(q, pt, ctx) of any number of slots, the query rows a slot,
    q of ``SLOTS`` slots, the table's columns, three live lengths).  The
    pools are NaN on the ``DEAD`` pages."""
    rng = np.random.RandomState(16)
    mpp = max(RINGS[window][1], 12)
    if kernel.startswith('latent'):
        per = STEP_KERNELS[kernel]
        pool = jnp.asarray(rng.randn(60, P, 192), jnp.bfloat16) \
            .at[DEAD].set(jnp.nan)
        q = jnp.asarray(rng.randn(SLOTS * per, 4, 192), jnp.float32)

        def run(q, pt, ctx):
            return latent_paged_attention(q, pool, pt, ctx, 0.1, 128,
                                          group=per, interpret=POISONED)
        return run, per, q, mpp, [8, 77, mpp * P]
    group, kv_heads, d, dtype = STEP_KERNELS[kernel]
    k, v = (jnp.asarray(rng.randn(60, P, kv_heads * d), dtype)
            .at[DEAD].set(jnp.nan) for _ in range(2))
    q = jnp.asarray(rng.randn(SLOTS, kv_heads * group, d), jnp.float32)
    kw = {} if window is None else {'window': window}

    def run(q, pt, ctx):
        return paged_attention(q, k, v, pt, ctx, interpret=POISONED, **kw)
    # (under a window a context is any length: the ring holds its newest)
    return run, 1, q, mpp, [P + 1, 77, mpp * P if window is None else 2000]


@pytest.mark.parametrize('pattern', sorted(IDLE))
@pytest.mark.parametrize('kernel,window', [
    (k, w) for k in sorted(STEP_KERNELS)
    for w in ([None] if k.startswith('latent') else [None, 512, 5])])
def test_idle_slots_are_skipped_and_zero(kernel, window, pattern):
    """Live rows are, bit for bit, the kernel's on that slot alone,
    whatever its neighbours; a slot without context comes out exactly
    zero though its step wrote nothing, the output buffer began as NaN
    and its table points at pages of NaN."""
    run, per, q, mpp, lengths = step_kernel(kernel, window)
    rng = np.random.RandomState(17)
    live = np.array(IDLE[pattern], bool)
    ctx = np.zeros(SLOTS, np.int32)
    ctx[live] = (lengths * 2)[:live.sum()]
    pt = np.stack([rng.permutation(50)[:mpp] if on else
                   50 + rng.permutation(10)[np.arange(mpp) % 10]
                   for on in live]).astype(np.int32)
    got = np.asarray(run(q, jnp.asarray(pt), jnp.asarray(ctx)))
    assert got.shape[0] == SLOTS * per and got.dtype == np.float32
    got = got.reshape((SLOTS, per) + got.shape[1:])
    assert np.all(np.isfinite(got))
    assert np.array_equal(got[~live], np.zeros_like(got[~live]))
    for s in np.flatnonzero(live):
        alone = run(q[s * per:(s + 1) * per], jnp.asarray(pt[s:s + 1]),
                    jnp.asarray(ctx[s:s + 1]))
        assert np.array_equal(got[s], np.asarray(alone)), s
        assert np.any(got[s] != 0)

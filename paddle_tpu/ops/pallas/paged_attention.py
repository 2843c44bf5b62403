"""Paged attention as Pallas TPU kernels: live pages only.

``ops/attention.py`` ``paged_attention_math`` gathers ``MPP * P``
positions for every slot whatever its context is, widens them to f32
and re-lays them out for XLA's multiply-reduce: three passes over
``S * max_seq`` rows of HBM a layer.  The decode kernel reads each
slot's ``ceil(ctx_len / P)`` live pages straight from the pool as the
engine holds it, ``[N, P, H*D]``: the pools stay in HBM, the page table
and the context lengths are scalar-prefetched, and the kernel copies
blocks of pages to VMEM itself (one DMA a page, the next block in
flight while this one is multiplied).  One grid step is one slot, and
the step of a slot without context is bare: no query copied in, no
scratch set, no output written (``_held_slots``, ``_skip_idle``).  A
block is ``[T, H*D]`` with every head's keys side by side on the lanes,
so all heads go through the MXU at once against a block-diagonal query
``[H, H*D]`` (row h holds q_h on head h's lanes): scores ``[H, T]``,
f32 online softmax over blocks, ``p @ V`` ``[H, H*D]`` whose diagonal
is picked once, at the end.  The mask is lane arithmetic, so a head may
be any width (OPT's 64); ``supported`` asks for a ROW ``H * D`` of
whole 128-lane registers and pages of whole sublane tiles.

Same mathematics as the math: every live position of every head, f32
scores, softmax and accumulation, K and V as stored (bf16 pools: bf16
inputs to the products, f32 accumulation; f32 pools: ``highest``);
positions ``>= ctx_len`` are left out, and a slot with ``ctx_len == 0``
reads nothing and returns zeros.  A prompt chunk's rows walk the same
pages 128 tokens a pass (``chunk_paged_attention``; what a block of
it costs, and what was measured of it, stands over ``_CHUNK_TOKENS``);
a shared latent row has its own kernel (``latent_paged_attention``).
"""
import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['paged_attention', 'supported', 'chunk_paged_attention',
           'chunk_supported', 'chunk_blocks', 'latent_paged_attention',
           'latent_supported']

_NEG_INF = -1e30
# positions a block: 8 pages of 16.  Measured on a v5e, one layer of 32
# slots x 16 heads of 128, bf16, ten slots running at contexts 32-1024:
# 0.099 ms at 128, 0.112 at 256, 0.140 at 512 (the first block of a slot
# is not overlapped, so a larger one waits longer); every slot at 1024:
# 0.40 ms, 670 GB/s over the live bytes (PERF.md section 6, PR 28)
_BLOCK_POSITIONS = 128


def _sublane_rows(dtype):
    """Rows of one (sublane, 128-lane) tile: 8 for f32, 16 for bf16."""
    return 32 // jnp.dtype(dtype).itemsize


def supported(n_kv_heads, head_dim, page_size, dtype, group=1):
    """Whether the kernel takes these shapes: a K/V row ``Hkv * D`` of
    whole 128-lane registers, whatever the width of a head, and pages
    that are whole sublane tiles of the pool's dtype (a page is one DMA
    into a tile-aligned slice of the block).  ``group`` query heads over
    each K/V head are laid out a group member at a time, ``Hkv`` rows
    each, which then have to be whole float32 sublane tiles; over ONE
    K/V head (multi-query attention) the query heads are the rows as
    they are."""
    return (n_kv_heads * head_dim) % 128 == 0 and \
        page_size % _sublane_rows(dtype) == 0 and \
        (group == 1 or n_kv_heads % 8 == 0 or n_kv_heads == 1)


def _live_pages(ctx, page, window):
    """(first live page, live pages, first live position) of a reader
    of ``ctx`` positions: everything, or the ``window`` newest."""
    if window is None:
        return 0, pl.cdiv(ctx, page), 0
    lo = jnp.maximum(ctx - window, 0)
    first = lo // page
    return first, pl.cdiv(ctx, page) - first, lo


# -- a slot without context costs the step kernels a bare grid step ---------

def _held_slots(ctx_len):
    """The slot whose query and output blocks each grid step holds: its
    own where the slot is live, else the live slot's before it (slot
    0's, before any).  Pallas copies no block whose index did not
    change, so an idle step moves no query in and no output out.
    Arithmetic on ``ctx_len`` alone, no sort: one small reduction that
    the layers of a step share."""
    at = jnp.arange(ctx_len.shape[0], dtype=jnp.int32)
    live_before = (ctx_len > 0)[None, :] & (at[None, :] <= at[:, None])
    return jnp.max(jnp.where(live_before, at[None, :], 0), axis=1)


def _skip_idle(body):
    """``body(slot, pt_ref, len_ref, *refs)`` as the kernel of a call
    whose third prefetched scalars are ``_held_slots``: the step of a
    slot without context runs nothing of it (no scratch set, no query
    laid out, no output written)."""
    def kernel(pt_ref, len_ref, held_ref, *refs):
        del held_ref                        # the index maps read it
        slot = pl.program_id(0)
        pl.when(len_ref[slot] > 0)(
            functools.partial(body, slot, pt_ref, len_ref, *refs))
    return kernel


def _zero_idle(out, ctx_len):
    """``out`` [S, rows, lanes] with zeros in the rows of the slots
    without context: a skipped step wrote nothing there, so they hold
    whatever the buffer held."""
    return jnp.where((ctx_len > 0)[:, None, None], out,
                     jnp.zeros((), out.dtype))


def _kernel(s, pt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
            sem, m_scr, l_scr, acc_scr, *, scale, page, ppb, mpp,
            head_dim, kv_heads, group, window, precision):
    ctx = len_ref[s]            # of slot ``s``, this grid step's: > 0
    # live pages of this slot: with a window, the pages that hold its
    # ``window`` newest positions, in a table that is a ring
    first, n_pages, lo = _live_pages(ctx, page, window)
    n_blocks = pl.cdiv(n_pages, ppb)
    hp, hd = acc_scr.shape
    t = ppb * page

    def on_live_pages(blk, slot, act):
        # one DMA a page, a page past the live ones none at all
        for j in range(ppb):
            @pl.when(blk * ppb + j < n_pages)
            def _():
                if window is None:
                    pid = pt_ref[s * mpp + blk * ppb + j]
                else:
                    pid = pt_ref[s * mpp + jax.lax.rem(
                        first + blk * ppb + j, mpp)]
                rows = pl.ds(j * page, page)
                act(pltpu.make_async_copy(
                    k_hbm.at[pid], k_buf.at[slot, rows], sem.at[0, slot]))
                act(pltpu.make_async_copy(
                    v_hbm.at[pid], v_buf.at[slot, rows], sem.at[1, slot]))

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

    @pl.when(n_blocks > 0)
    def _first():
        on_live_pages(0, 0, start)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    # row r of the block-diagonal query holds a query head on the lanes
    # of the K/V head it reads.  One query head a K/V head: row h is q_h
    # on head h's lanes.  A group of them: member g of every group in
    # rows g * Hkv .., so row r reads K/V head r % Hkv
    row = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 1)
    if group > 1:
        row = jax.lax.rem(row, kv_heads)
    diag = (lane >= row * head_dim) & (lane < (row + 1) * head_dim)
    if group == 1 or kv_heads == 1:
        # (one K/V head under every query head: the rows are the query
        # heads, up to a whole tile, and every lane is theirs)
        q_rows = q_ref[0]
    else:
        pieces = [jnp.broadcast_to(q_ref[0, g:g + 1], (kv_heads, hd))
                  for g in range(group)]
        if hp > group * kv_heads:       # up to a whole tile of rows
            pieces.append(jnp.zeros((hp - group * kv_heads, hd),
                                    jnp.float32))
        q_rows = jnp.concatenate(pieces, axis=0)
    qbd = jnp.where(diag, q_rows, 0.0).astype(k_buf.dtype)

    def position(x):
        """A position among the live pages -> the stream's."""
        return x if window is None else first * page + x

    def block(blk, carry):
        slot = jax.lax.rem(blk, 2)

        @pl.when(blk + 1 < n_blocks)
        def _prefetch():
            on_live_pages(blk + 1, 1 - slot, start)

        on_live_pages(blk, slot, wait)

        @pl.when(ctx < position((blk + 1) * t))
        def _zero_dead_rows():
            # the last block's rows past ctx_len (a last page's tail,
            # pages not copied) hold whatever was there: their p is 0,
            # and 0 * NaN is not
            rows = position(blk * t + jax.lax.broadcasted_iota(
                jnp.int32, (t, 1), 0))
            v_buf[slot] = jnp.where(rows < ctx, v_buf[slot],
                                    jnp.zeros((), v_buf.dtype))

        sc = jax.lax.dot_general(
            qbd, k_buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * scale               # [hp, t]
        pos = position(
            blk * t + jax.lax.broadcasted_iota(jnp.int32, (hp, t), 1))
        live = pos < ctx
        if window is not None:
            # the first live page's positions before the window (its
            # rows are the stream's own: finite, and p is 0)
            live &= pos >= lo
        sc = jnp.where(live, sc, _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)        # a live block has a live column
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1,
                                                  keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v_buf.dtype), v_buf[slot], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    l = l_scr[...]
    out = acc_scr[...] / jnp.where(l > 0, l, 1.0)
    if group == 1:
        o_ref[0] = jnp.sum(jnp.where(diag, out, 0.0), axis=0,
                           keepdims=True).astype(o_ref.dtype)
    elif kv_heads == 1:
        o_ref[0] = out.astype(o_ref.dtype)
    else:
        out = jnp.where(diag, out, 0.0)
        for g in range(group):
            o_ref[0, g:g + 1] = jnp.sum(
                out[g * kv_heads:(g + 1) * kv_heads], axis=0,
                keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('scale', 'window', 'interpret'))
def paged_attention(q, k_pool, v_pool, page_table, ctx_len, scale=None,
                    window=None, interpret=False):
    """``paged_attention_math``'s signature and result: ``q`` [S, H, D],
    pools [N, P, Hkv*D] (a 4-D [N, P, Hkv, D] pool is viewed flat; on
    the TPU that view is a copy unless the pool is held flat, as the
    decode engine holds it), ``page_table`` [S, MPP], ``ctx_len`` [S].
    The pool's row says how many K/V heads there are; the H query heads
    are grouped over them (``h // (H / Hkv)``).  With ``window`` a slot
    reads its ``window`` newest positions: pages wholly before them are
    neither copied nor scored, and the table is a ring (logical page j
    in column ``j % MPP``).  Page ids are clipped to the pool as the
    math clips them.  The caller tests ``supported`` first."""
    s, h, d = q.shape
    n, page = k_pool.shape[0], k_pool.shape[1]
    mpp = page_table.shape[1]
    hd = math.prod(k_pool.shape[2:])
    kv_heads = hd // d
    group = h // kv_heads
    if scale is None:
        scale = float(d) ** -0.5
    dtype = k_pool.dtype
    ppb = max(1, min(mpp, _BLOCK_POSITIONS // page))
    hp = -(-h // 16) * 16         # query rows, a whole tile in any dtype
    kernel = functools.partial(
        _kernel, scale=scale, page=page, ppb=ppb, mpp=mpp, head_dim=d,
        kv_heads=kv_heads, group=group, window=window,
        precision=(jax.lax.Precision.HIGHEST if dtype == jnp.float32
                   else None))

    def queries():
        qf = q.astype(jnp.float32)
        if group == 1:
            return qf.reshape(s, 1, hd)
        if kv_heads == 1:       # the heads as rows, up to a whole tile
            return jnp.pad(qf, ((0, 0), (0, hp - h), (0, 0)))
        # member g of every group side by side on its K/V head's lanes
        return qf.reshape(s, kv_heads, group, d).transpose(
            0, 2, 1, 3).reshape(s, group, hd)

    rows = hp if kv_heads == 1 and group > 1 else group
    row = pl.BlockSpec((1, rows, hd), lambda i, pt, ln, held: (held[i], 0, 0))
    # (a ring holds the newest of any number of positions)
    ctx_len = jnp.clip(ctx_len.astype(jnp.int32), 0, mpp * page) \
        if window is None else jnp.maximum(ctx_len.astype(jnp.int32), 0)
    out = pl.pallas_call(
        _skip_idle(kernel),
        out_shape=jax.ShapeDtypeStruct((s, rows, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, ppb * page, hd), dtype),
                pltpu.VMEM((2, ppb * page, hd), dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hp, 1), jnp.float32),
                pltpu.VMEM((hp, 1), jnp.float32),
                pltpu.VMEM((hp, hd), jnp.float32)]),
        # the scoped-vmem default (16 MB) holds the double-buffered
        # blocks of a 2048-wide row; wider rows and f32 pools need more
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=64 * 1024 * 1024),
        name='paged_attention_live_pages',
        interpret=interpret,
    )(jnp.clip(page_table.astype(jnp.int32), 0, n - 1).reshape(-1),
      ctx_len, _held_slots(ctx_len),
      queries(), k_pool.reshape(n, page, hd), v_pool.reshape(n, page, hd))
    out = _zero_idle(out, ctx_len)
    if group == 1:
        return out.reshape(s, h, d)
    if kv_heads == 1:
        return out[:, :h]
    return out.reshape(s, group, kv_heads, d).transpose(
        0, 2, 1, 3).reshape(s, h, d)


# -- a prompt chunk's rows over the stream's live pages --------------------

# tokens of a chunk that share one pass over the stream's pages, and
# the positions a block of that pass holds.  Every pass copies the live
# context again, a DMA a page: 128 tokens a pass are a quarter of the
# copies of 32 (2.8 -> 2.5 ms a layer at 16 k, PR 51).  What a block
# costs (my chip runs, PR 57: 512 rows, 48 heads over 8, 16 k, bf16, the
# op with its re-layouts): 2.70 ms a layer; 2.56 with the copies off, so
# it computes; the two products alone 1.35 (206 GFLOP: 1.05 at the peak),
# with the scores' softmax and no ``p @ V`` 1.63: products and softmax
# add up.  The mask is NOT the softmax's cost: a body without it on the
# 31 blocks of 32 that every row sees whole (22.9 k bundles for 27.6 k,
# ~6.5 vector operations a score for ~10) took 2.69 ms and 7 s more of
# every set-up to trace: not kept.  Blocks of 128 / 256 / 384: 4.12 /
# 2.78 / 3.47.  Under a window ONE block for all a pass sees: 0.277 ms
# for 0.395 (``_chunk_tiles``).
_CHUNK_TOKENS = 128
_CHUNK_BLOCK_POSITIONS = 512


def chunk_supported(n_kv_heads, head_dim, page_size, dtype):
    """Whether ``chunk_paged_attention`` takes these shapes: what
    ``supported`` asks of the row and the page, and heads of whole
    128-lane registers (a K/V head is a lane slice of the block)."""
    return supported(n_kv_heads, head_dim, page_size, dtype) and \
        head_dim % 128 == 0


def _chunk_kernel(pt_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                  v_buf, sem, m_scr, l_scr, acc_scr, *, scale, page, ppb,
                  mpp, head_dim, kv_heads, group, tokens, window,
                  precision):
    i = pl.program_id(0)
    tok0 = pos_ref[0] + i * tokens   # the position of the first token
    ctx = tok0 + tokens              # what the LAST token reads, itself too
    # the pages between the first token's oldest position and the last
    # token's own, in blocks (the host counts them by the same function)
    first, n_pages, n_blocks = _chunk_pass(
        jnp, tok0, tokens, window, page, ppb)
    rows = tokens * group
    t = ppb * page

    def on_live_pages(blk, slot, act):
        for j in range(ppb):
            @pl.when(blk * ppb + j < n_pages)
            def _():
                at = first + blk * ppb + j
                if window is not None:
                    at = jax.lax.rem(at, mpp)
                pid = pt_ref[at]
                at = pl.ds(j * page, page)
                act(pltpu.make_async_copy(
                    k_hbm.at[pid], k_buf.at[slot, at], sem.at[0, slot]))
                act(pltpu.make_async_copy(
                    v_hbm.at[pid], v_buf.at[slot, at], sem.at[1, slot]))

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

    @pl.when(n_blocks > 0)
    def _first():
        on_live_pages(0, 0, start)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    # row r of a K/V head's queries is token r // group of this pass
    row_ctx = tok0 + 1 + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) // group

    def block(blk, carry):
        slot = jax.lax.rem(blk, 2)
        at = first * page + blk * t

        @pl.when(blk + 1 < n_blocks)
        def _prefetch():
            on_live_pages(blk + 1, 1 - slot, start)

        on_live_pages(blk, slot, wait)

        @pl.when(ctx < at + t)
        def _zero_dead_rows():
            # rows past the last token hold whatever was there: their p
            # is 0, and 0 * NaN is not
            pos = at + jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
            v_buf[slot] = jnp.where(pos < ctx, v_buf[slot],
                                    jnp.zeros((), v_buf.dtype))

        pos = at + jax.lax.broadcasted_iota(jnp.int32, (rows, t), 1)
        live = pos < row_ctx
        if window is not None:
            live &= pos >= row_ctx - window
        for kv in range(kv_heads):
            lanes = slice(kv * head_dim, (kv + 1) * head_dim)
            sc = jax.lax.dot_general(
                q_ref[0, kv], k_buf[slot, :, lanes],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=precision) * scale               # [rows, t]
            sc = jnp.where(live, sc, _NEG_INF)
            m_prev = m_scr[kv]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a block may hold no position of a row (its window starts
            # after it, or the block starts after the row): p is 0 there
            p = jnp.where(live, jnp.exp(sc - m_new), 0.0)
            l_scr[kv] = alpha * l_scr[kv] + jnp.sum(p, axis=1,
                                                    keepdims=True)
            m_scr[kv] = m_new
            acc_scr[kv] = acc_scr[kv] * alpha + jax.lax.dot_general(
                p.astype(v_buf.dtype), v_buf[slot, :, lanes],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    l = l_scr[...]
    o_ref[0] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('scale', 'window', 'tokens',
                                             'block_positions',
                                             'interpret'))
def chunk_paged_attention(q, k_pool, v_pool, page_table, pos0, scale=None,
                          window=None, tokens=_CHUNK_TOKENS,
                          block_positions=None, interpret=False):
    """``chunked_prefill_attention_math``'s signature and result from
    the stream's live pages alone: ``q`` [C, H, D], query j at absolute
    position ``pos0 + j``; pools [N, P, Hkv*D]; ``page_table`` [MPP]
    (a ring under ``window``, as ``paged_attention`` takes it).

    One grid step is ``tokens`` consecutive rows of the chunk (the
    largest common divisor of C and it): they share one pass over the pages
    between the first row's oldest position and the last row's own.  A
    block of pages is [T, Hkv*D] in VMEM; K/V head by K/V head, the
    ``tokens * (H / Hkv)`` query rows that read it multiply its lane
    slice, [rows, D] x [D, T] (no block-diagonal: a head is whole
    registers), with a causal and window mask a row, float32 online
    softmax over blocks and ``p @ V``'s slice.  The caller tests
    ``chunk_supported``."""
    c, h, d = q.shape
    n, page = k_pool.shape[0], k_pool.shape[1]
    mpp = page_table.shape[0]
    hd = math.prod(k_pool.shape[2:])
    kv_heads = hd // d
    group = h // kv_heads
    if scale is None:
        scale = float(d) ** -0.5
    dtype = k_pool.dtype
    tokens, ppb = _chunk_tiles(c, tokens, window, block_positions, page, mpp)
    passes, rows = c // tokens, tokens * group
    kernel = functools.partial(
        _chunk_kernel, scale=scale, page=page, ppb=ppb, mpp=mpp,
        head_dim=d, kv_heads=kv_heads, group=group, tokens=tokens,
        window=window,
        precision=(jax.lax.Precision.HIGHEST if dtype == jnp.float32
                   else None))
    # [passes, Hkv, tokens * group, D]: a K/V head's query rows together
    qg = q.astype(dtype).reshape(passes, tokens, kv_heads, group, d) \
        .transpose(0, 2, 1, 3, 4).reshape(passes, kv_heads, rows, d)
    spec = pl.BlockSpec((1, kv_heads, rows, d),
                        lambda i, pt, p0: (i, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((passes, kv_heads, rows, d),
                                       jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(passes,),
            in_specs=[spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=spec,
            scratch_shapes=[
                pltpu.VMEM((2, ppb * page, hd), dtype),
                pltpu.VMEM((2, ppb * page, hd), dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((kv_heads, rows, 1), jnp.float32),
                pltpu.VMEM((kv_heads, rows, 1), jnp.float32),
                pltpu.VMEM((kv_heads, rows, d), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=64 * 1024 * 1024),
        name='chunk_paged_attention_live_pages',
        interpret=interpret,
    )(jnp.clip(page_table.astype(jnp.int32), 0, n - 1),
      jnp.asarray(pos0, jnp.int32).reshape(1),
      qg, k_pool.reshape(n, page, hd), v_pool.reshape(n, page, hd))
    return out.reshape(passes, kv_heads, tokens, group, d).transpose(
        0, 2, 1, 3, 4).reshape(c, h, d).astype(q.dtype)

# -- a shared latent row (multi-head latent attention) ---------------------

# positions a block of the latent kernel: 16 pages of 16.  A page of one
# 640-lane bf16 row is a 20 KB DMA, a tenth of a K + V page above, so the
# work a block is what amortises a block's fixed cost.  Measured on a v5e
# (my chip run, PR 32; one layer, 128 heads, bf16), 256 chunk rows in
# groups of 8 over 256 / 2048 / 3840 positions: 0.44 / 2.25 / 4.03 ms at
# 128, 0.39 / 1.31 / 2.21 at 256, 0.49 / 1.21 / 2.16 at 512 (groups of 16
# or 32: within 10%); a decode step of 64 slots, 24 of them running over
# 54k positions: 0.62 / 0.48 / 0.45 ms.  Tried and taken back: one copy
# for a run of 16 pages that follow one another in the pool (0.73 ms a
# step against 0.75: the copies' issue is not what a block waits for),
# and blocks of 768 or 1024 positions for a chunk's rows (a chunk's
# cost becomes a coarse step function of its context and the judged
# 95th percentile hops between two steps: spread 2.6% and 3.6% over six
# seeds against 1.2-1.6% at 256; PERF.md section 6, PR 32).
_LATENT_BLOCK_POSITIONS = 256


def latent_supported(page_size, dtype):
    """Whether ``latent_paged_attention`` takes these shapes: pages that
    are whole sublane tiles of the pool's dtype (the row may be any
    width: a block is one [T, W] matrix every head multiplies)."""
    return page_size % _sublane_rows(dtype) == 0


def _latent_kernel(g, pt_ref, len_ref, q_ref, pool_hbm, o_ref, buf, sem,
                   m_scr, l_scr, acc_scr, *, scale, page, ppb, mpp, heads,
                   group, value_dim, precision):
    # positions the LAST token of group ``g``, this grid step's, sees: > 0
    ctx = len_ref[g]
    n_pages = pl.cdiv(ctx, page)
    n_blocks = pl.cdiv(n_pages, ppb)
    rows = group * heads
    t = ppb * page

    def on_live_pages(blk, slot, act):
        for j in range(ppb):
            @pl.when(blk * ppb + j < n_pages)
            def _():
                pid = pt_ref[g * mpp + blk * ppb + j]
                act(pltpu.make_async_copy(
                    pool_hbm.at[pid], buf.at[slot, pl.ds(j * page, page)],
                    sem.at[slot]))

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

    @pl.when(n_blocks > 0)
    def _first():
        on_live_pages(0, 0, start)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q = q_ref[0]                               # [rows, W], pool dtype
    # token r of the group sits ``group - 1 - r`` positions before the
    # last and sees that many fewer; its heads are rows r*H .. r*H+H-1
    tok = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // heads
    row_ctx = ctx - (group - 1 - tok)

    def block(blk, carry):
        slot = jax.lax.rem(blk, 2)

        @pl.when(blk + 1 < n_blocks)
        def _prefetch():
            on_live_pages(blk + 1, 1 - slot, start)

        on_live_pages(blk, slot, wait)

        @pl.when(ctx < (blk + 1) * t)
        def _zero_dead_rows():
            # rows past the live positions hold whatever was there:
            # their p is 0, and 0 * NaN is not
            at = blk * t + jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
            buf[slot] = jnp.where(at < ctx, buf[slot],
                                  jnp.zeros((), buf.dtype))

        kv = buf[slot]                                      # [t, W]
        sc = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * scale                    # [rows, t]
        pos = blk * t + jax.lax.broadcasted_iota(jnp.int32, (rows, t), 1)
        sc = jnp.where(pos < row_ctx, sc, _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)     # every row sees position 0: m is real
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1,
                                                  keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :value_dim],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    l = l_scr[...]
    o_ref[0] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('scale', 'value_dim', 'group',
                                             'block_positions',
                                             'interpret'))
def latent_paged_attention(q, pool, page_table, ctx_len, scale, value_dim,
                           group=1, block_positions=_LATENT_BLOCK_POSITIONS,
                           interpret=False):
    """``latent_paged_attention_math``'s result from the live pages
    alone.  ``q`` [G * group, H, W]: G groups of ``group`` tokens at
    consecutive positions of ONE stream (a decode step: group 1, a
    group a slot; a prompt chunk: its rows cut into groups); ``pool``
    [N, P, W], one row a position shared by every head; ``page_table``
    [G, MPP] the group's stream's pages; ``ctx_len`` [G] the positions
    the group's LAST token attends over, itself included (token r of
    the group sees ``group - 1 - r`` fewer).  Returns float32
    [G * group, H, value_dim]: softmax(q . row * scale) over a token's
    positions, times the rows' first ``value_dim`` lanes.

    One grid step is one group: its ``group * H`` query rows multiply a
    block of pages as one ``[group * H, W] x [W, T]`` product (nothing
    block-diagonal: the row is every head's), f32 online softmax over
    blocks, ``p @ block[:, :value_dim]``.  The page table and the
    lengths are scalar-prefetched, pages come one DMA each, the next
    block in flight while this one is multiplied, as in
    ``paged_attention``.  The caller tests ``latent_supported``."""
    n_tok, heads, w = q.shape
    n, page = pool.shape[0], pool.shape[1]
    groups, mpp = page_table.shape
    rows = group * heads
    dtype = pool.dtype
    ppb = max(1, min(mpp, block_positions // page))
    kernel = functools.partial(
        _latent_kernel, scale=float(scale), page=page, ppb=ppb, mpp=mpp,
        heads=heads, group=group, value_dim=value_dim,
        precision=(jax.lax.Precision.HIGHEST if dtype == jnp.float32
                   else None))
    ctx_len = jnp.clip(ctx_len.astype(jnp.int32), 0, mpp * page)
    out = pl.pallas_call(
        _skip_idle(kernel),
        out_shape=jax.ShapeDtypeStruct((groups, rows, value_dim),
                                       jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(groups,),
            in_specs=[pl.BlockSpec((1, rows, w),
                                   lambda i, pt, ln, held: (held[i], 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, rows, value_dim),
                                   lambda i, pt, ln, held: (held[i], 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, ppb * page, w), dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, value_dim), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=64 * 1024 * 1024),
        name='latent_paged_attention_live_pages',
        interpret=interpret,
    )(jnp.clip(page_table.astype(jnp.int32), 0, n - 1).reshape(-1),
      ctx_len, _held_slots(ctx_len),
      q.astype(dtype).reshape(groups, rows, w), pool)
    return _zero_idle(out, ctx_len).reshape(n_tok, heads, value_dim)


# -- the chunk kernel's blocks, in the kernel and counted on the host (down
# here so that the latent kernel's lines stay where the compiled modules
# of the programs that hold it have them) ---------------------------------

def _chunk_tiles(c, tokens, window, block_positions, page, mpp):
    """(tokens a pass, pages a block) of a chunk of ``c`` rows.  Where
    no block size is given: ``_CHUNK_BLOCK_POSITIONS``, or under a
    window ONE block, in whole lane tiles, for all a pass can see
    (``window + tokens`` positions) unless that is over two such.
    Measured (my chip run, PR 57: 512 rows, 72 heads over 8, window
    512, bf16, the op with its re-layouts): two blocks of 512 as
    before 0.395 ms a layer, one of 640 0.277; split so that some
    blocks are whole, in 128 / 256 / 384: 0.417 / 0.386 / 0.545 (a
    block's fixed cost, the row reductions and the softmax state's
    columns, outweighs the masks it saves)."""
    tokens = math.gcd(c, tokens)
    if block_positions is None:
        block_positions = _CHUNK_BLOCK_POSITIONS
        if window is not None and window + tokens <= 2 * block_positions:
            block_positions = -(-(window + tokens) // 128) * 128
    return tokens, max(1, min(mpp, block_positions // page))


def _chunk_pass(xp, tok0, tokens, window, page, ppb):
    """One pass of ``tokens`` rows from position ``tok0`` over blocks of
    ``ppb`` pages -> (first page, pages, blocks): the pages from the
    first row's oldest position to the last row's own.  ``xp`` is
    ``jnp`` in the kernel and ``numpy`` for the host's count: one
    arithmetic."""
    first = 0 if window is None else \
        xp.maximum(tok0 + 1 - window, 0) // page
    n_pages = (tok0 + tokens + page - 1) // page - first
    return first, n_pages, (n_pages + ppb - 1) // ppb


def chunk_blocks(pos0, rows, window, page, table_pages,
                 tokens=_CHUNK_TOKENS, block_positions=None):
    """(blocks, whole blocks) ``chunk_paged_attention`` walks for a
    chunk of ``rows`` rows from position ``pos0``, summed over its
    passes, as host integers.  A block is WHOLE when every row of its
    pass sees every position of it, so that no mask can bite: it ends
    no later than the first row's own position and, under a window,
    begins no earlier than the last row's oldest.  (The kernel masks
    such a block like any other: see ``_CHUNK_TOKENS``.)"""
    tokens, ppb = _chunk_tiles(rows, tokens, window, block_positions,
                               page, table_pages)
    t = ppb * page
    tok0 = int(pos0) + tokens * np.arange(rows // tokens)
    first, _, n_blocks = _chunk_pass(np, tok0, tokens, window, page, ppb)
    hi = (tok0 + 1 - first * page) // t
    lo = 0 if window is None else np.minimum(
        -(-np.maximum(tok0 + tokens - window - first * page, 0) // t), hi)
    return int(np.sum(n_blocks)), int(np.sum(hi - lo))

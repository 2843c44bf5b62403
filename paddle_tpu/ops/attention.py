"""Program-level fused attention op riding the Pallas kernel.

Reference parity: the reference composes attention from matmul+softmax ops
(fluid nets.py scaled_dot_product_attention); this op is the TPU-native
fused form — ops/pallas/flash_attention.py online-softmax kernel, O(block)
on-chip memory instead of a [Tq, Tk] HBM score matrix.  When the
executor's place is NOT a TPU (ctx.backend), the op computes the same
math densely in jnp — a CPUPlace run on a TPU-attached host must not
compile Pallas for CPU, and interpret mode would be orders slower.
"""
import math

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import first, out


def _dense_attention(q, k, v, causal, scale, window=None):
    """``window``: a query reads the ``window`` positions up to and with
    its own and none before them (None: everything behind it)."""
    squeeze = q.ndim == 3
    if squeeze:
        q, k, v = (x[:, :, None, :] for x in (q, k, v))
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    s = jnp.einsum('bqhd,bkhd->bhqk', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        tq, tk = s.shape[2], s.shape[3]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        if window is not None:
            mask &= jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :] \
                < window
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum('bhqk,bkhd->bqhd', p, v.astype(jnp.float32))
    return o[:, :, 0, :] if squeeze else o


def _kv_heads(pool, head_dim):
    """K/V heads of a pool's row, [N, P, Hkv, D] or [N, P, Hkv * D]."""
    return math.prod(pool.shape[2:]) // head_dim


def _grouped(rows, n_heads):
    """Gathered K or V rows [..., Hkv, D] under ``n_heads`` query heads:
    query head h reads K/V head ``h // (n_heads / Hkv)``."""
    group = n_heads // rows.shape[-2]
    return rows if group == 1 else jnp.repeat(rows, group, axis=-2)


def _ring_positions(ctx_len, columns, page):
    """The positions a ring of ``columns`` pages holds for a reader whose
    newest position is ``ctx_len - 1``: logical page j lives in column
    ``j % columns``, so a column holds the newest page of its residue
    that is not past the reader's last.  [R, columns * page] int32 for
    ``ctx_len`` [R]; negative where the column has no such page.  (A
    table as long as the stream is the ring that never wraps.)"""
    last = (ctx_len - 1) // page
    j = last[:, None] - jnp.mod(last[:, None] - jnp.arange(columns)[None],
                                columns)
    return (j[:, :, None] * page + jnp.arange(page)).reshape(
        ctx_len.shape[0], columns * page)


def _window_valid(ctx_len, columns, page, window):
    """[R, columns * page]: the ring's positions inside a reader's
    window, the ``window`` newest of its ``ctx_len``."""
    pos = _ring_positions(ctx_len, columns, page)
    return (pos >= jnp.maximum(ctx_len - window, 0)[:, None]) \
        & (pos < ctx_len[:, None])


def paged_attention_math(q, k_pool, v_pool, page_table, ctx_len,
                         scale=None, window=None):
    """Decode-step attention against a paged KV cache, the jnp math the
    registered op and the decode engine share.

    ``q`` [S, H, D] — one new token per stream slot; ``k_pool``/
    ``v_pool`` [N, P, H, D] page pools, or the same pages with the row
    flattened, [N, P, H*D] (how the decode engine holds them: the
    gathered span is reshaped, never the pool); ``page_table`` [S, MPP]
    int32 page ids per stream (unused entries may point anywhere — typically
    the trash page — their keys are masked); ``ctx_len`` [S] int32
    VALID key count per stream, current token included.  Returns
    [S, H, D].  The pool's row says how many K/V heads there are: with
    fewer than H, query head h reads K/V head ``h // (H / Hkv)``.
    ``window`` (None: everything) is how many of the newest positions a
    slot reads, and makes the table a RING: logical page j is column
    ``j % MPP`` (``_ring_positions``).  Gathers each stream's pages, masks positions >= ctx_len
    to -1e30, and softmaxes in f32 — identical masking/accumulation to
    ``_dense_attention``, so paged decode logits sit within ulps of the
    full-context recompute (tests/test_decode.py pins it).
    """
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    n, p = k_pool.shape[0], k_pool.shape[1]
    s, h, d = q.shape
    mpp = page_table.shape[1]
    idx = jnp.clip(page_table, 0, n - 1)
    hkv = _kv_heads(k_pool, d)
    k = _grouped(k_pool[idx].reshape(s, mpp * p, hkv, d), h)  # [S, T, H, D]
    v = _grouped(v_pool[idx].reshape(s, mpp * p, hkv, d), h)
    scores = jnp.einsum('shd,sthd->sht', q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if window is None:
        valid = jnp.arange(mpp * p)[None, :] < ctx_len[:, None]  # [S, T]
    else:
        valid = _window_valid(ctx_len, mpp, p, window)
        # a ring's columns behind the window hold what was there, as the
        # kernel's dead rows do: their p is 0, and 0 * NaN is not
        v = jnp.where(valid[:, :, None, None], v, jnp.zeros((), v.dtype))
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum('sht,sthd->shd', probs, v.astype(jnp.float32))
    return o.astype(q.dtype)


def chunked_prefill_attention_math(q, k_pool, v_pool, page_table, pos0,
                                   scale=None, window=None):
    """Chunked-prefill attention for ONE stream against a partial page
    table: chunk queries attend over every already-cached position —
    prior chunks AND the chunk's own keys (scattered before the call) —
    via the stream's page table.

    ``q`` [C, H, D] — a prompt chunk whose query ``i`` sits at ABSOLUTE
    position ``pos0 + i``; ``k_pool``/``v_pool`` [N, P, H, D] page
    pools (or [N, P, H*D], as ``paged_attention_math`` takes them);
    ``page_table`` [MPP] int32 page ids for the stream (entries
    past the claimed span may point anywhere — typically the trash
    page — their keys are causally masked); ``pos0`` scalar int32.
    Returns [C, H, D].  Key at absolute position ``j`` is valid for
    query ``i`` iff ``j <= pos0 + i`` — the causal mask on the
    absolute-position grid, so stale pages, trash entries, and the
    chunk's padded tail all mask out.  f32 scores/softmax, identical
    accumulation order to ``paged_attention_math``: a chunk sequence
    over the same cached pages reproduces the prefix bitwise
    (tests/test_decode_prefix.py pins hit-vs-cold equality).  K/V heads
    from the pool's row and ``window`` (a query's newest positions, the
    table a ring) as ``paged_attention_math`` takes them.
    """
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    n, p = k_pool.shape[0], k_pool.shape[1]
    c, h, d = q.shape
    mpp = page_table.shape[0]
    idx = jnp.clip(page_table, 0, n - 1)
    hkv = _kv_heads(k_pool, d)
    k = _grouped(k_pool[idx].reshape(mpp * p, hkv, d), h)     # [T, H, D]
    v = _grouped(v_pool[idx].reshape(mpp * p, hkv, d), h)
    scores = jnp.einsum('chd,thd->cht', q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = pos0 + jnp.arange(c)                  # absolute positions
    if window is None:
        valid = jnp.arange(mpp * p)[None, :] <= qpos[:, None]  # [C, T]
    else:
        valid = _window_valid(qpos + 1, mpp, p, window)
        v = jnp.where(jnp.any(valid, axis=0)[:, None, None], v,
                      jnp.zeros((), v.dtype))
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum('cht,thd->chd', probs, v.astype(jnp.float32))
    return o.astype(q.dtype)


# A query row's float32 scores over a whole table, [H, MPP * P], above
# which a chunk's rows take the live-pages kernel on a TPU.  Under it
# the gather is the faster form (16 heads over a table of 1024
# positions, 64 KB a row: 0.1 ms a layer for a chunk of 128; PERF.md
# section 6, PR 28); 72 heads over a ring of 1040 positions are 300 KB a
# row and 48 over a table of 17408 are 3.3 MB, 2.6 GB for a chunk of
# 512, which no chip holds nine layers of.
_GATHER_ROW_SCORES_LIMIT = 128 * 1024


def paged_attention_path(backend, n_kv_heads, head_dim, page_size, dtype,
                         group=1):
    """What the ``paged_attention`` op runs for these shapes:
    ``'pallas_paged'`` (ops/pallas/paged_attention.py, live pages only)
    on a TPU when the K/V ROW ``n_kv_heads * head_dim`` is a whole
    number of 128-lane registers and a page a whole number of the pool
    dtype's sublane tiles (and, with ``group`` query heads over a K/V
    head, the K/V heads fill whole sublane tiles), else
    ``'xla_gather'`` (``paged_attention_math``).  Backend, shapes and
    dtype decide, nothing else; the decode engine records the answer
    with its step's ``decode.compile`` span."""
    if backend == 'tpu':
        # lazy, as flash_attention below
        from .pallas.paged_attention import supported
        if supported(n_kv_heads, head_dim, page_size, dtype, group):
            return 'pallas_paged'
    return 'xla_gather'


def chunk_attention_path(backend, n_kv_heads, head_dim, page_size, dtype,
                         n_heads, table_pages):
    """What ``chunked_prefill_attention`` runs for a chunk under
    ``n_heads`` query heads over a table of ``table_pages``:
    ``'pallas_paged'`` (``chunk_paged_attention``, the stream's live
    pages) on a TPU when the kernel takes the K/V row and a query row's
    float32 scores over the whole table would pass
    ``_GATHER_ROW_SCORES_LIMIT``, else ``'xla_gather'``."""
    if backend == 'tpu' and n_heads * table_pages * page_size * 4 \
            > _GATHER_ROW_SCORES_LIMIT:
        from .pallas.paged_attention import chunk_supported
        if chunk_supported(n_kv_heads, head_dim, page_size, dtype):
            return 'pallas_paged'
    return 'xla_gather'


def _window_attrs(attrs):
    """The attention ops' attributes as keywords: ``scale``, and
    ``window`` where the layer has one."""
    kw = {'scale': attrs.get('scale', None)}
    if attrs.get('window') is not None:
        kw['window'] = int(attrs['window'])
    return kw


@register_op('chunked_prefill_attention')
def _chunked_prefill_attention(ctx, ins, attrs):
    """One stream's prompt chunk over its K/V pages: Q [C, H, D] at
    positions Pos0 .., KPool / VPool [N, P, Hkv * D] (the row says how
    many K/V heads the H query heads share), PT [MPP]; causal on the
    absolute-position grid; ``window``: a query reads that many of the
    newest positions and PT is a ring.  Out [C, H, D].  On a TPU the
    live-pages kernel where a query row's scores over the whole table
    are not worth gathering, else a gather of the table."""
    q = first(ins, 'Q')              # [C, H, D]
    k_pool = first(ins, 'KPool')     # [N, P, Hkv, D] or [N, P, Hkv*D]
    v_pool = first(ins, 'VPool')
    page_table = first(ins, 'PT')    # [MPP] int32
    pos0 = first(ins, 'Pos0')        # scalar int32
    backend = getattr(ctx, 'backend', jax.default_backend())
    attend = chunked_prefill_attention_math
    c, h, d = q.shape
    if chunk_attention_path(backend, _kv_heads(k_pool, d), d,
                            k_pool.shape[1], k_pool.dtype, h,
                            page_table.shape[0]) == 'pallas_paged':
        from .pallas.paged_attention import chunk_paged_attention as attend
    return out(attend(
        q, k_pool, v_pool, page_table.astype(jnp.int32),
        jnp.asarray(pos0, jnp.int32).reshape(()), **_window_attrs(attrs)))


@register_op('paged_attention')
def _paged_attention(ctx, ins, attrs):
    """Decode-step attention over a paged K/V cache: Q [S, H, D], one
    token a slot, KPool / VPool [N, P, Hkv * D] (the row says how many
    K/V heads the H query heads share), PT [S, MPP], CtxLen [S];
    ``window``: a slot reads that many of its newest positions and PT
    is a ring.  Out [S, H, D].  On a TPU a Pallas kernel over the live
    pages, else a gather of the page tables."""
    q = first(ins, 'Q')              # [S, H, D]
    k_pool = first(ins, 'KPool')     # [N, P, Hkv, D] or [N, P, Hkv*D]
    v_pool = first(ins, 'VPool')
    page_table = first(ins, 'PT')    # [S, MPP] int32
    ctx_len = first(ins, 'CtxLen')   # [S] int32
    backend = getattr(ctx, 'backend', jax.default_backend())
    attend = paged_attention_math
    h, d = q.shape[1:]
    hkv = _kv_heads(k_pool, d)
    if paged_attention_path(backend, hkv, d, k_pool.shape[1],
                            k_pool.dtype, h // hkv) == 'pallas_paged':
        from .pallas import paged_attention as attend
    return out(attend(
        q, k_pool, v_pool, page_table.astype(jnp.int32),
        ctx_len.astype(jnp.int32), **_window_attrs(attrs)))


# -- a shared latent row (multi-head latent attention) ---------------------
#
# DeepSeek-V2's MLA (arXiv:2405.04434) caches ONE row a position, shared
# by every head: [the compressed KV latent after its norm | the rotary
# key after rotation].  With the key and value up-projections absorbed
# into the query and the output, attention over the cache is, per head,
# softmax(q_h . row * scale) over the positions, times the rows' first
# ``value_dim`` lanes (the latent alone).  ``q`` [.., H, W] are the
# absorbed queries, W the row's width.

def _latent_attend(q, rows, valid, scale, value_dim):
    """q [R, H, W] over rows [R, T, W] where ``valid`` [R, T]."""
    f32 = jnp.float32
    rows = rows.astype(f32)
    scores = jnp.einsum('rhw,rtw->rht', q.astype(f32), rows) * scale
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum('rht,rtc->rhc', probs, rows[..., :value_dim])


def latent_paged_attention_math(q, pool, page_table, ctx_len, scale,
                                value_dim):
    """Decode-step attention against a paged latent cache: ``q``
    [S, H, W], one token a slot; ``pool`` [N, P, W]; ``page_table``
    [S, MPP]; ``ctx_len`` [S] valid positions, the current one included.
    Returns float32 [S, H, value_dim].  Gathers whole page tables and
    masks as ``paged_attention_math`` does."""
    n, p, w = pool.shape
    s, mpp = page_table.shape
    rows = pool[jnp.clip(page_table, 0, n - 1)].reshape(s, mpp * p, w)
    valid = jnp.arange(mpp * p)[None, :] < ctx_len[:, None]
    return _latent_attend(q, rows, valid, scale, value_dim)


def latent_chunked_prefill_attention_math(q, pool, page_table, pos0, scale,
                                          value_dim):
    """Chunked-prefill attention for ONE stream against its latent
    pages: ``q`` [C, H, W], query ``i`` at absolute position ``pos0 +
    i``; ``page_table`` [MPP]; a row at position ``j`` is valid for
    query ``i`` iff ``j <= pos0 + i``, as in
    ``chunked_prefill_attention_math``.  Returns float32
    [C, H, value_dim]."""
    n, p, w = pool.shape
    c, mpp = q.shape[0], page_table.shape[0]
    rows = pool[jnp.clip(page_table, 0, n - 1)].reshape(1, mpp * p, w)
    valid = jnp.arange(mpp * p)[None, :] <= (pos0 + jnp.arange(c))[:, None]
    return _latent_attend(q, jnp.broadcast_to(rows, (c,) + rows.shape[1:]),
                          valid, scale, value_dim)


# tokens of a prompt chunk that share one pass over the stream's pages
# in the live-pages kernel: 8 x 128 heads = 1024 query rows a block
_LATENT_CHUNK_GROUP = 8


def latent_attention_path(backend, page_size, dtype):
    """What the two latent ops run for these shapes: ``'pallas_latent'``
    (ops/pallas/paged_attention.py ``latent_paged_attention``, live
    pages only) on a TPU when a page is a whole number of the pool
    dtype's sublane tiles, else ``'xla_gather'`` (the math above)."""
    if backend == 'tpu':
        from .pallas.paged_attention import latent_supported
        if latent_supported(page_size, dtype):
            return 'pallas_latent'
    return 'xla_gather'


@register_op('latent_paged_attention')
def _latent_paged_attention(ctx, ins, attrs):
    """Decode-step attention over a paged cache of ONE latent row a
    position, shared by all heads (MLA, absorbed form): Q [S, H, W],
    Pool [N, P, W], PT [S, MPP], CtxLen [S]; ``scale``; Out [S, H,
    value_dim] float32 = softmax(Q . row) x row[:value_dim].  On a TPU
    a Pallas kernel over the live pages, else a gather of the page
    tables."""
    q = first(ins, 'Q')
    pool = first(ins, 'Pool')
    pt = first(ins, 'PT').astype(jnp.int32)
    ctx_len = first(ins, 'CtxLen').astype(jnp.int32)
    scale = float(attrs.get('scale', q.shape[-1] ** -0.5))
    value_dim = int(attrs.get('value_dim', q.shape[-1]))
    backend = getattr(ctx, 'backend', jax.default_backend())
    if latent_attention_path(backend, pool.shape[1],
                             pool.dtype) == 'pallas_latent':
        from .pallas.paged_attention import latent_paged_attention
        return out(latent_paged_attention(q, pool, pt, ctx_len, scale,
                                          value_dim))
    return out(latent_paged_attention_math(q, pool, pt, ctx_len, scale,
                                           value_dim))


@register_op('latent_chunked_prefill_attention')
def _latent_chunked_prefill_attention(ctx, ins, attrs):
    """One stream's prompt chunk over its latent pages (MLA, absorbed
    form): Q [C, H, W] at positions Pos0 .., Pool [N, P, W], PT [MPP];
    causal on the absolute-position grid; Out [C, H, value_dim]
    float32.  On a TPU the live-pages kernel, the chunk's rows in
    groups that share a pass over the pages."""
    q = first(ins, 'Q')
    pool = first(ins, 'Pool')
    pt = first(ins, 'PT').astype(jnp.int32)
    pos0 = jnp.asarray(first(ins, 'Pos0'), jnp.int32).reshape(())
    scale = float(attrs.get('scale', q.shape[-1] ** -0.5))
    value_dim = int(attrs.get('value_dim', q.shape[-1]))
    backend = getattr(ctx, 'backend', jax.default_backend())
    c = q.shape[0]
    group = math.gcd(c, _LATENT_CHUNK_GROUP)
    if latent_attention_path(backend, pool.shape[1],
                             pool.dtype) == 'pallas_latent':
        from .pallas.paged_attention import latent_paged_attention
        groups = c // group
        # the positions the last token of each group sees
        ctx_len = pos0 + (jnp.arange(groups) + 1) * group
        return out(latent_paged_attention(
            q, pool, jnp.broadcast_to(pt, (groups,) + pt.shape), ctx_len,
            scale, value_dim, group=group))
    return out(latent_chunked_prefill_attention_math(
        q, pool, pt, pos0, scale, value_dim))


@register_op('flash_attention')
def _flash_attention(ctx, ins, attrs):
    q = first(ins, 'Q')  # [B, T, H, D] or [B, T, D]
    k = first(ins, 'K')
    v = first(ins, 'V')
    causal = attrs.get('causal', False)
    scale = attrs.get('scale', None)
    backend = getattr(ctx, 'backend', jax.default_backend())
    if backend != 'tpu' and not attrs.get('pallas_interpret', False):
        return out(_dense_attention(q, k, v, causal, scale)
                   .astype(q.dtype))
    # lazy: jax.experimental.pallas loads only when the op actually runs,
    # keeping `import paddle_tpu` free of the pallas extras
    from .pallas import flash_attention
    y = flash_attention(
        q, k, v,
        causal=causal,
        scale=scale,
        block_q=attrs.get('block_q'),   # None -> head-dim-aware auto
        block_k=attrs.get('block_k'),
        interpret=backend != 'tpu')
    return out(y.astype(q.dtype))

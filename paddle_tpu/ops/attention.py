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


def _dense_attention(q, k, v, causal, scale):
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
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum('bhqk,bkhd->bqhd', p, v.astype(jnp.float32))
    return o[:, :, 0, :] if squeeze else o


def paged_attention_math(q, k_pool, v_pool, page_table, ctx_len,
                         scale=None):
    """Decode-step attention against a paged KV cache, the jnp math the
    registered op and the decode engine share.

    ``q`` [S, H, D] — one new token per stream slot; ``k_pool``/
    ``v_pool`` [N, P, H, D] page pools, or the same pages with the row
    flattened, [N, P, H*D] (how the decode engine holds them: the
    gathered span is reshaped, never the pool); ``page_table`` [S, MPP]
    int32 page ids per stream (unused entries may point anywhere — typically
    the trash page — their keys are masked); ``ctx_len`` [S] int32
    VALID key count per stream, current token included.  Returns
    [S, H, D].  Gathers each stream's pages, masks positions >= ctx_len
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
    k = k_pool[idx].reshape(s, mpp * p, h, d)   # [S, T, H, D]
    v = v_pool[idx].reshape(s, mpp * p, h, d)
    scores = jnp.einsum('shd,sthd->sht', q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    valid = jnp.arange(mpp * p)[None, :] < ctx_len[:, None]  # [S, T]
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum('sht,sthd->shd', probs, v.astype(jnp.float32))
    return o.astype(q.dtype)


def chunked_prefill_attention_math(q, k_pool, v_pool, page_table, pos0,
                                   scale=None):
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
    (tests/test_decode_prefix.py pins hit-vs-cold equality).
    """
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    n, p = k_pool.shape[0], k_pool.shape[1]
    c, h, d = q.shape
    mpp = page_table.shape[0]
    idx = jnp.clip(page_table, 0, n - 1)
    k = k_pool[idx].reshape(mpp * p, h, d)      # [T, H, D]
    v = v_pool[idx].reshape(mpp * p, h, d)
    scores = jnp.einsum('chd,thd->cht', q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = pos0 + jnp.arange(c)                  # absolute positions
    valid = jnp.arange(mpp * p)[None, :] <= qpos[:, None]  # [C, T]
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum('cht,thd->chd', probs, v.astype(jnp.float32))
    return o.astype(q.dtype)


@register_op('chunked_prefill_attention')
def _chunked_prefill_attention(ctx, ins, attrs):
    q = first(ins, 'Q')              # [C, H, D]
    k_pool = first(ins, 'KPool')     # [N, P, H, D]
    v_pool = first(ins, 'VPool')
    page_table = first(ins, 'PT')    # [MPP] int32
    pos0 = first(ins, 'Pos0')        # scalar int32
    return out(chunked_prefill_attention_math(
        q, k_pool, v_pool, page_table.astype(jnp.int32),
        jnp.asarray(pos0, jnp.int32).reshape(()),
        scale=attrs.get('scale', None)))


def paged_attention_path(backend, n_heads, head_dim, page_size, dtype):
    """What the ``paged_attention`` op runs for these shapes:
    ``'pallas_paged'`` (ops/pallas/paged_attention.py, live pages only)
    on a TPU when the row ``n_heads * head_dim`` is a whole number of
    128-lane registers and a page a whole number of the pool dtype's
    sublane tiles, else ``'xla_gather'`` (``paged_attention_math``).
    Backend, shapes and dtype decide, nothing else; the decode engine
    records the answer with its step's ``decode.compile`` span."""
    if backend == 'tpu':
        # lazy, as flash_attention below
        from .pallas.paged_attention import supported
        if supported(n_heads, head_dim, page_size, dtype):
            return 'pallas_paged'
    return 'xla_gather'


@register_op('paged_attention')
def _paged_attention(ctx, ins, attrs):
    q = first(ins, 'Q')              # [S, H, D]
    k_pool = first(ins, 'KPool')     # [N, P, H, D] or [N, P, H*D]
    v_pool = first(ins, 'VPool')
    page_table = first(ins, 'PT')    # [S, MPP] int32
    ctx_len = first(ins, 'CtxLen')   # [S] int32
    backend = getattr(ctx, 'backend', jax.default_backend())
    attend = paged_attention_math
    if paged_attention_path(backend, *q.shape[1:], k_pool.shape[1],
                            k_pool.dtype) == 'pallas_paged':
        from .pallas import paged_attention as attend
    return out(attend(
        q, k_pool, v_pool, page_table.astype(jnp.int32),
        ctx_len.astype(jnp.int32), scale=attrs.get('scale', None)))


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

"""Program-level fused attention op riding the Pallas kernel.

Reference parity: the reference composes attention from matmul+softmax ops
(fluid nets.py scaled_dot_product_attention); this op is the TPU-native
fused form — ops/pallas/flash_attention.py online-softmax kernel, O(block)
on-chip memory instead of a [Tq, Tk] HBM score matrix.  When the
executor's place is NOT a TPU (ctx.backend), the op computes the same
math densely in jnp — a CPUPlace run on a TPU-attached host must not
compile Pallas for CPU, and interpret mode would be orders slower.
"""
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


def paged_attention_path(backend, head_dim, page_size, dtype):
    """What the ``paged_attention`` op runs for these shapes:
    ``'pallas_paged'`` (ops/pallas/paged_attention.py, live pages only)
    on a TPU when each head is a whole number of 128-lane registers and
    a page a whole number of the pool dtype's sublane tiles, else
    ``'xla_gather'`` (``paged_attention_math``).  Backend, shapes and
    dtype decide, nothing else; the decode engine records the answer
    with its step's ``decode.compile`` span."""
    if backend == 'tpu':
        # lazy, as flash_attention below
        from .pallas.paged_attention import supported
        if supported(head_dim, page_size, dtype):
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
    if paged_attention_path(backend, q.shape[-1], k_pool.shape[1],
                            k_pool.dtype) == 'pallas_paged':
        from .pallas import paged_attention as attend
    return out(attend(
        q, k_pool, v_pool, page_table.astype(jnp.int32),
        ctx_len.astype(jnp.int32), scale=attrs.get('scale', None)))


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

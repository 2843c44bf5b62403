"""Flash attention as a Pallas TPU kernel.

Reference parity: the reference's attention rides separate matmul/softmax
ops (scaled_dot_product_attention in fluid nets.py) materializing the
[Tq, Tk] score matrix in HBM.  This kernel keeps the online-softmax
running (max, sum, acc) state in VMEM across K blocks — O(block) memory,
one HBM pass — the bandwidth-bound fusion XLA does not do by itself.

Forward is the Pallas kernel (grid = (batch*heads, q blocks, k blocks),
VMEM scratch carries m/l/acc between k iterations).  Backward on TPU is
a pair of Pallas kernels (dk/dv: grid (bh, nk, nq); dq: grid (bh, nq,
nk)) recomputing p from the saved logsumexp in VMEM; off-TPU it falls
back to a jax lax.scan flash recompute.  Causal grids skip fully-masked
tiles.  Env gates (resolved per call, part of the vjp cache key):
PADDLE_TPU_FLASH_BWD_SCAN forces the scan path on TPU,
PADDLE_TPU_FLASH_BWD_PALLAS runs the Pallas backward in interpret mode
off-TPU (how CPU CI exercises the kernel path).

On non-TPU backends the forward kernel runs with interpret=True, so the
same code path is exercised by CPU CI.
"""
import functools
import math
import os

import numpy as _np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['flash_attention']

_NEG_INF = -1e30


def _env_on(name):
    return os.environ.get(name, '') not in ('', '0')


def _tile_alive(qoff, koff, qi, ki, block_q, block_k):
    """Causal dead-tile predicate shared by fwd/dkv/dq kernels: the tile
    is fully masked when its newest query precedes its oldest key."""
    return (qoff + qi * block_q + block_q - 1) >= (koff + ki * block_k)


def _tile_interior(qoff, koff, qi, ki, block_q, block_k):
    """Causal all-valid predicate: every (q, k) pair in the tile is
    unmasked when the tile's oldest query is >= its newest key."""
    return (qoff + qi * block_q) >= (koff + ki * block_k + block_k - 1)


def _fa_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
               m_scr, l_scr, acc_scr, *, causal, block_q, block_k,
               nk, tk):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    # causal dead-tile skip: tile fully masked when its newest query
    # precedes its oldest key — costs one predicate, halves causal work
    alive = True
    if causal:
        alive = _tile_alive(qoff_ref[0], koff_ref[0], qi, ki,
                            block_q, block_k)

    @pl.when(alive)
    def _compute():
        # matmul inputs stay in the storage dtype (bf16 on the bench
        # path): the MXU multiplies bf16 at full rate and accumulates
        # fp32 via preferred_element_type — casting to fp32 first would
        # run the matmul at a fraction of peak.  Softmax state (m, l,
        # acc) is fp32 throughout.  q arrives pre-scaled (see
        # _fa_forward), so no per-element scale multiply here.
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]  # [bk, d]
        d = v.shape[-1]
        # l-sum rides the PV matmul when head_dim leaves idle lanes:
        # augmenting v with a ones column turns sum(p, axis=1) — a
        # 1M-element cross-lane VPU reduce per 1024^2 tile — into lane
        # d of the matmul output the MXU was padding to 128 anyway
        mxu_lsum = d % 128 != 0
        if mxu_lsum:
            dx = -(-(d + 1) // 128) * 128 - d  # lanes to fill
            v = jnp.concatenate(
                [v, jnp.full((v.shape[0], 1), 1, v.dtype),
                 jnp.zeros((v.shape[0], dx - 1), v.dtype)], axis=1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

        def _tail(s, valid):
            m_prev = m_scr[:, 0]  # [bq]
            l_prev = l_scr[:, 0]
            m_cur = jnp.max(s, axis=1)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            if valid is not None:
                # explicit zero for masked entries: when a whole row is
                # masked, s == m_new == _NEG_INF and exp(0) would be 1
                p = jnp.where(valid, p, 0.0)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if mxu_lsum:
                l_new = l_prev * alpha + pv[:, d]
            else:
                l_new = l_prev * alpha + jnp.sum(p, axis=1)
            acc_scr[...] = acc_scr[...] * alpha[:, None] + pv[:, :d]
            m_scr[...] = m_new[:, None]
            l_scr[...] = l_new[:, None]

        def _masked_tail():
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            valid = kpos < tk  # last block may pad past the real length
            if causal:
                # global positions: scalar-prefetched offsets shift the
                # local indices, so causal masking works across
                # ring-rotated K blocks
                qpos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                valid = valid & ((qoff_ref[0] + qpos) >=
                                 (koff_ref[0] + kpos))
            _tail(jnp.where(valid, s, _NEG_INF), valid)

        # interior fast path: tiles with no padding columns and (if
        # causal) strictly below the diagonal band skip the iota/
        # compare/where masking ops entirely — at bq=bk=1024 that is
        # ~5 of the ~15 VPU ops per element on the T=8192 bench, and
        # interior tiles are the vast majority of alive tiles
        no_pad = True if tk % block_k == 0 else (ki + 1) * block_k <= tk
        if causal:
            interior = _tile_interior(qoff_ref[0], koff_ref[0], qi, ki,
                                      block_q, block_k)
            if no_pad is not True:
                interior = jnp.logical_and(interior, no_pad)
            pl.when(interior)(lambda: _tail(s, None))
            pl.when(jnp.logical_not(interior))(_masked_tail)
        elif tk % block_k == 0:
            _tail(s, None)
        else:
            pl.when(no_pad)(lambda: _tail(s, None))
            pl.when(jnp.logical_not(no_pad))(_masked_tail)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, 0]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        # lse as a [bq, 1] sublane vector (the same layout the backward
        # reads it in): 4 KB per q-block instead of the 512 KB a
        # 128-lane broadcast would write — over half a GB per step saved
        # at the T=8192 bench shape
        lse = m_scr[:, 0] + jnp.log(l_safe)
        lse_ref[0, 0] = lse[:, None].astype(lse_ref.dtype)


def _sds(shape, dtype):
    """ShapeDtypeStruct annotated as varying over the ambient mapped
    axes.  This clears shard_map's out_shape vma requirement; pallas
    -internal slice ops still trip the strict checker, so callers pass
    check_vma=False on the enclosing shard_map (see
    parallel/ring_attention.ring_attention)."""
    try:
        import jax.core as jc
        vma = frozenset(jc.unsafe_get_axis_names_DO_NOT_USE())
        if vma:
            return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    except Exception:
        pass
    return jax.ShapeDtypeStruct(shape, dtype)


def _dimsem(*sems):
    """Grid dimension semantics: the two outer dims (batch*heads and the
    non-accumulated block axis) are parallel, the innermost accumulation
    axis is arbitrary/sequential — lets Mosaic pipeline DMA + MXU + VPU
    across grid steps instead of treating the whole grid as a chain.
    The scoped-vmem limit is raised from the 16 MB default: the
    interior/masked two-branch tails hold two [bq, bk] fp32 tiles live
    (~18.4 MB at 1024x1024), and v5e has 128 MB of VMEM to spend."""
    return pltpu.CompilerParams(dimension_semantics=sems,
                           vmem_limit_bytes=64 * 1024 * 1024)


def _fa_forward(q, k, v, causal, scale, block_q, block_k, interpret,
                q_offset=None, k_offset=None):
    """q/k/v: [BH, T, D] -> (o [BH, T, D], lse [BH, T]).  Optional traced
    q_offset/k_offset (int32 scalars, scalar-prefetched into SMEM) shift
    the causal mask's global positions — the hook ring attention uses to
    run causal flash blocks against rotated K/V shards."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    nq = pl.cdiv(tq, block_q)
    nk = pl.cdiv(tk, block_k)
    # pad sequence dims to block multiples: Mosaic requires block shapes
    # that divide (or equal) the array dims; padded K columns are masked
    # in-kernel via `tk`, padded Q rows are sliced off below
    tq_p, tk_p = nq * block_q, nk * block_k
    if tq_p != tq:
        q = jnp.pad(q, ((0, 0), (0, tq_p - tq), (0, 0)))
    if tk_p != tk:
        k = jnp.pad(k, ((0, 0), (0, tk_p - tk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, tk_p - tk), (0, 0)))
    # fold the softmax scale into q once ([BH, T, D] pass) instead of
    # multiplying every [bq, bk] score tile in-kernel (T/bk times more
    # elements); backward folds it symmetrically (see _fa_backward_pallas)
    q = (q * scale).astype(q.dtype)
    kernel = functools.partial(_fa_kernel, causal=causal,
                               block_q=block_q, block_k=block_k, nk=nk,
                               tk=tk)
    qoff = jnp.asarray([0 if q_offset is None else q_offset], jnp.int32)
    koff = jnp.asarray([0 if k_offset is None else k_offset], jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, *_: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, *_: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, i, j, *_: (b, i, 0, 0)),
        ],
        scratch_shapes=[
            # m/l as [bq, 1] sublane vectors: a 128-lane scratch would
            # broadcast-write 512 KB per k-iteration for 4 KB of state
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            _sds((bh, tq_p, d), q.dtype),
            _sds((bh, nq, block_q, 1), jnp.float32),
        ],
        compiler_params=_dimsem('parallel', 'parallel', 'arbitrary'),
        interpret=interpret,
    )(qoff, koff, q, k, v)


def _fa_forward_sliced(q, k, v, causal, scale, block_q, block_k,
                       interpret, q_offset=None, k_offset=None):
    tq = q.shape[1]
    o, lse = _fa_forward(q, k, v, causal, scale, block_q, block_k,
                         interpret, q_offset, k_offset)
    bh = lse.shape[0]
    return o[:, :tq], lse.reshape(bh, -1)[:, :tq]


def _dense_ref(q, k, v, causal, scale):
    s = jnp.einsum('btd,bsd->bts', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        tq, tk = s.shape[1], s.shape[2]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask[None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bts,bsd->btd', p, v.astype(jnp.float32))


def _fa_backward(causal, scale, block_k, res, do, dlse=None):
    """Flash backward: recompute scores per K block against the saved
    logsumexp; never materializes [Tq, Tk].  `dlse` is the cotangent of
    the logsumexp output (d lse/d s = p, so it folds into ds)."""
    q, k, v, q_off, k_off, o, lse = res
    qf = q.astype(jnp.float32)
    do = do.astype(jnp.float32)
    of = o.astype(jnp.float32)
    di = jnp.sum(do * of, axis=-1)  # [BH, T]
    if dlse is not None:
        di = di - dlse.astype(jnp.float32)  # ds += p * dlse
    tk = k.shape[1]
    bk = min(block_k, tk)
    nk = pl.cdiv(tk, bk)
    pad = nk * bk - tk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    kpos0 = jnp.arange(nk) * bk
    tq = q.shape[1]
    qpos = q_off + jnp.arange(tq)

    def kblock(carry, inp):
        dq_acc = carry
        kb, vb, k0 = inp  # [BH, bk, D], [BH, bk, D], scalar
        kf = kb.astype(jnp.float32)
        vf = vb.astype(jnp.float32)
        s = jnp.einsum('btd,bsd->bts', qf, kf) * scale
        kpos = k0 + jnp.arange(bk)
        valid = (kpos < tk)[None, None, :]
        if causal:
            valid = valid & (qpos[:, None] >=
                             (k_off + kpos)[None, :])[None]
        s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp(s - lse[:, :, None])  # [BH, Tq, bk]
        p = jnp.where(valid, p, 0.0)
        dv = jnp.einsum('bts,btd->bsd', p, do)
        dp = jnp.einsum('btd,bsd->bts', do, vf)
        ds = p * (dp - di[:, :, None]) * scale
        dq_acc = dq_acc + jnp.einsum('bts,bsd->btd', ds, kf)
        dk = jnp.einsum('bts,btd->bsd', ds, qf)
        return dq_acc, (dk, dv)

    kb = kp.reshape(kp.shape[0], nk, bk, -1).swapaxes(0, 1)
    vb = vp.reshape(vp.shape[0], nk, bk, -1).swapaxes(0, 1)
    dq, (dks, dvs) = jax.lax.scan(
        kblock, jnp.zeros_like(qf), (kb, vb, kpos0))
    dk = dks.swapaxes(0, 1).reshape(kp.shape)[:, :tk]
    dv = dvs.swapaxes(0, 1).reshape(vp.shape)[:, :tk]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


def _bwd_common(q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref, *,
                causal, q0, k0, qoff, koff, bq, bk, masked):
    """Shared per-tile flash backward math: returns
    (q, do, k, p, ds) with q/do/k in storage dtype (bf16 matmul inputs
    at full MXU rate, fp32 accumulate) and p/ds [bq, bk] fp32.

    q arrives pre-scaled (s and hence p/lse agree with the forward);
    ds therefore carries no scale factor — dk = ds^T q_scaled is exact,
    and the dq kernel multiplies its accumulator by scale once at
    flush.  Padding needs no mask here: padded q/do/lse/di rows are
    zeros (p row = 1 but do/di = 0 ⇒ dv/ds contributions vanish),
    padded k rows zero out dq contributions, and padded dk/dv rows are
    sliced off by the caller — so `masked` (a static flag; the caller
    branches on the tile predicate) is only True on causal
    diagonal-band tiles."""
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0]  # [bq, 1] sublane vector
    di = di_ref[0, 0]
    k = k_ref[0]
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    p = jnp.exp(s - lse)
    if masked:
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        p = jnp.where((qoff + qpos) >= (koff + kpos), p, 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - di)
    return q, do, k, p, ds


def _fa_bwd_dkv_kernel(qoff_ref, koff_ref, q_ref, do_ref, lse_ref, di_ref,
                       k_ref, v_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                       causal, block_q, block_k, nq):
    ki = pl.program_id(1)
    qi = pl.program_id(2)  # innermost: accumulate over q blocks

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    # causal dead-tile skip: the whole tile is masked when its newest
    # query precedes its oldest key
    alive = True
    if causal:
        alive = _tile_alive(qoff_ref[0], koff_ref[0], qi, ki,
                            block_q, block_k)

    def _go(masked):
        q, do, k, p, ds = _bwd_common(
            q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref,
            causal=causal, q0=qi * block_q, k0=ki * block_k,
            qoff=qoff_ref[0], koff=koff_ref[0], bq=block_q, bk=block_k,
            masked=masked)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # interior tiles (strictly below the diagonal band) skip the
        # iota/compare/where masking ops — see _bwd_common for why
        # padding never needs a mask in the backward
        interior = _tile_interior(qoff_ref[0], koff_ref[0], qi, ki,
                                  block_q, block_k)
        pl.when(interior)(lambda: _go(False))
        pl.when(jnp.logical_and(alive, jnp.logical_not(interior)))(
            lambda: _go(True))
    else:
        _go(False)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(qoff_ref, koff_ref, q_ref, do_ref, lse_ref, di_ref,
                      k_ref, v_ref, dq_ref, dq_scr, *, scale, causal,
                      block_q, block_k, nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)  # innermost: accumulate over k blocks

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])

    alive = True
    if causal:
        alive = _tile_alive(qoff_ref[0], koff_ref[0], qi, ki,
                            block_q, block_k)

    def _go(masked):
        _q, _do, k, p, ds = _bwd_common(
            q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref,
            causal=causal, q0=qi * block_q, k0=ki * block_k,
            qoff=qoff_ref[0], koff=koff_ref[0], bq=block_q, bk=block_k,
            masked=masked)
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        interior = _tile_interior(qoff_ref[0], koff_ref[0], qi, ki,
                                  block_q, block_k)
        pl.when(interior)(lambda: _go(False))
        pl.when(jnp.logical_and(alive, jnp.logical_not(interior)))(
            lambda: _go(True))
    else:
        _go(False)

    @pl.when(ki == nk - 1)
    def _finish():
        # ds carried no scale in-kernel (q was pre-scaled); fold the
        # d(scale*qk)/dq chain factor in once per accumulator flush
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _fa_bwd_fused_kernel(qoff_ref, koff_ref, q_ref, do_ref, lse_ref,
                         di_ref, k_ref, v_ref, dk_ref, dv_ref, dq_ref,
                         dk_scr, dv_scr, dq_acc, *, scale, causal,
                         block_q, block_k, nq, nk):
    """One k-major pass computing dk, dv AND dq: recomputes s/dp once
    per tile instead of once in each of the split kernels — 5 matmuls
    per tile instead of 7 (the split pair's s+dp are exactly the two
    redundant ones).  dq accumulates in a persistent [tq_p, d] fp32
    VMEM scratch across the outer k loop (callers gate the fused path
    on that scratch fitting VMEM; long-T falls back to the split
    kernels).  Grid (bh, nk, nq): k blocks outer, q blocks inner."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    qs = pl.dslice(qi * block_q, block_q)

    @pl.when(qi == 0)
    def _init_kv():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    @pl.when(ki == 0)
    def _init_dq():
        # unconditional (outside the alive gate): with ring offsets a
        # q block can have no alive k tile at all and must still flush
        # zeros
        dq_acc[qs, :] = jnp.zeros((block_q, dq_acc.shape[-1]),
                                  jnp.float32)

    alive = True
    if causal:
        alive = _tile_alive(qoff_ref[0], koff_ref[0], qi, ki,
                            block_q, block_k)

    def _go(masked):
        q, do, k, p, ds = _bwd_common(
            q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref,
            causal=causal, q0=qi * block_q, k0=ki * block_k,
            qoff=qoff_ref[0], koff=koff_ref[0], bq=block_q, bk=block_k,
            masked=masked)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dsl = ds.astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            dsl, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_acc[qs, :] += jax.lax.dot_general(
            dsl, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        interior = _tile_interior(qoff_ref[0], koff_ref[0], qi, ki,
                                  block_q, block_k)
        pl.when(interior)(lambda: _go(False))
        pl.when(jnp.logical_and(alive, jnp.logical_not(interior)))(
            lambda: _go(True))
    else:
        _go(False)

    @pl.when(qi == nq - 1)
    def _finish_kv():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(ki == nk - 1)
    def _finish_dq():
        # ds carried no scale in-kernel (q was pre-scaled): fold the
        # chain factor in at the single flush
        dq_ref[0, qs, :] = (dq_acc[qs, :] * scale).astype(dq_ref.dtype)


# cap on the fused backward's persistent dq accumulator (fp32 [tq_p, d]
# VMEM scratch); longer sequences fall back to the split kernels
_FUSED_DQ_BYTES = 16 * 1024 * 1024


def _pow2_floor(n):
    """Largest power of two <= n (n >= 1)."""
    return 1 << (int(n).bit_length() - 1)


def _clamp_blocks(b1, b2, t):
    """Clamp the two split kernels' block sizes on one axis so the
    SHARED padding (lcm of the two) stays bounded.  min(block, t) alone
    can hand lcm a non-power-of-two: with the default d<=64 tiles,
    tk=1100 clamps bk1 to 1100 and lcm(1100, 1024) = 281600 — a 256x
    padding blowup in the k/v/dk/dv buffers and grid (ADVICE.md).  When
    the naive clamp's lcm exceeds max(b1, b2), both blocks drop to the
    largest power of two <= min(block, t); powers of two keep
    lcm == max, so padding is bounded by one block.  Exactly-dividing
    cases (t a multiple of both clamps) keep the naive clamp and its
    zero padding."""
    b1, b2 = min(b1, t), min(b2, t)
    if math.lcm(b1, b2) > max(b1, b2):
        b1, b2 = _pow2_floor(b1), _pow2_floor(b2)
    return b1, b2


def _shared_padding(tq, tk, tiles):
    """Per-axis clamped block pairs + the shared padded lengths both
    split backward kernels read from one padded buffer.  Split out of
    _fa_backward_pallas so the padding arithmetic is unit-testable at
    adversarial lengths."""
    (bq1, bk1), (bq2, bk2) = tiles
    bq1, bq2 = _clamp_blocks(bq1, bq2, tq)
    bk1, bk2 = _clamp_blocks(bk1, bk2, tk)
    tq_p = pl.cdiv(tq, math.lcm(bq1, bq2)) * math.lcm(bq1, bq2)
    tk_p = pl.cdiv(tk, math.lcm(bk1, bk2)) * math.lcm(bk1, bk2)
    return (bq1, bk1), (bq2, bk2), tq_p, tk_p


def _fa_backward_pallas(causal, scale, tiles, res, do,
                        dlse, interpret, phases=('dkv', 'dq'),
                        allow_fused=True):
    """Pallas flash backward.  Default is ONE fused k-major kernel
    (grid bh, nk, nq) producing dk, dv and dq with a single s/dp
    recompute per tile — 5 matmuls instead of the split pair's 7.  The
    split kernels (dk/dv: grid (bh, nk, nq); dq: grid (bh, nq, nk))
    remain for long sequences whose [tq, d] dq accumulator would not
    fit VMEM, for per-phase perf runs, and for the
    PADDLE_TPU_FLASH_BWD_SPLIT A/B gate.  All recompute p from the
    saved lse in VMEM — the [Tq, Tk] lattice never touches HBM (the
    jax-scan fallback `_fa_backward` streams [Tq, block_k] slabs
    through HBM instead).
    `tiles` = ((bq_dkv, bk_dkv), (bq_dq, bk_dq)): the two split
    kernels have different best tiles on v5e (dkv likes wide k blocks —
    its accumulators live on the k axis); the fused kernel uses the
    dkv pair.  `phases` lets the perf harness time each split kernel
    alone (skipped grads come back as None)."""
    q, k, v, q_off, k_off, o, lse = res
    bh, tq, d = q.shape
    tk = k.shape[1]
    # one shared padding serves both kernels: pad to the lcm of the two
    # (clamped — see _clamp_blocks) block sizes on each axis
    (bq1, bk1), (bq2, bk2), tq_p, tk_p = _shared_padding(tq, tk, tiles)

    dof = do.astype(jnp.float32)
    di = jnp.sum(dof * o.astype(jnp.float32), axis=-1)  # [BH, Tq]
    if dlse is not None:
        di = di - dlse.astype(jnp.float32)

    # pre-scale q (one [BH, T, D] pass) so the kernels never touch the
    # [bq, bk] score tiles with a scale multiply; dq re-applies scale at
    # its accumulator flush (see _fa_bwd_dq_kernel._finish)
    qp = jnp.pad((q * scale).astype(q.dtype),
                 ((0, 0), (0, tq_p - tq), (0, 0)))
    dop = jnp.pad(do, ((0, 0), (0, tq_p - tq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, tk_p - tk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, tk_p - tk), (0, 0)))
    # lse/di ride as [BH, nq, bq, 1] sublane-vector blocks: 512B per
    # tile visit instead of the 64KB a 128-lane broadcast would re-read
    lse_p = jnp.pad(lse, ((0, 0), (0, tq_p - tq)))
    di_p = jnp.pad(di, ((0, 0), (0, tq_p - tq)))

    qoff = jnp.asarray([0 if q_off is None else q_off], jnp.int32)
    koff = jnp.asarray([0 if k_off is None else k_off], jnp.int32)

    dk = dv = dq = None
    if (allow_fused and 'dkv' in phases and 'dq' in phases
            and tq_p * d * 4 <= _FUSED_DQ_BYTES):
        bq, bk = bq1, bk1
        nq, nk = tq_p // bq, tk_p // bk
        fused_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nk, nq),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, j, i, *_: (b, i, 0)),
                pl.BlockSpec((1, bq, d), lambda b, j, i, *_: (b, i, 0)),
                pl.BlockSpec((1, 1, bq, 1),
                             lambda b, j, i, *_: (b, i, 0, 0)),
                pl.BlockSpec((1, 1, bq, 1),
                             lambda b, j, i, *_: (b, i, 0, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i, *_: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i, *_: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, d), lambda b, j, i, *_: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i, *_: (b, j, 0)),
                # dq rides one whole-[tq_p, d] block per bh, flushed
                # from the persistent accumulator at the last k block
                pl.BlockSpec((1, tq_p, d), lambda b, j, i, *_: (b, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((tq_p, d), jnp.float32)],
        )
        dk, dv, dq = pl.pallas_call(
            functools.partial(_fa_bwd_fused_kernel, scale=scale,
                              causal=causal, block_q=bq, block_k=bk,
                              nq=nq, nk=nk),
            grid_spec=fused_spec,
            out_shape=[_sds((bh, tk_p, d), k.dtype),
                       _sds((bh, tk_p, d), v.dtype),
                       _sds((bh, tq_p, d), q.dtype)],
            # the k axis carries the dq accumulation -> arbitrary
            compiler_params=_dimsem('parallel', 'arbitrary', 'arbitrary'),
            interpret=interpret,
        )(qoff, koff, qp, dop,
          lse_p.reshape(bh, nq, bq, 1), di_p.reshape(bh, nq, bq, 1),
          kp, vp)
        return dq[:, :tq], dk[:, :tk], dv[:, :tk]

    if 'dkv' in phases:
        bq, bk = bq1, bk1
        nq, nk = tq_p // bq, tk_p // bk
        dkv_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nk, nq),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, j, i, *_: (b, i, 0)),
                pl.BlockSpec((1, bq, d), lambda b, j, i, *_: (b, i, 0)),
                pl.BlockSpec((1, 1, bq, 1),
                             lambda b, j, i, *_: (b, i, 0, 0)),
                pl.BlockSpec((1, 1, bq, 1),
                             lambda b, j, i, *_: (b, i, 0, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i, *_: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i, *_: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, d), lambda b, j, i, *_: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i, *_: (b, j, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32)],
        )
        dk, dv = pl.pallas_call(
            functools.partial(_fa_bwd_dkv_kernel,
                              causal=causal, block_q=bq, block_k=bk,
                              nq=nq),
            grid_spec=dkv_spec,
            out_shape=[_sds((bh, tk_p, d), k.dtype),
                       _sds((bh, tk_p, d), v.dtype)],
            compiler_params=_dimsem('parallel', 'parallel', 'arbitrary'),
            interpret=interpret,
        )(qoff, koff, qp, dop,
          lse_p.reshape(bh, nq, bq, 1), di_p.reshape(bh, nq, bq, 1),
          kp, vp)

    if 'dq' in phases:
        bq, bk = bq2, bk2
        nq, nk = tq_p // bq, tk_p // bk
        dq_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, 1, bq, 1),
                             lambda b, i, j, *_: (b, i, 0, 0)),
                pl.BlockSpec((1, 1, bq, 1),
                             lambda b, i, j, *_: (b, i, 0, 0)),
                pl.BlockSpec((1, bk, d), lambda b, i, j, *_: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, i, j, *_: (b, j, 0)),
            ],
            out_specs=[pl.BlockSpec((1, bq, d),
                                    lambda b, i, j, *_: (b, i, 0))],
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        )
        dq, = pl.pallas_call(
            functools.partial(_fa_bwd_dq_kernel, scale=scale,
                              causal=causal, block_q=bq, block_k=bk,
                              nk=nk),
            grid_spec=dq_spec,
            out_shape=[_sds((bh, tq_p, d), q.dtype)],
            compiler_params=_dimsem('parallel', 'parallel', 'arbitrary'),
            interpret=interpret,
        )(qoff, koff, qp, dop,
          lse_p.reshape(bh, nq, bq, 1), di_p.reshape(bh, nq, bq, 1),
          kp, vp)

    return (None if dq is None else dq[:, :tq],
            None if dk is None else dk[:, :tk],
            None if dv is None else dv[:, :tk])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_with_lse(q, k, v, q_off, k_off, causal, scale, tiles,
                    interpret, bwd_mode):
    """[BH, T, D] kernel entry returning (o, lse); differentiable —
    the backward folds both cotangents into one flash recompute.
    q_off/k_off are traced int32 scalars shifting the causal mask.
    tiles = ((bq, bk) for fwd, dkv, dq) — static, per-phase.
    bwd_mode ('pallas'|'scan') is part of the vjp cache key, so the env
    gates that select it (resolved by the caller) take effect on the
    next call instead of silently needing jax.clear_caches()."""
    return _fa_forward_sliced(q, k, v, causal, scale, tiles[0][0],
                              tiles[0][1], interpret, q_off, k_off)


def _flash_fwd(q, k, v, q_off, k_off, causal, scale, tiles,
               interpret, bwd_mode):
    o, lse = _fa_forward_sliced(q, k, v, causal, scale, tiles[0][0],
                                tiles[0][1], interpret, q_off, k_off)
    return (o, lse), (q, k, v, q_off, k_off, o, lse)


def _bwd_mode_from_env(interpret):
    """PADDLE_TPU_FLASH_BWD_SCAN forces the jax-scan path on TPU (A/B
    numerics); PADDLE_TPU_FLASH_BWD_PALLAS forces the Pallas kernels
    (interpret mode) off-TPU; PADDLE_TPU_FLASH_BWD_SPLIT forces the
    split dkv/dq kernel pair instead of the fused k-major kernel."""
    if _env_on('PADDLE_TPU_FLASH_BWD_PALLAS'):
        return ('pallas_split' if _env_on('PADDLE_TPU_FLASH_BWD_SPLIT')
                else 'pallas')
    if interpret or _env_on('PADDLE_TPU_FLASH_BWD_SCAN'):
        return 'scan'
    if _env_on('PADDLE_TPU_FLASH_BWD_SPLIT'):
        return 'pallas_split'
    return 'pallas'


def _flash_bwd(causal, scale, tiles, interpret, bwd_mode,
               res, cts):
    do, dlse = cts
    if bwd_mode in ('pallas', 'pallas_split'):
        dq, dk, dv = _fa_backward_pallas(
            causal, scale, tiles[1:], res, do, dlse,
            interpret=interpret,
            allow_fused=(bwd_mode == 'pallas'))
    else:  # CPU: the jax-scan recompute (fast under interpret-free jit)
        dq, dk, dv = _fa_backward(causal, scale, tiles[1][1], res, do,
                                  dlse)
    f0 = _np.zeros((), jax.dtypes.float0)  # int operands: zero cotangent
    return dq, dk, dv, f0, f0


_flash_with_lse.defvjp(_flash_fwd, _flash_bwd)


def _to_bhtd(q, k, v):
    """[B, T, H, D] (or [BH, T, D] pass-through) -> flattened [B*H, T, D]
    plus the info to restore — the single home of the layout contract."""
    if q.ndim == 3:
        return q, k, v, None
    b, tq, h, d = q.shape
    tk = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    return qf, kf, vf, (b, h, tq, d)


def attention_with_lse(q, k, v, causal=False, scale=None, block_q=None,
                       block_k=None, q_offset=0, k_offset=0,
                       interpret=None):
    """Fused attention returning (o, lse) for online-softmax merging
    (ring attention's local blocks).  q/k/v [B, T, H, D] -> o same shape,
    lse [B, H, T].  Differentiable.  q_offset/k_offset (traced int ok)
    place the local blocks on the global sequence axis for causal
    masking across ring-rotated K/V shards."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    # per-phase default tiles from the v5e sweep (benchmarks/exp_flash,
    # steps=100 chains — short chains are launch-overhead-dominated):
    # fwd 24.4 ms at 2048^2 vs 26.1 at 1024^2; the fused backward
    # (which reads the dkv slot) 48.7 ms at (1024, 2048) vs 50.1 at
    # 1024^2 — its accumulators live on the k axis; d=128 halves
    # everything for VMEM.  Explicit block_q/block_k pin all phases.
    if block_q is None and block_k is None:
        tiles = (((2048, 2048), (1024, 2048), (1024, 1024))
                 if q.shape[-1] <= 64
                 else ((512, 512), (512, 512), (512, 512)))
    else:
        bq = int(block_q if block_q is not None else block_k)
        bk = int(block_k if block_k is not None else block_q)
        tiles = ((bq, bk),) * 3
    qf, kf, vf, restore = _to_bhtd(q, k, v)
    qo = jnp.asarray(q_offset, jnp.int32)
    ko = jnp.asarray(k_offset, jnp.int32)
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    o, lse = _flash_with_lse(qf, kf, vf, qo, ko, bool(causal),
                             float(scale), tiles,
                             bool(interpret),
                             _bwd_mode_from_env(bool(interpret)))
    if restore is None:
        return o, lse
    b, h, tq, d = restore
    o = o.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
    return o, lse.reshape(b, h, tq)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None):
    """Fused attention over [B, T, H, D] (or [BH, T, D]) tensors.

    Returns softmax(q k^T * scale [+ causal mask]) v with O(block) live
    memory on-chip.  Differentiable (Pallas backward on TPU, flash
    recompute scan elsewhere).  Default tiles are head-dim-aware and
    per-phase (see attention_with_lse); explicit block_q/block_k pin
    every phase to one tile for testing.
    """
    squeeze = False
    if q.ndim == 3:
        q4, k4, v4 = (x[:, :, None, :] for x in (q, k, v))
        squeeze = True
    else:
        q4, k4, v4 = q, k, v
    o, _lse = attention_with_lse(q4, k4, v4, causal=causal, scale=scale,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    return o[:, :, 0, :] if squeeze else o

"""Routed-expert feed-forward (mixture of experts) and the two small
ops today's decoder blocks need beside it: RMSNorm and rotary position
embedding.

No reference parity: the reference predates all three.  ``moe_ffn`` is
the OLMoE / Mixtral-style layer (Muennighoff et al., arXiv:2409.02060):
a softmax router over E experts, the k largest per token, SiLU-gated
experts, weighted combine.  It is EXACT: no capacity factor, no dropped
token, whatever the skew.  The experts run as three batched matmuls
over ALL E experts for every token, and the gated product is multiplied
by the token's routing weight — zero for an expert outside its top k —
before the down projection contracts over experts and width at once.
Rows never mix, so a token's result does not depend on which other
tokens share the batch.  Computing all E costs E/k times the routed
FLOPs and reads every expert's weights; on a v5e that is the faster
form up to at least 512 tokens a call (one layer of 64 x 2048 x 1024
experts, 8 a token, ms: 1.19 / 1.20 / 2.46 at 32 / 64 / 512 tokens
against 1.61 / 2.59 / 3.04 for sort + ``jax.lax.ragged_dot``; PERF.md,
PR 26): a decode step is bound by the experts' bytes, which both forms
read once, and XLA:TPU's grouped-matmul kernel loses more on ~4 rows a
group than the dense form spends on masked work.
(parallel/expert_parallel.py is the ``ep`` all-to-all routine, top-1
with capacity; it is not this op.)

The math functions are shared with the decode engine's OLMoE block
(inference/blocks.py), as ops/attention.py's are with the OPT block.
"""
import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import first, out

_HIGHEST = jax.lax.Precision.HIGHEST


def rms_norm_math(x, w, eps=1e-5):
    """x * rsqrt(mean(x^2, -1) + eps) * w, in float32."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(ms + eps) * w.astype(jnp.float32)


def rotary_math(x, positions, theta=10000.0):
    """Rotate ``x`` [..., T, H, Dh] by ``positions`` [..., T] (int):
    inv_freq_j = theta^(-2j/Dh), the pairing (j, j + Dh/2) of the
    published "rotate_half" code, computed in float32."""
    dh = x.shape[-1]
    half = dh // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)[..., None, :]
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return xf * jnp.cos(ang) + rot * jnp.sin(ang)


def moe_route(x, router_w, top_k, renormalize=False):
    """Router of ``moe_ffn``: softmax over the experts in float32 at
    ``highest`` matmul precision (the published code computes routing
    weights in float32), then the ``top_k`` largest per token (ties:
    lower index first, as ``lax.top_k``).  Returns (weights [N, k] f32,
    indices [N, k] int32); weights are the softmax values as they are
    unless ``renormalize``."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=_HIGHEST)
    r = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(r, int(top_k))
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx.astype(jnp.int32)


def moe_experts(x, weights, idx, gate_w, up_w, down_w):
    """sum_j weights[n, j] * expert_{idx[n, j]}(x[n]) for x [N, D], with
    expert_e(h) = (silu(h Wg_e) * (h Wu_e)) Wd_e.  ``gate_w``/``up_w``
    [E, D, F], ``down_w`` [E, F, D].  Matmul inputs take the weights'
    dtype, accumulation is float32; returns float32 [N, D]."""
    n = x.shape[0]
    e = gate_w.shape[0]
    f32 = jnp.float32
    # routing weight of every (token, expert): zero off the top k
    r = jnp.zeros((n, e), f32).at[jnp.arange(n)[:, None], idx].set(
        weights.astype(f32))
    xb = x.astype(gate_w.dtype)
    g = jnp.einsum('nd,edf->enf', xb, gate_w, preferred_element_type=f32)
    u = jnp.einsum('nd,edf->enf', xb, up_w, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u * r.T[:, :, None]).astype(down_w.dtype)
    return jnp.einsum('enf,efd->nd', h, down_w, preferred_element_type=f32)


def moe_counts(idx, n_experts, active=None):
    """Tokens routed to each expert, [E] int32; rows where ``active``
    [N] is false are not counted."""
    n, k = idx.shape
    ones = jnp.ones((n, k), jnp.int32) if active is None else \
        jnp.broadcast_to(active.astype(jnp.int32)[:, None], (n, k))
    return jnp.zeros((int(n_experts),), jnp.int32).at[
        idx.reshape(-1)].add(ones.reshape(-1))


def moe_ffn_math(x, router_w, gate_w, up_w, down_w, top_k,
                 renormalize=False, active=None):
    """The whole layer on x [N, D]: (y [N, D] f32, counts [E] int32)."""
    w, idx = moe_route(x, router_w, top_k, renormalize)
    y = moe_experts(x, w, idx, gate_w, up_w, down_w)
    return y, moe_counts(idx, gate_w.shape[0], active)


@register_op('rms_norm')
def _rms_norm(ctx, ins, attrs):
    """Out = X * rsqrt(mean(X^2, last axis) + epsilon) * Scale, computed
    in float32 (Zhang & Sennrich, arXiv:1910.07467)."""
    x = first(ins, 'X')
    scale = first(ins, 'Scale')
    y = rms_norm_math(x, scale, float(attrs.get('epsilon', 1e-5)))
    return out(y.astype(x.dtype))


@register_op('rotary_embedding')
def _rotary_embedding(ctx, ins, attrs):
    """Rotary position embedding (Su et al., arXiv:2104.09864) of X
    [..., T, H, Dh] at positions Pos [..., T] (0..T-1 when absent),
    half-split pairing (j, j + Dh/2), base ``theta``."""
    x = first(ins, 'X')
    pos = first(ins, 'Pos')
    if pos is None:
        pos = jnp.arange(x.shape[-3], dtype=jnp.int32)
    y = rotary_math(x, pos, float(attrs.get('theta', 10000.0)))
    return out(y.astype(x.dtype))


@register_op('moe_ffn')
def _moe_ffn(ctx, ins, attrs):
    """Routed-expert FFN over X [..., D]: float32 softmax router
    (RouterW [D, E]), ``top_k`` experts a token, SiLU-gated experts
    (GateW/UpW [E, D, F], DownW [E, F, D]), weights not renormalised
    unless ``norm_topk_prob``; no capacity, no dropped token.  Out
    [..., D]; Counts [E] int32, tokens routed to each expert."""
    x = first(ins, 'X')
    lead = x.shape[:-1]
    y, counts = moe_ffn_math(
        x.reshape(-1, x.shape[-1]), first(ins, 'RouterW'),
        first(ins, 'GateW'), first(ins, 'UpW'), first(ins, 'DownW'),
        attrs.get('top_k', 1),
        renormalize=bool(attrs.get('norm_topk_prob', False)))
    return {'Out': [y.reshape(lead + (x.shape[-1],)).astype(x.dtype)],
            'Counts': [counts]}

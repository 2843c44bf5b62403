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
import math

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


def yarn_inv_freq(dim, theta, factor, beta_fast=32.0, beta_slow=1.0,
                  original_max=4096):
    """YaRN's per-pair frequencies (Peng et al., arXiv:2309.00071) for a
    rotation over ``dim`` lanes, [dim / 2] float32: the plain
    ``theta^(-2j/dim)`` where a pair turns more than ``beta_fast`` times
    over the ``original_max`` trained positions, that over ``factor``
    where it turns fewer than ``beta_slow`` times, a linear ramp over
    the pair index between the two."""
    half = dim // 2
    j = jnp.arange(half, dtype=jnp.float32)
    extrap = theta ** (-j * 2.0 / dim)

    def pair_turning(n):    # the (real-valued) pair index turning n times
        return dim * math.log(original_max / (2.0 * math.pi * n)) \
            / (2.0 * math.log(theta))
    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), dim - 1)
    ramp = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extrap / factor * ramp + extrap * (1.0 - ramp)


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_math(x, positions, theta=10000.0, yarn=None, interleaved=False):
    """Rotate ``x`` [..., T, H, Dh] by ``positions`` [..., T] (int), in
    float32: inv_freq_j = theta^(-2j/Dh), or ``yarn_inv_freq`` with the
    keywords ``yarn`` gives; the pairing (j, j + Dh/2) of the published
    "rotate_half" code, or (2j, 2j + 1) when ``interleaved``."""
    dh = x.shape[-1]
    half = dh // 2
    if yarn:
        inv_freq = yarn_inv_freq(dh, theta, **yarn)
    else:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32)
                             * 2.0 / dh)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    xf = x.astype(jnp.float32)
    if interleaved:
        cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
        pairs = xf.reshape(xf.shape[:-1] + (half, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(xf.shape)
    ang = jnp.concatenate([ang, ang], axis=-1)[..., None, :]
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return xf * jnp.cos(ang) + rot * jnp.sin(ang)


def moe_route(x, router_w, top_k, renormalize=False, scale=None):
    """Router of ``moe_ffn``: softmax over the experts in float32 at
    ``highest`` matmul precision (the published code computes routing
    weights in float32), then the ``top_k`` largest per token (ties:
    lower index first, as ``lax.top_k``).  Returns (weights [N, k] f32,
    indices [N, k] int32); weights are the softmax values as they are
    unless ``renormalize`` (divided by their sum), times ``scale`` where
    the router has a scaling factor."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=_HIGHEST)
    r = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(r, int(top_k))
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if scale is not None:
        w = w * scale
    return w, idx.astype(jnp.int32)


def moe_route_grouped(x, router_w, bias, top_k, n_group, topk_group,
                      scale=1.0, renormalize=True):
    """Router of the DeepSeek-V3 family (``noaux_tc``; Liu et al.,
    arXiv:2412.19437): sigmoid scores in float32 at ``highest``; the
    CHOICE is made on scores + ``bias`` [E] (the correction bias that
    balances load without an auxiliary loss): a group of E / n_group
    experts scores the sum of its 2 largest, the ``topk_group`` best
    groups stay (the others' entries count as 0.0, as the published
    code fills them), and the ``top_k`` largest among what stays are
    taken (ties: lower index first).  The WEIGHTS are the scores
    without the bias, divided by their sum + 1e-20 when ``renormalize``,
    times ``scale``.  Returns (weights [N, k] f32, indices [N, k]
    int32)."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=_HIGHEST)
    s = jax.nn.sigmoid(logits)
    c = s + bias.astype(jnp.float32)
    n, e = c.shape
    per = e // int(n_group)
    group_score = jnp.sum(
        jax.lax.top_k(c.reshape(n, int(n_group), per), 2)[0], axis=-1)
    _, kept = jax.lax.top_k(group_score, int(topk_group))
    keep = jnp.zeros((n, int(n_group)), bool).at[
        jnp.arange(n)[:, None], kept].set(True)
    _, idx = jax.lax.top_k(
        jnp.where(jnp.repeat(keep, per, axis=1), c, 0.0), int(top_k))
    w = jnp.take_along_axis(s, idx, axis=1)
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale, idx.astype(jnp.int32)


def _held(idx, first, n_held):
    """Routing indices as positions among the ``n_held`` experts held
    here, [first, first + n_held); an expert held elsewhere maps to
    ``n_held``, one past them."""
    local = idx - int(first)
    return jnp.where((local >= 0) & (local < n_held), local, n_held)


def swiglu_math(x, gate_w, up_w, down_w):
    """(silu(x Wg) * (x Wu)) Wd at the weights' precision, f32 out."""
    f32 = jnp.float32
    xb = x.astype(gate_w.dtype)
    h = jax.nn.silu(jnp.dot(xb, gate_w, preferred_element_type=f32)) \
        * jnp.dot(xb, up_w, preferred_element_type=f32)
    return jnp.dot(h.astype(down_w.dtype), down_w,
                   preferred_element_type=f32)


def moe_experts(x, weights, idx, gate_w, up_w, down_w, first=None,
                shared=None):
    """sum_j weights[n, j] * expert_{idx[n, j]}(x[n]) for x [N, D], with
    expert_e(h) = (silu(h Wg_e) * (h Wu_e)) Wd_e.  ``gate_w``/``up_w``
    [E, D, F], ``down_w`` [E, F, D].  Matmul inputs take the weights'
    dtype, accumulation is float32; returns float32 [N, D].

    ``first`` tells the op which experts it holds: the stacked weights
    are experts ``first .. first + E - 1`` of a wider router's (one
    chip's share under expert parallelism), ``idx`` still counts over
    all of them, and the sum runs over the held ones alone — what the
    experts held elsewhere would add is left out, not stood in for.
    None: all of them are here.  ``shared`` (gate [D, Fs], up, down
    [Fs, D]) is an expert every token takes at weight 1, added once."""
    n = x.shape[0]
    e = gate_w.shape[0]
    f32 = jnp.float32
    # routing weight of every (token, held expert): zero off the top k
    if first is None:
        r = jnp.zeros((n, e), f32).at[jnp.arange(n)[:, None], idx].set(
            weights.astype(f32))
    else:
        r = jnp.zeros((n, e), f32).at[
            jnp.arange(n)[:, None], _held(idx, first, e)].set(
                weights.astype(f32), mode='drop')
    xb = x.astype(gate_w.dtype)
    g = jnp.einsum('nd,edf->enf', xb, gate_w, preferred_element_type=f32)
    u = jnp.einsum('nd,edf->enf', xb, up_w, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u * r.T[:, :, None]).astype(down_w.dtype)
    y = jnp.einsum('enf,efd->nd', h, down_w, preferred_element_type=f32)
    return y if shared is None else y + swiglu_math(x, *shared)


def moe_counts(idx, n_experts, active=None, first=None):
    """Tokens routed to each expert, [E] int32; rows where ``active``
    [N] is false are not counted.  With ``first`` (the op holds experts
    ``first .. first + n_experts - 1``): [n_experts + 1], the held
    experts' counts and, last, the assignments to experts held
    elsewhere."""
    n, k = idx.shape
    ones = jnp.ones((n, k), jnp.int32) if active is None else \
        jnp.broadcast_to(active.astype(jnp.int32)[:, None], (n, k))
    e = int(n_experts)
    if first is not None:
        idx, e = _held(idx, first, e), e + 1
    return jnp.zeros((e,), jnp.int32).at[
        idx.reshape(-1)].add(ones.reshape(-1))


def moe_ffn_math(x, router_w, gate_w, up_w, down_w, top_k,
                 renormalize=False, active=None, grouped=None, first=None,
                 shared=None):
    """The whole layer on x [N, D]: (y [N, D] f32, counts int32 as
    ``moe_counts`` gives them).  ``grouped`` = (bias, n_group,
    topk_group, scale) routes with ``moe_route_grouped`` in place of
    the softmax router."""
    if grouped is None:
        w, idx = moe_route(x, router_w, top_k, renormalize)
    else:
        bias, n_group, topk_group, scale = grouped
        w, idx = moe_route_grouped(x, router_w, bias, top_k, n_group,
                                   topk_group, scale, renormalize)
    y = moe_experts(x, w, idx, gate_w, up_w, down_w, first, shared)
    return y, moe_counts(idx, gate_w.shape[0], active, first)


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
    base ``theta``; pairing (j, j + Dh/2), or (2j, 2j + 1) when
    ``interleaved``; with ``yarn_factor`` > 0 the YaRN frequencies
    (``yarn_beta_fast``, ``yarn_beta_slow``, ``yarn_original_max``)."""
    x = first(ins, 'X')
    pos = first(ins, 'Pos')
    if pos is None:
        pos = jnp.arange(x.shape[-3], dtype=jnp.int32)
    yarn = None
    if float(attrs.get('yarn_factor', 0.0)) > 0.0:
        yarn = {'factor': float(attrs.get('yarn_factor')),
                'beta_fast': float(attrs.get('yarn_beta_fast', 32.0)),
                'beta_slow': float(attrs.get('yarn_beta_slow', 1.0)),
                'original_max': int(attrs.get('yarn_original_max', 4096))}
    y = rotary_math(x, pos, float(attrs.get('theta', 10000.0)), yarn,
                    bool(attrs.get('interleaved', False)))
    return out(y.astype(x.dtype))


@register_op('moe_ffn')
def _moe_ffn(ctx, ins, attrs):
    """Routed-expert FFN over X [..., D]: float32 softmax router
    (RouterW [D, E]), ``top_k`` experts a token, SiLU-gated experts
    (GateW/UpW [E, D, F], DownW [E, F, D]), weights not renormalised
    unless ``norm_topk_prob``; no capacity, no dropped token.  Out
    [..., D]; Counts [E] int32, tokens routed to each expert.

    With ``n_group`` > 0 the router is the grouped sigmoid one
    (``moe_route_grouped``: RouterBias [E], ``topk_group``,
    ``routed_scaling_factor``).  With ``first_expert`` >= 0 the op
    holds experts ``first_expert ..`` of a router wider than its
    stacked weights and computes their part alone (Counts gains a last
    entry, the assignments to experts held elsewhere).  SharedGateW /
    SharedUpW [D, Fs] and SharedDownW [Fs, D], when given, are an
    expert every token takes."""
    x = first(ins, 'X')
    lead = x.shape[:-1]
    grouped = None
    if int(attrs.get('n_group', 0)) > 0:
        grouped = (first(ins, 'RouterBias'), int(attrs.get('n_group')),
                   int(attrs.get('topk_group', 1)),
                   float(attrs.get('routed_scaling_factor', 1.0)))
    held_from = int(attrs.get('first_expert', -1))
    shared = first(ins, 'SharedGateW')
    if shared is not None:
        shared = (shared, first(ins, 'SharedUpW'),
                  first(ins, 'SharedDownW'))
    y, counts = moe_ffn_math(
        x.reshape(-1, x.shape[-1]), first(ins, 'RouterW'),
        first(ins, 'GateW'), first(ins, 'UpW'), first(ins, 'DownW'),
        attrs.get('top_k', 1),
        renormalize=bool(attrs.get('norm_topk_prob', False)),
        grouped=grouped, first=held_from if held_from >= 0 else None,
        shared=shared)
    return {'Out': [y.reshape(lead + (x.shape[-1],)).astype(x.dtype)],
            'Counts': [counts]}

"""Plain reference for a ``dots_vlm_serve`` configuration: the language
model of rednote-hilab/dots.vlm1.inst (``model_type`` dots_vlm, a
DeepSeek-V3-shaped decoder; Liu et al., arXiv:2412.19437 and
arXiv:2405.04434 for the attention) as ONE full-context forward pass in
float32 ``jax.numpy`` at ``highest`` matmul precision.  No cache, no
pages, no batching, no absorbed form, no program code.

The layer, for a residual stream x [T, D] (RMSNorm eps 1e-6, no biases):

- h = RMSNorm(x; in_norm_w).  Queries through a low-rank pair:
  c_q = RMSNorm(h W_qa; q_norm_w), q = c_q W_qb -> [T, H, nope | rope].
- [c_raw | r_raw] = h W_kva; c_kv = RMSNorm(c_raw; kv_norm_w);
  k_rope = RoPE(r_raw), ONE per position for all heads; q_rope rotated
  too.  RoPE over ``rope`` lanes, theta 10000, YaRN frequencies (factor
  40, beta_fast 32, beta_slow 1, 4096 original positions), pairing
  (2j, 2j + 1) as the published modelling code.
- [k_nope_h | v_h] = c_kv W_kvb, head by head; score_h(t, s) =
  (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s)) * sigma, sigma =
  (nope + rope)^-1/2 * m^2, m = 0.1 ln 40 + 1; causal float32 softmax;
  x += concat_h(sum_s p v_h(s)) W_o.
- h2 = RMSNorm(x; post_norm_w).  A leading dense layer (its gate_w is a
  matrix): x += (silu(h2 W_g) * (h2 W_u)) W_d.  An expert layer (gate_w
  is a stack): s = sigmoid(h2 W_r); c = s + router_bias; a group of
  E / 8 experts scores the sum of its 2 largest c; the 4 best groups
  stay, the others' c count as 0.0; idx = the 8 largest c among what
  stays; w = s[idx] / (sum s[idx] + 1e-20) * 2.5;
  x += sum_{e in idx, e held here} w_e E_e(h2) + Shared(h2), with E_e and
  Shared SwiGLU.
- after the last layer RMSNorm and the head.

THE SHARE.  The stacked expert weights hold experts ``FIRST_EXPERT ..``
of the router's (one chip's share of a 16-way expert-parallel layer);
the router is as wide as published, every token routes over all of its
experts, and what the experts held elsewhere would add is left out,
here as in the program.  The vocabulary is the slice the weights hold.

Departures from the published model: the multi-token-prediction module
(``num_nextn_predict_layers`` 1) is not run (plain decoding does not
run it); the vision tower is not part of the language model's config.
None in the layer equations.  The weights are read by the program's
fixed ``dots_*`` names (models/dots_vlm.py), input-major (``h @ W``; the
checkpoint stores the transpose).  Weights held in bfloat16 are widened
to float32 and used as the values they are.

What the weights' shapes do not give is fixed here: 8 experts a token
in 4 of 8 groups, 2.5, the YaRN numbers, theta, eps.  The head sizes
follow from shapes (rope = W_kva's columns - the latent norm's; nope =
W_qb's columns a head - rope; v = W_kvb's columns a head - nope).

Memory: heads and experts are walked one at a time (``lax.map``), so
that 4096 positions at the published widths fit beside the served
system: one head's [T, T] scores and one expert's three matrices in
float32 at a time.

TOLERANCE.  The error is max|got - want| over max|want| of the logits
of one request.  The system holds weights and cache in bfloat16 and
multiplies bf16 x bf16 into f32 (activations rounded to 8 mantissa
bits before every matmul); it decodes in the absorbed form, which
rounds the absorbed query and the attended latent once more; this
reference multiplies the same bf16 weights at ``highest`` with float32
activations.  Measured on the chip at the published widths, 1 + 5
layers, the configuration's seeded weights (my chip runs, PR 32;
weights and prompts from the seed, prompts of 96 and 1500 tokens
through the chunked path, 6 positions each; 18 seeds, 36 readings):
0.0030-0.0063 (the 96-token request 0.0046-0.0063, the 1500-token one
0.0030-0.0042).  The same equations with both inputs of every matrix
product cut to 4 mantissa bits (a scaled float8, the nearest precision
below the stated one; 3 seeds, 6 readings;
chipbench/tests/test_dots_vlm_chip.py): 0.078-0.123.  LOGITS_TOL 2.5e-2 is 4 times the largest reading and a
third of the smallest 4-bit one: weights, cache or matmul inputs held
below the stated precision are not correct.

How much two correct computations differ depends on the seeded
weights, and the scales were chosen for that (configuration file,
``assumed``; PERF.md sections 4 and 6).  A sigmoid router whose chosen
weights are renormalised has no decided choice: an expert taken token
by token is taken where its logit crosses a threshold, and a bf16
system and this reference disagree on that crossing now and then,
whatever the router's scale; where the expert is one held here the row
moves by its whole contribution (with routed experts at std 0.012 and
the choice left to the scores, 3 of 7 seeds read 0.046-0.088 on one
request).  So the configuration's seeded correction bias DECIDES which
held experts are taken (one a layer for every token, the others for
none; the weights stay each token's scores), the experts held
elsewhere are taken token by token, and the held experts are as large
in the stream as the shared expert: this comparison sees them (every
held expert dropped 0.29 / 0.34, shifted by one 0.48 / 0.48 on the two
requests, test_dots_vlm_chip.py through the harness's own
comparison).  The mathematics is proven at 2e-5 on the CPU
(tests/test_dots_vlm_decode.py, every wrong-block variant).
"""
import math

import jax
import jax.numpy as jnp

LOGITS_TOL = 2.5e-2
TOP_K = 8
N_GROUP = 8
TOPK_GROUP = 4
ROUTED_SCALE = 2.5
FIRST_EXPERT = 0
THETA = 10000.0
EPS = 1e-6
YARN_FACTOR = 40.0
YARN_BETA_FAST = 32.0
YARN_BETA_SLOW = 1.0
YARN_ORIGINAL_MAX = 4096
MSCALE = 0.1 * math.log(YARN_FACTOR) + 1.0


def _mm(a, b):
    """Every matrix product of this file (the chip test of the
    tolerance swaps it for one whose inputs are cut to 4 mantissa
    bits)."""
    return jnp.matmul(a, b)


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * w


def _inv_freq(dim):
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    extrap = THETA ** (-2.0 * j / dim)
    interp = extrap / YARN_FACTOR

    def d(n):
        return dim * math.log(YARN_ORIGINAL_MAX / (2.0 * math.pi * n)) \
            / (2.0 * math.log(THETA))
    low = max(math.floor(d(YARN_BETA_FAST)), 0)
    high = min(math.ceil(d(YARN_BETA_SLOW)), dim - 1)
    mask = 1.0 - jnp.clip((j - low) / (high - low), 0.0, 1.0)
    return interp * (1.0 - mask) + extrap * mask


def _rope(u, pos):
    """u [T, ..., R] rotated by pos [T]; pairing (2j, 2j + 1)."""
    r = u.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * _inv_freq(r)[None, :]
    ang = ang.reshape((u.shape[0],) + (1,) * (u.ndim - 2) + (r // 2,))
    a, b = u[..., 0::2], u[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(u.shape)


def _swiglu(h, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(h, wg)) * _mm(h, wu), wd)


def route(h, router_w, bias):
    """(weights [T, 8], indices [T, 8], scores [T, E])."""
    s = jax.nn.sigmoid(_mm(h, router_w))
    c = s + bias
    t, e = c.shape
    per = e // N_GROUP
    top2 = jax.lax.top_k(c.reshape(t, N_GROUP, per), 2)[0].sum(-1)
    _, groups = jax.lax.top_k(top2, TOPK_GROUP)
    keep = jnp.zeros((t, N_GROUP), bool).at[
        jnp.arange(t)[:, None], groups].set(True)
    _, idx = jax.lax.top_k(
        jnp.where(jnp.repeat(keep, per, axis=1), c, 0.0), TOP_K)
    w = jnp.take_along_axis(s, idx, axis=1)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * ROUTED_SCALE, idx, s


def attention(p, n, h, pos, n_heads):
    """The MLA branch's contribution to the residual, [T, D]."""
    f32 = lambda name: p[n + name].astype(jnp.float32)
    t = h.shape[0]
    c_q = _rms(_mm(h, f32('qa_w')), f32('q_norm_w'))
    rank = p[n + 'kv_norm_w'].shape[0]
    kva = _mm(h, f32('kva_w'))
    rope = kva.shape[1] - rank
    c_kv = _rms(kva[:, :rank], f32('kv_norm_w'))
    k_rope = _rope(kva[:, rank:], pos)
    qb = p[n + 'qb_w']
    kvb = p[n + 'kvb_w']
    nope = qb.shape[1] // n_heads - rope
    sigma = (nope + rope) ** -0.5 * MSCALE * MSCALE
    causal = jnp.tril(jnp.ones((t, t), bool))
    # per head: its slice of W_qb [q_lora, nope + rope] and of W_kvb
    # [rank, nope + v], widened one head at a time
    qb_h = qb.reshape(qb.shape[0], n_heads, -1).transpose(1, 0, 2)
    kvb_h = kvb.reshape(rank, n_heads, -1).transpose(1, 0, 2)

    def head(w):
        wq, wkv = (x.astype(jnp.float32) for x in w)
        q = _mm(c_q, wq)
        kv = _mm(c_kv, wkv)
        s = (_mm(q[:, :nope], kv[:, :nope].T)
             + _mm(_rope(q[:, nope:], pos), k_rope.T)) * sigma
        s = jnp.where(causal, s, -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), kv[:, nope:])   # [T, v]

    o = jax.lax.map(head, (qb_h, kvb_h))                       # [H, T, v]
    return _mm(o.transpose(1, 0, 2).reshape(t, -1), f32('o_w'))


def ffn(p, n, h, first_expert=FIRST_EXPERT, with_shared=True):
    """The layer's FFN branch [T, D] and, for an expert layer, the
    router's (weights, indices, scores); None for a dense layer."""
    f32 = lambda name: p[n + name].astype(jnp.float32)
    gate = p[n + 'gate_w']
    if gate.ndim == 2:                   # a leading dense layer
        return _swiglu(h, f32('gate_w'), f32('up_w'), f32('down_w')), None
    w, idx, s = route(h, f32('router_w'), f32('router_bias'))
    held = gate.shape[0]
    # the weight every (token, held expert) pair carries: zero unless
    # the expert is among the token's 8
    local = idx - first_expert
    weight = jnp.sum(jnp.where(
        local[:, :, None] == jnp.arange(held)[None, None, :],
        w[:, :, None], 0.0), axis=1)                           # [T, held]

    def expert(a):
        wg, wu, wd, r = a
        return _swiglu(h, wg.astype(jnp.float32), wu.astype(jnp.float32),
                       wd.astype(jnp.float32)) * r[:, None]

    y = jnp.sum(jax.lax.map(
        expert, (gate, p[n + 'up_w'], p[n + 'down_w'], weight.T)), axis=0)
    if with_shared:
        y = y + _swiglu(h, f32('shared_gate_w'), f32('shared_up_w'),
                        f32('shared_down_w'))
    return y, (w, idx, s)


def branches(p, tokens, n_layers, n_heads):
    """The forward pass with what it went through: (logits [T, V], per
    layer the RMS of the stream, of the attention branch's and of the
    FFN branch's contribution to it, per expert layer the router's
    (weights, indices, scores))."""
    with jax.default_matmul_precision('highest'):
        t = tokens.shape[0]
        x = p['dots_embed'][tokens].astype(jnp.float32)
        pos = jnp.arange(t)
        rms_of = lambda a: jnp.sqrt(jnp.mean(a * a))
        rms, routed = [], []
        for i in range(n_layers):
            n = 'dots_l%d_' % i
            a = attention(p, n, _rms(x, p[n + 'in_norm_w']
                                     .astype(jnp.float32)), pos, n_heads)
            x = x + a
            y, r = ffn(p, n, _rms(x, p[n + 'post_norm_w']
                                  .astype(jnp.float32)))
            x = x + y
            rms.append(jnp.stack([rms_of(x), rms_of(a), rms_of(y)]))
            if r is not None:
                routed.append(r)
        x = _rms(x, p['dots_norm_f_w'].astype(jnp.float32))
        return (_mm(x, p['dots_head_w'].astype(jnp.float32)),
                jnp.stack(rms), routed)


def logits(p, tokens, n_layers, n_heads):
    """[T, V] next-token scores for one sequence of int tokens [T]."""
    return branches(p, tokens, n_layers, n_heads)[0]

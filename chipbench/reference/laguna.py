"""Plain reference for a ``laguna_serve`` configuration: poolside's
Laguna-S-2.1 (https://huggingface.co/poolside/Laguna-S-2.1, config.json)
as ONE full-context forward pass in float32 ``jax.numpy`` at ``highest``
matmul precision, with an explicit [T, T] mask a layer kind.  No cache,
no pages, no ring, no kernel, no program code.

The layer i, for a residual stream x [T, D] (RMSNorm eps 1e-6, no
biases; H_i query heads of Dh lanes over Hkv K/V heads, G_i = H_i / Hkv):

- u = RMSNorm(x; in_norm_w).  q = u W_q -> [T, H_i, Dh]; k = u W_k,
  v = u W_v -> [T, Hkv, Dh].
- A FULL layer turns the first ``lanes`` lanes of every head of q and k
  (pairs (j, j + lanes/2)) by YaRN's frequencies, cos and sin times
  ``factor``; the other lanes pass.  A WINDOW layer turns all Dh lanes,
  plain frequencies theta^(-2j/Dh).
- Query head h reads K/V head h // G_i.  score(t, s) = q_h(t) . k(s) /
  sqrt(Dh), float32; position t reads s <= t, and on a window layer
  only s > t - window.  Softmax; a_h(t) = sum_s p v(s).
- g = sigmoid(u W_g) [T, H_i]; x += concat_h(g_h a_h) W_o.
- h2 = RMSNorm(x; post_norm_w).  A leading dense layer (its gate_w is a
  matrix): x += (silu(h2 W_g) * (h2 W_u)) W_d.  An expert layer (gate_w
  is a stack): r = softmax(h2 W_r) over the router's whole width; idx =
  the ``top_k`` largest; w = r[idx] / sum r[idx] * ``scale``;
  x += Shared(h2) + sum_{e in idx, e held here} w_e E_e(h2), E_e and
  Shared SwiGLU.
- after the last layer RMSNorm and the head.

THE SHARE.  The stacked expert weights hold experts ``first_expert ..``
of the router's (one chip's share of an expert-parallel layer); the
router is as wide as published, every token routes over all of it, and
what the experts held elsewhere would add is left out, here as in the
program.  The vocabulary is the slice the weights hold.

What the weights' shapes do not give comes in ``n_heads``, the one
argument the harness hands a reference beside the layer count: a dict
``{'kinds': a kind a layer, 'window', 'top_k', 'scale', 'first_expert',
'kv_heads', 'rope': {kind: {'theta', 'lanes', 'factor', 'yarn'}}}``
(the query heads of a layer follow from W_g's columns, Dh from W_k's
over ``kv_heads``).  The weights are read by the program's fixed
``laguna_*`` names (models/laguna.py), input-major (``h @ W``).  Weights
held in bfloat16 are widened to float32 and used as the values they are.

Memory: heads and experts are walked one at a time (``lax.map``, ``lax.scan``), and
a head's rows, the dense layer's and the head's in blocks of 2048, so
that 16384 positions at the published widths fit beside the served
system: one head's [2048, T] scores and mask, one expert's three
matrices and one block's [2048, 12288] in float32 at a time.

TOLERANCE.  The error is max|got - want| over max|want| of the logits
of one request.  The system holds weights and cache in bfloat16 and
multiplies bf16 x bf16 into f32 (activations rounded to 8 mantissa bits
before every matmul; the kernels round the queries and the softmax's
probabilities once more); this reference multiplies the same bf16
weights at ``highest`` with float32 activations.  Measured on the chip
at the published widths, 1 + 8 layers, the configuration's seeded
weights (my chip runs, PR 51; weights and prompts from the seed,
prompts of 96 and 6000 tokens through chunked prefill and the paged
step, 6 positions each; 31 seeds, 62 readings):
the 96-token request 0.0057-0.0085, the 6000-token one 0.0043-0.0062
(5489 positions lie behind every window there).  The same equations
with both inputs of every matrix product cut to 4 mantissa bits (a
scaled float8, the nearest precision below the stated one; 3 seeds, 6
readings; chipbench/tests/test_laguna_chip.py): 0.054-0.080.
LOGITS_TOL 2.5e-2 is 3 times the largest reading and under half the
smallest 4-bit one: weights, cache or matmul inputs held below the
stated precision are not correct.

How much two correct computations differ depends on the seeded
weights, and the scales were chosen for that (configuration file,
``assumed``; PERF.md sections 4 and 6): every branch adds 0.25-0.6 to a
stream of 1.1-2.2, and the held experts' choice is DECIDED in the
router's columns (``decide_held``), since ten renormalised softmax
scores of 256 have no decided tenth.  This comparison sees each part
of the layer (through ``kinds/serving.py build``, my chip runs, PR 51,
the two requests): the window ignored on the sliding layers 0.007 /
0.320 (the short request never leaves its window), the gate left out
0.903 / 0.961, the held experts dropped 0.562 / 0.548, every query
head over the K/V head after its own 1.069 / 0.895.  The mathematics
is proven at 2e-5 on the CPU (tests/test_laguna_decode.py, every
wrong-block variant).
"""
import math

import jax
import jax.numpy as jnp

LOGITS_TOL = 2.5e-2
EPS = 1e-6


def _mm(a, b):
    """Every matrix product of this file (the chip test of the
    tolerance swaps it for one whose inputs are cut to 4 mantissa
    bits)."""
    return jnp.matmul(a, b)


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * w


def _inv_freq(dim, theta, yarn):
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * j / dim)
    if not yarn:
        return plain

    def d(n):
        return dim * math.log(yarn['original_max'] / (2.0 * math.pi * n)) \
            / (2.0 * math.log(theta))
    low = max(math.floor(d(yarn['beta_fast'])), 0)
    high = min(math.ceil(d(yarn['beta_slow'])), dim - 1)
    keep = 1.0 - jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / yarn['factor'] * (1.0 - keep) + plain * keep


def _rope(u, pos, r):
    """u [T, H, Dh]: the first ``lanes`` lanes turned, pairs
    (j, j + lanes/2), cos and sin times ``factor``."""
    lanes = int(r.get('lanes', u.shape[-1]))
    half = lanes // 2
    ang = pos.astype(jnp.float32)[:, None] \
        * _inv_freq(lanes, r['theta'], r.get('yarn'))[None, :]
    cos = (jnp.cos(ang) * r.get('factor', 1.0))[:, None, :]
    sin = (jnp.sin(ang) * r.get('factor', 1.0))[:, None, :]
    a, b = u[..., :half], u[..., half:lanes]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            u[..., lanes:]], axis=-1)


def _swiglu(h, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(h, wg)) * _mm(h, wu), wd)


def route(h, router_w, top_k, scale):
    """(weights [T, k], indices [T, k], scores [T, E])."""
    r = jax.nn.softmax(_mm(h, router_w), axis=-1)
    w, idx = jax.lax.top_k(r, top_k)
    return w / w.sum(-1, keepdims=True) * scale, idx, r


ROWS = 2048     # rows a block, where rows are walked in blocks


def _row_blocks(fn, *arrays):
    """``fn`` over blocks of ``ROWS`` rows of arrays [T, ...], one block
    at a time (``fn`` gets the block's first row, then the blocks)."""
    t = arrays[0].shape[0]
    rows = math.gcd(t, ROWS)
    blocks = [a.reshape((t // rows, rows) + a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda a: fn(a[0], *a[1:]),
                      (jnp.arange(t // rows) * rows, *blocks))
    return out.reshape((t,) + out.shape[2:])


def attention(p, n, u, pos, kind, spec, gated=True, kv_shift=0):
    """The attention branch's contribution to the residual, [T, D].
    (``gated`` and ``kv_shift`` are the controls' handles: the gate
    left out, every query head over the K/V head after its own.)"""
    f32 = lambda name: p[n + name].astype(jnp.float32)
    t = u.shape[0]
    hkv = spec['kv_heads']
    heads = p[n + 'g_w'].shape[1]
    dh = p[n + 'k_w'].shape[1] // hkv
    g = heads // hkv
    r = spec['rope'][kind]
    k = _rope(_mm(u, f32('k_w')).reshape(t, hkv, dh), pos, r)
    v = _mm(u, f32('v_w')).reshape(t, hkv, dh)
    gate = jax.nn.sigmoid(_mm(u, f32('g_w'))) if gated \
        else jnp.ones((t, heads), jnp.float32)
    # a head at a time: its slice of W_q [D, Dh], its K/V head, its gate
    wq = p[n + 'q_w'].reshape(-1, heads, dh).transpose(1, 0, 2)

    def head(a):
        w, h = a
        kh, vh = (a[:, (h // g + kv_shift) % hkv] for a in (k, v))
        q = _rope(_mm(u, w.astype(jnp.float32))[:, None, :], pos, r)[:, 0]

        def rows(first, qb):
            # the mask of these rows, written out: row t reads s <= t,
            # and on a window layer only s > t - window
            behind = (first + jnp.arange(qb.shape[0]))[:, None] \
                - jnp.arange(t)[None, :]
            mask = behind >= 0
            if kind == 'window':
                mask &= behind < spec['window']
            s = jnp.where(mask, _mm(qb, kh.T) / math.sqrt(dh), -jnp.inf)
            return _mm(jax.nn.softmax(s, axis=-1), vh)

        return _row_blocks(rows, q) * gate[:, h][:, None]

    o = jax.lax.map(head, (wq, jnp.arange(heads)))            # [H, T, Dh]
    return _mm(o.transpose(1, 0, 2).reshape(t, -1), f32('o_w'))


def ffn(p, n, h, spec, with_shared=True, with_held=True):
    """The layer's FFN branch [T, D] and, for an expert layer, the
    router's (weights, indices, scores); None for a dense layer."""
    f32 = lambda name: p[n + name].astype(jnp.float32)
    gate = p[n + 'gate_w']
    if gate.ndim == 2:                   # a leading dense layer
        return _row_blocks(lambda _first, hb: _swiglu(
            hb, f32('gate_w'), f32('up_w'), f32('down_w')), h), None
    w, idx, r = route(h, f32('router_w'), spec['top_k'], spec['scale'])
    held = gate.shape[0]
    # the weight every (token, held expert) pair carries: zero unless
    # the expert is among the token's chosen
    local = idx - spec['first_expert']
    weight = jnp.sum(jnp.where(
        local[:, :, None] == jnp.arange(held)[None, None, :],
        w[:, :, None], 0.0), axis=1)                           # [T, held]

    def expert(y, a):
        wg, wu, wd, rw = a
        return y + _swiglu(
            h, wg.astype(jnp.float32), wu.astype(jnp.float32),
            wd.astype(jnp.float32)) * rw[:, None], None

    y = jnp.zeros_like(h)
    if with_held:
        y = jax.lax.scan(expert, y, (gate, p[n + 'up_w'], p[n + 'down_w'],
                                     weight.T))[0]
    if with_shared:
        y = y + _swiglu(h, f32('shared_gate_w'), f32('shared_up_w'),
                        f32('shared_down_w'))
    return y, (w, idx, r)


def branches(p, tokens, n_layers, spec):
    """The forward pass with what it went through: (logits [T, V], per
    layer the RMS of the stream, of the attention branch's and of the
    FFN branch's contribution to it, per expert layer the router's
    (weights, indices, scores))."""
    with jax.default_matmul_precision('highest'):
        t = tokens.shape[0]
        x = p['laguna_embed'][tokens].astype(jnp.float32)
        pos = jnp.arange(t)
        rms_of = lambda a: jnp.sqrt(jnp.mean(a * a))
        rms, routed = [], []
        for i in range(n_layers):
            n = 'laguna_l%d_' % i
            a = attention(p, n, _rms(x, p[n + 'in_norm_w']
                                     .astype(jnp.float32)), pos,
                          spec['kinds'][i], spec)
            x = x + a
            y, r = ffn(p, n, _rms(x, p[n + 'post_norm_w']
                                  .astype(jnp.float32)), spec)
            x = x + y
            rms.append(jnp.stack([rms_of(x), rms_of(a), rms_of(y)]))
            if r is not None:
                routed.append(r)
        x = _rms(x, p['laguna_norm_f_w'].astype(jnp.float32))
        head = p['laguna_head_w'].astype(jnp.float32)
        return (_row_blocks(lambda _first, xb: _mm(xb, head), x),
                jnp.stack(rms), routed)


def logits(p, tokens, n_layers, n_heads):
    """[T, V] next-token scores for one sequence of int tokens [T];
    ``n_heads`` is the dict the module's docstring describes."""
    return branches(p, tokens, n_layers, n_heads)[0]

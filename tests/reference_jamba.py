"""Plain reference for a ``jamba_serve`` configuration: AI21's Jamba
(https://huggingface.co/ai21labs/AI21-Jamba2-3B, config.json; the block
is ``transformers``' ``modeling_jamba.py``) as ONE full-context forward
pass in float32 ``jax.numpy`` at ``highest`` matmul precision.  No
cache, no pages, no state pool, no chunk, no batch, no kernel, no
program code: one sequence from position 0, a state-space layer's
recurrence walked a token at a time from a zero state.

Layer i, for a residual stream x [T, D] (RMSNorm eps 1e-6, no
projection bias), is attention where ``i % period == offset`` and Mamba
elsewhere: ``x += mixer(RMSNorm(x; in_norm_w))``, then
``x += (silu(h W_g) * (h W_u)) W_d`` with ``h = RMSNorm(x; post_norm_w)``.
After the last layer RMSNorm and the head ``x E^T``, E the embedding.

- ``attention_mixer``: q = h W_q -> [T, H, Dh]; k = h W_k, v = h W_v ->
  [T, Hkv, Dh]; query head j reads K/V head j // (H / Hkv); causal
  softmax of q k^T / sqrt(Dh); W_o.  NO positional encoding
  (``positional`` hands q and k back as they are).
- ``mamba_mixer``: [u, z] = h W_in; ``conv``: v[t] = silu(b + sum_j
  w[j] u[t - 3 + j]), zeros before t = 0; [dt, B, C] = v W_x, then
  ``dt_norm``, ``b_norm``, ``c_norm`` (an RMSNorm each); Dt[t] =
  softplus(dt[t] W_dt + b_dt); A = -exp(a_log); token by token from
  s = 0: s = exp(Dt[t] * A) * s + (Dt[t] * v[t]) * B[t], y[t] = sum_n
  s[n] C[t][n] + D * v[t]; out (y * silu(z)) W_out.

The weights are read by the program's fixed ``jamba_*`` names
(models/jamba.py), input-major (``h @ W``): a RUN of consecutive Mamba
layers is stacked, ``jamba_r<run>_<name>[layer of the run]``, and this
file indexes it.  What the weights' shapes do not give comes in
``n_heads``, the one argument the harness hands a reference beside the
layer count: ``{'heads', 'kv_heads', 'period', 'offset'}``.  Weights
held in bfloat16 are widened to float32 and used as the values they are.

DEPARTURES from ``modeling_jamba.py`` that the builder knows of:
- channels are the minor dimension of the small Mamba tensors:
  ``conv_w`` [K, Dc] for the published [Dc, 1, K], ``a_log`` [N, Dc] for
  [Dc, N] (the program's layout, ops/ssm.py; the same numbers);
- the published slow path computes ``exp(Dt A)`` and the products in the
  activations' dtype (bfloat16) and the fast path in its fused kernel's
  float32; here everything is float32;
- the recurrence is a ``lax.scan`` over the tokens with ``token`` as its
  body, not a Python loop: the harness pads a check's sequence to 2048
  tokens, and 2048 x 26 unrolled steps do not trace in a set-up's time.
  tests/test_jamba_decode.py walks ``token`` in a Python loop at its
  sizes and gets the same numbers;
- ``num_experts`` is 1 in this configuration: every layer's FFN is the
  plain gated MLP and no router exists (``modeling_jamba.py`` builds
  ``JambaMLP`` for such layers too);
- the attention mask, ``use_cache`` and the cache classes are left out:
  one sequence, no padding.

TOLERANCE.  The error is max|got - want| over max|want| of the logits
of one request.  LOGITS_TOL and the readings it rests on: see the copy
the benchmark runs, chipbench/reference/jamba.py.
"""
import jax
import jax.numpy as jnp

LOGITS_TOL = 2.5e-2
EPS = 1e-6
STATE_LAYER = ('in_norm_w', 'in_w', 'conv_w', 'conv_b', 'x_w', 'dt_norm_w',
               'b_norm_w', 'c_norm_w', 'dt_w', 'dt_b', 'a_log', 'd',
               'out_w', 'post_norm_w', 'gate_w', 'up_w', 'down_w')
ATTENTION_LAYER = ('in_norm_w', 'q_w', 'k_w', 'v_w', 'o_w', 'post_norm_w',
                   'gate_w', 'up_w', 'down_w')


def _mm(a, b):
    """Every matrix product of this file (the chip test of the
    tolerance swaps it for one whose inputs are cut to 4 mantissa
    bits)."""
    return jnp.matmul(a, b)


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * w


def is_attention(i, spec):
    return i % spec['period'] == spec['offset']


def layer_weights(p, i, spec):
    """(layer i's weights by their short names, float32): an attention
    layer's own, or its slice of its run's stack."""
    f32 = lambda a: a.astype(jnp.float32)
    if is_attention(i, spec):
        return {s: f32(p['jamba_l%d_%s' % (i, s)]) for s in ATTENTION_LAYER}
    run, first, inside = -1, 0, False   # the run layer i lies in
    for j in range(i + 1):
        if is_attention(j, spec):
            inside = False
        elif not inside:
            run, first, inside = run + 1, j, True
    return {s: f32(p['jamba_r%d_%s' % (run, s)][i - first])
            for s in STATE_LAYER}


def positional(q, k, pos):
    """Jamba's attention has no positional encoding of any kind."""
    return q, k


def attention_mixer(w, h, spec):
    t = h.shape[0]
    heads, kv = spec['heads'], spec['kv_heads']
    q = _mm(h, w['q_w']).reshape(t, heads, -1)
    k = _mm(h, w['k_w']).reshape(t, kv, -1)
    v = _mm(h, w['v_w']).reshape(t, kv, -1)
    q, k = positional(q, k, jnp.arange(t))
    dh = q.shape[-1]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def head(j):
        g = j // (heads // kv)
        s = jnp.where(causal, _mm(q[:, j], k[:, g].T) / jnp.sqrt(float(dh)),
                      -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), v[:, g])
    out = jax.lax.map(head, jnp.arange(heads))          # [H, T, Dh]
    return _mm(out.transpose(1, 0, 2).reshape(t, -1), w['o_w'])


def conv(u, w, b):
    """u [T, Dc], w [K, Dc], b [Dc] -> silu(b + sum_j w[j] u[t-(K-1)+j]),
    zeros before the sequence's first token."""
    k, t = w.shape[0], u.shape[0]
    ext = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), u.dtype), u])
    return jax.nn.silu(b + sum(w[j] * ext[j:j + t] for j in range(k)))


def dt_norm(x, w):
    return _rms(x, w)


def b_norm(x, w):
    return _rms(x, w)


def c_norm(x, w):
    return _rms(x, w)


def token(s, v, dt, a, b, c, d):
    """One token of the recurrence: s [N, Dc]; v, dt [Dc]; b, c [N]."""
    s = jnp.exp(dt[None, :] * a) * s + (dt * v)[None, :] * b[:, None]
    return s, jnp.sum(s * c[:, None], axis=0) + d * v


def recurrence(v, dt, a, b, c, d):
    """The tokens one after another from a zero state -> y [T, Dc]."""
    def step(s, x):
        return token(s, x[0], x[1], a, x[2], x[3], d)
    return jax.lax.scan(step, jnp.zeros_like(a), (v, dt, b, c))[1]


def mamba_mixer(w, h, spec):
    n = w['a_log'].shape[0]
    uz = _mm(h, w['in_w'])
    dc = uz.shape[1] // 2
    u, z = uz[:, :dc], uz[:, dc:]
    v = conv(u, w['conv_w'], w['conv_b'])
    dbc = _mm(v, w['x_w'])
    dt = dt_norm(dbc[:, :-2 * n], w['dt_norm_w'])
    b = b_norm(dbc[:, -2 * n:-n], w['b_norm_w'])
    c = c_norm(dbc[:, -n:], w['c_norm_w'])
    dt = jax.nn.softplus(_mm(dt, w['dt_w']) + w['dt_b'])
    y = recurrence(v, dt, -jnp.exp(w['a_log']), b, c, w['d'])
    return _mm(y * jax.nn.silu(z), w['out_w'])


def mlp(w, x):
    h = _rms(x, w['post_norm_w'])
    return _mm(jax.nn.silu(_mm(h, w['gate_w'])) * _mm(h, w['up_w']),
               w['down_w'])


def logits(p, tokens, n_layers, n_heads):
    """[T, V] next-token scores for one sequence of int tokens [T]."""
    spec = n_heads
    with jax.default_matmul_precision('highest'):
        e = p['jamba_embed'].astype(jnp.float32)
        x = e[tokens]
        for i in range(n_layers):
            w = layer_weights(p, i, spec)
            mixer = attention_mixer if is_attention(i, spec) else mamba_mixer
            x = x + mixer(w, _rms(x, w['in_norm_w']), spec)
            x = x + mlp(w, x)
        x = _rms(x, p['jamba_norm_f_w'].astype(jnp.float32))
        return _mm(x, e.T)

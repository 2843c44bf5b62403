"""Plain reference for an ``ouro_serve`` configuration: ByteDance's Ouro
(https://huggingface.co/ByteDance/Ouro-2.6B, config.json, ``model_type``
ouro; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741) as ONE full-context forward pass in float32
``jax.numpy`` at ``highest`` matmul precision.  No cache, no pages, no
kernel, no batching, no program code.

With L layers, T = ``ut_steps`` recurrences, x = embed[tokens] float32
(RMSNorm eps 1e-6, no biases, H heads of Dh = D / H lanes):

    for t in 0..T-1:                            # the same L layers' weights every time
      for l in 0..L-1:
        a = RMSNorm(x; in_norm_w[l])
        q, k, v = a Wq[l], a Wk[l], a Wv[l]     # [T, H, Dh]; q and k turned by rotary
                                                #   positions, theta 1e6, over the whole head,
                                                #   pairs (j, j + Dh/2)
        c = causal softmax(q k^T / sqrt(Dh)) v  # over THIS recurrence's k and v only: a
                                                #   recurrence has keys and values of its own
        x = x + RMSNorm(c Wo[l]; in_norm2_w[l]) # sandwich: a norm on the branch's way OUT too
        m = RMSNorm(x; post_norm_w[l])
        x = x + RMSNorm((silu(m Wg[l]) * (m Wu[l])) Wd[l]; post_norm2_w[l])
      x = RMSNorm(x; norm_f_w)                  # the final norm closes EVERY recurrence, and
                                                #   its output is the next recurrence's input
      g[t] = sigmoid(x . exit_w + exit_b)       # the exit gate, one number a token a recurrence
    logits = x W_head                           # after recurrence T-1; untied head
    exit: p[t] = g[t] * prod_{s<t} (1 - g[s]), the last recurrence taking the remainder; a
          token leaves at the first t whose running sum of p reaches early_exit_threshold.  At
          the published 1.0 no token leaves early: all T recurrences run for every token.

Departures from the published code, each by line:
- the recurrences of a position are computed over the whole sequence at
  once (recurrence t of every position before recurrence t + 1 of any),
  which is what a cache with a slot a recurrence gives token by token;
- no token leaves early (threshold 1.0, as published): the exit
  distribution is returned and changes nothing;
- what the catalog's ``config`` does not state is the configuration
  file's ``assumed`` (the sandwich norms' placement, the closing norm
  feeding the next recurrence, no biases, the pairing, the gate's form
  and that it reads the normalised x, the exit rule, no K/V shared
  across recurrences).

What the weights' shapes do not give comes in ``n_heads``, the one
argument the harness hands a reference beside the layer count: the
number of heads, or a dict ``{'heads', 'ut_steps'}`` (``ut_steps``
defaults to the published 4).  The weights are read by the program's
fixed ``ouro_*`` names (models/ouro.py), input-major (``h @ W``).
Weights held in bfloat16 are widened to float32 and used as the values
they are.

``ut_steps``, ``out_norm`` and ``close`` are functions of their own so
that a test can tell the reference one thing the model does not do (one
recurrence, no norm on a branch's way out, no closing norm between
recurrences) and see the comparison fail.

Memory: a head's [T, T] scores at a time (``lax.map`` over heads), so
that the check's 453 positions at the published widths fit beside the
served system.

TOLERANCE.  The error is max|got - want| over max|want| of the logits
of one request.  The system holds weights and cache in bfloat16 and
multiplies bf16 x bf16 into f32 (activations rounded to 8 mantissa bits
before every matmul; the kernels round the queries and the softmax's
probabilities once more); this reference multiplies the same bf16
weights at ``highest`` with float32 activations.  LOGITS_TOL and the
readings it rests on: see the copy the benchmark runs,
chipbench/reference/ouro.py.
"""
import jax
import jax.numpy as jnp

LOGITS_TOL = 2.5e-2
EPS = 1e-6
THETA = 1e6
UT_STEPS = 4


def _mm(a, b):
    """Every matrix product of this file (the chip test of the
    tolerance swaps it for one whose inputs are cut to 4 mantissa
    bits)."""
    return jnp.matmul(a, b)


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * w


def _rope(u, pos):
    """u [T, H, Dh] turned by pos [T]; pairs (j, j + Dh/2)."""
    dh = u.shape[-1]
    inv_freq = THETA ** (-jnp.arange(dh // 2, dtype=jnp.float32)
                         * 2.0 / dh)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-u[..., dh // 2:], u[..., :dh // 2]], axis=-1)
    return u * jnp.cos(ang) + rot * jnp.sin(ang)


def _attend(q, k, v):
    """q, k, v [T, H, Dh] -> [T, H * Dh], causal, a head at a time."""
    t, _h, dh = q.shape
    causal = jnp.tril(jnp.ones((t, t), bool))

    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(causal, _mm(qh, kh.T) / jnp.sqrt(float(dh)), -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), vh)
    out = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    return out.transpose(1, 0, 2).reshape(t, -1)


def ut_steps(spec):
    """How many times the stack runs."""
    return spec.get('ut_steps', UT_STEPS)


def out_norm(y, w):
    """The norm on a branch's way out (the sandwich's second slice)."""
    return _rms(y, w)


def close(x, w, last):
    """The norm that closes a recurrence (``last``: the final one)."""
    return _rms(x, w)


def exit_distribution(g):
    """g [T, rows] -> p [T, rows]: p[t] = g[t] prod_{s<t} (1 - g[s]), the
    last recurrence taking the remainder."""
    p, stay = [], jnp.ones_like(g[0])
    for t in range(g.shape[0] - 1):
        p.append(g[t] * stay)
        stay = stay * (1.0 - g[t])
    return jnp.stack(p + [stay])


def forward(p, tokens, n_layers, n_heads):
    """(logits [T, V], gates g [ut_steps, T]) for one sequence of int
    tokens [T]."""
    spec = n_heads if isinstance(n_heads, dict) else {'heads': n_heads}
    heads, steps = spec['heads'], ut_steps(spec)
    f32 = lambda name: p[name].astype(jnp.float32)
    with jax.default_matmul_precision('highest'):
        t = tokens.shape[0]
        x = f32('ouro_embed')[tokens]
        shape = (t, heads, x.shape[-1] // heads)
        pos = jnp.arange(t)
        gates = []
        for step in range(steps):
            for i in range(n_layers):
                n = 'ouro_l%d_' % i
                a = _rms(x, f32(n + 'in_norm_w'))
                q = _rope(_mm(a, f32(n + 'q_w')).reshape(shape), pos)
                k = _rope(_mm(a, f32(n + 'k_w')).reshape(shape), pos)
                v = _mm(a, f32(n + 'v_w')).reshape(shape)
                c = _mm(_attend(q, k, v), f32(n + 'o_w'))
                x = x + out_norm(c, f32(n + 'in_norm2_w'))
                m = _rms(x, f32(n + 'post_norm_w'))
                y = _mm(jax.nn.silu(_mm(m, f32(n + 'gate_w')))
                        * _mm(m, f32(n + 'up_w')), f32(n + 'down_w'))
                x = x + out_norm(y, f32(n + 'post_norm2_w'))
            x = close(x, f32('ouro_norm_f_w'), step == steps - 1)
            gates.append(jax.nn.sigmoid(
                _mm(x, f32('ouro_exit_w')) + f32('ouro_exit_b')[0]))
        return _mm(x, f32('ouro_head_w')), jnp.stack(gates)


def logits(p, tokens, n_layers, n_heads):
    """[T, V] next-token scores for one sequence of int tokens [T]."""
    return forward(p, tokens, n_layers, n_heads)[0]

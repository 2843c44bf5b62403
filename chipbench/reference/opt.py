"""Plain reference for a ``decoder_serve`` configuration: the OPT decoder
(Zhang et al., arXiv:2205.01068; the block of facebook/opt-1.3b's
config.json: pre-LN, learned positions, ReLU FFN, full multi-head
attention, biases everywhere) as one full-context forward pass in
float32 ``jax.numpy`` at ``highest`` matmul precision.  No cache, no
pages, no batching, no program code.

Departures, each because the system under test has it: the output head
is its own matrix with a bias (``tr_head_w/b``), not the transposed
embedding; positions index the table from 0, without OPT's offset of 2.

It reads the weights by the program's fixed ``tr_*`` names
(models/transformer.py): ``tr_embed`` [V, D], ``tr_pos`` [T, D], per
layer ``ln_attn_w/b``, ``qkv_w`` [D, 3D] / ``qkv_b`` (q, k, v side by
side), ``proj_w/b``, ``ln_ffn_w/b``, ``ffn_up_w/b``, ``ffn_down_w/b``,
then ``tr_ln_f_w/b`` and ``tr_head_w`` [D, V] / ``tr_head_b``.

TOLERANCE.  The error is max|got - want| over max|want| of the logits of
one request.  The engine computes in f32 but its matmuls take jax's
default precision, which on a TPU is one bf16 pass (8 mantissa bits,
2^-8 = 4e-3 an operand); this reference runs them at ``highest``.  The
roundings grow through 12 layers of residual adds: measured 6.4e-3 and
6.9e-3 on the chip at the published widths (my chip run, PR 23) and 5e-7
on the CPU, where both sides are true f32.  LOGITS_TOL 2e-2 is three
times the measured error; a wrong page, a stale cache line or a position
off by one moves logits by their own scale (error ~1), and weights or
cache held in bf16 as well would roughly double the measured error.
"""
import jax
import jax.numpy as jnp

LOGITS_TOL = 2e-2
EPS = 1e-5


def _ln(x, w, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * w + b


def logits(p, tokens, n_layers, n_heads):
    """[T, V] next-token scores for one sequence of int tokens [T]."""
    with jax.default_matmul_precision('highest'):
        t = tokens.shape[0]
        x = p['tr_embed'][tokens] + p['tr_pos'][:t]
        d = x.shape[-1]
        dh = d // n_heads
        causal = jnp.tril(jnp.ones((t, t), bool))
        for i in range(n_layers):
            q = 'tr_l%d_' % i
            h = _ln(x, p[q + 'ln_attn_w'], p[q + 'ln_attn_b'])
            qkv = h @ p[q + 'qkv_w'] + p[q + 'qkv_b']
            qh, kh, vh = (a.reshape(t, n_heads, dh)
                          for a in jnp.split(qkv, 3, axis=-1))
            s = jnp.einsum('qhd,khd->hqk', qh, kh) / jnp.sqrt(float(dh))
            s = jnp.where(causal[None], s, -jnp.inf)
            a = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum('hqk,khd->qhd', a, vh).reshape(t, d)
            x = x + ctx @ p[q + 'proj_w'] + p[q + 'proj_b']
            h = _ln(x, p[q + 'ln_ffn_w'], p[q + 'ln_ffn_b'])
            h = jnp.maximum(h @ p[q + 'ffn_up_w'] + p[q + 'ffn_up_b'], 0.0)
            x = x + h @ p[q + 'ffn_down_w'] + p[q + 'ffn_down_b']
        x = _ln(x, p['tr_ln_f_w'], p['tr_ln_f_b'])
        return x @ p['tr_head_w'] + p['tr_head_b']

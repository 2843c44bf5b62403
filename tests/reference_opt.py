"""Plain full-context forward of the OPT layer (models/transformer.py's
``tr_*`` parameters): what ``DecodeEngine`` serving ``OptBlock`` must
agree with, written without the engine or the block description.  No
cache, no pages, no buckets: every position of every sequence attends
over the whole causal context (ops/attention.py's dense math, which the
program's flash_attention op runs off the TPU).

This was ``inference/decode.py``'s ``_forward``, the engine's own
prefill until the engine served OPT through a block description; it
stays here as the independent side of the comparison.  The engine's
matmuls and this file's take the same precision, so on the CPU they
agree to a few float32 ulps (reassociation only).
"""
import jax.numpy as jnp

from paddle_tpu.ops.attention import _dense_attention


def _ln(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return (xf - mean) / jnp.sqrt(var + eps) * w + b


def forward(params, tokens, n_layers, n_heads):
    """[B, T] int32 tokens -> (logits [B, T, V], k_all [L, B, T, H, Dh],
    v_all): the logits of every position and each layer's K/V rows as
    the cache should hold them."""
    b, t = tokens.shape
    x = params['tr_embed'][tokens] + params['tr_pos'][:t][None]
    d = x.shape[-1]
    dh = d // n_heads
    ks, vs = [], []
    for i in range(n_layers):
        p = 'tr_l%d_' % i
        h = _ln(x, params[p + 'ln_attn_w'], params[p + 'ln_attn_b'])
        qkv = h @ params[p + 'qkv_w'] + params[p + 'qkv_b']
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, n_heads, dh)
        k = k.reshape(b, t, n_heads, dh)
        v = v.reshape(b, t, n_heads, dh)
        ks.append(k)
        vs.append(v)
        ctx = _dense_attention(q, k, v, True, None).reshape(b, t, d)
        x = x + ctx @ params[p + 'proj_w'] + params[p + 'proj_b']
        h = _ln(x, params[p + 'ln_ffn_w'], params[p + 'ln_ffn_b'])
        h = jnp.maximum(h @ params[p + 'ffn_up_w']
                        + params[p + 'ffn_up_b'], 0.0)
        x = x + h @ params[p + 'ffn_down_w'] + params[p + 'ffn_down_b']
    x = _ln(x, params['tr_ln_f_w'], params['tr_ln_f_b'])
    logits = x @ params['tr_head_w'] + params['tr_head_b']
    return logits, jnp.stack(ks), jnp.stack(vs)

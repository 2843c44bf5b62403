"""Block descriptions: what ``DecodeEngine`` needs to know about a
decoder's layer to serve it.

The engine owns the loop over layers, the page pools, where K/V rows
are written and which attention op reads them (dense for a whole-prompt
prefill, ``chunked_prefill_attention`` for a chunk, ``paged_attention``
for a decode step).  A block description supplies the rest, once, as
pure functions of the weights (a ``{name: array}`` dict):

- ``names(n_layers)``      the weights it reads, by their fixed names;
- ``sizes(params)``        ``d_model`` and ``vocab_size`` from shapes,
  and ``positions`` (a learned position table's rows) where it has one;
- ``embed(p, tokens, positions)`` -> x [T, D] float32 (the positions
  it is handed lie inside the engine's ``max_seq``);
- ``qkv(p, x, i, positions)`` -> q [T, H, Dh], and k, v [T, H * Dh] as
  the cache holds a position (the engine casts them to the pools'
  dtype, writes them, and attends over what it wrote);
- ``after_attention(p, x, ctx, i, active)`` -> (x, counts): everything
  between attention and the next layer; ``counts`` is [n_experts] int32
  (tokens routed to each expert among the rows where ``active``) or
  None for a block without experts;
- ``head(p, x)`` -> logits [T, V] float32;
- ``constant_weights``     how the weights enter the engine's ``step``
  and ``chunk`` programs: False, as an operand (no program holds a
  copy); True, bound as constants of each executable (XLA folds them:
  float32 weights whose matmuls run as one bf16 pass are held and read
  as bf16, at the price of a copy a program and a compile no cache can
  keep).  ``prefill`` takes them as an operand under either.

``positions`` are absolute token positions [T]; a block with a learned
position table indexes it in ``embed`` and the engine's ``max_seq``
defaults to its rows, a rotary block turns q and k in ``qkv`` and the
engine's ``max_seq`` is a setting.
"""
import jax.numpy as jnp

from ..ops.moe import (moe_counts, moe_experts, moe_route, rms_norm_math,
                       rotary_math)

__all__ = ['OptBlock', 'OlmoeBlock']


def _mm(x, w):
    """Matmul at the weights' precision: the activation takes the
    weight's dtype, the accumulator is float32."""
    return jnp.dot(x.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


class OptBlock(object):
    """The OPT layer (models/transformer.py builds the same block as a
    ``Program``; chipbench/reference/opt.py is its plain reference):
    learned positions, pre-LayerNorm, one fused q/k/v projection, full
    multi-head attention, a ReLU FFN, biases everywhere, a final
    LayerNorm and an untied head with a bias."""

    # ROADMAP D2: as operands the chip's step reads float32 weights
    # (about +1.7 ms of 61), and setup_s loses the ~100 s compile
    constant_weights = True

    def __init__(self, n_heads, eps=1e-5):
        self.n_heads = int(n_heads)
        self.eps = float(eps)

    @staticmethod
    def names(n_layers):
        from ..models.transformer import param_names
        return param_names(n_layers)

    def sizes(self, params):
        v, d = params['tr_embed'].shape
        return {'d_model': int(d), 'vocab_size': int(v),
                'positions': int(params['tr_pos'].shape[0])}

    def norm(self, x, w, b):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        return (xf - mean) / jnp.sqrt(var + self.eps) * w + b

    def embed(self, p, tokens, positions):
        return p['tr_embed'][tokens] + p['tr_pos'][positions]

    def qkv(self, p, x, i, positions):
        n = 'tr_l%d_' % i
        h = self.norm(x, p[n + 'ln_attn_w'], p[n + 'ln_attn_b'])
        q, k, v = jnp.split(h @ p[n + 'qkv_w'] + p[n + 'qkv_b'], 3,
                            axis=-1)
        return q.reshape(x.shape[0], self.n_heads, -1), k, v

    def activation(self, h):
        return jnp.maximum(h, 0.0)

    def after_attention(self, p, x, ctx, i, active):
        n = 'tr_l%d_' % i
        x = x + ctx.reshape(x.shape) @ p[n + 'proj_w'] + p[n + 'proj_b']
        h = self.norm(x, p[n + 'ln_ffn_w'], p[n + 'ln_ffn_b'])
        h = self.activation(h @ p[n + 'ffn_up_w'] + p[n + 'ffn_up_b'])
        return x + h @ p[n + 'ffn_down_w'] + p[n + 'ffn_down_b'], None

    def head(self, p, x):
        x = self.norm(x, p['tr_ln_f_w'], p['tr_ln_f_b'])
        return x @ p['tr_head_w'] + p['tr_head_b']


class OlmoeBlock(object):
    """The OLMoE layer (models/olmoe.py builds the same block as a
    ``Program``; chipbench/reference/olmoe.py is its plain reference):
    pre-RMSNorm, QK-norm over the whole projected row before the split
    into heads, rotary positions (half-split pairing), full multi-head
    attention, then a routed-expert FFN — float32 softmax router, the
    ``top_k`` largest experts a token, weights as they are
    (``renormalize`` False, the published ``norm_topk_prob``), every
    token reaching every one of its experts.  Keys are cached AFTER
    QK-norm and rotation, values as they are."""

    constant_weights = False

    def __init__(self, n_heads, top_k=8, eps=1e-5, theta=10000.0,
                 renormalize=False):
        self.n_heads = int(n_heads)
        self.top_k = int(top_k)
        self.eps = float(eps)
        self.theta = float(theta)
        self.renormalize = bool(renormalize)

    @staticmethod
    def names(n_layers):
        from ..models.olmoe import param_names
        return param_names(n_layers)

    def sizes(self, params):
        v, d = params['olmoe_embed'].shape
        return {'d_model': int(d), 'vocab_size': int(v)}

    def norm(self, x, w):
        return rms_norm_math(x, w, self.eps)

    def qk_norm(self, u, w):
        """Over the whole [T, H * Dh] row, before the heads are split."""
        return self.norm(u, w)

    def rotate(self, u, positions):
        return rotary_math(u, positions, self.theta)

    def embed(self, p, tokens, positions):
        return p['olmoe_embed'][tokens].astype(jnp.float32)

    def qkv(self, p, x, i, positions):
        n = 'olmoe_l%d_' % i
        t, d = x.shape
        heads = (t, self.n_heads, d // self.n_heads)
        h = self.norm(x, p[n + 'in_norm_w'])
        q = self.qk_norm(_mm(h, p[n + 'q_w']), p[n + 'q_norm_w'])
        k = self.qk_norm(_mm(h, p[n + 'k_w']), p[n + 'k_norm_w'])
        q = self.rotate(q.reshape(heads), positions)
        k = self.rotate(k.reshape(heads), positions).reshape(t, d)
        return q, k, _mm(h, p[n + 'v_w'])

    def after_attention(self, p, x, ctx, i, active):
        n = 'olmoe_l%d_' % i
        x = x + _mm(ctx.reshape(x.shape), p[n + 'o_w'])
        h = self.norm(x, p[n + 'post_norm_w'])
        w, idx = moe_route(h, p[n + 'router_w'], self.top_k,
                           self.renormalize)
        y = moe_experts(h, w, idx, p[n + 'gate_w'], p[n + 'up_w'],
                        p[n + 'down_w'])
        return x + y, moe_counts(idx, p[n + 'router_w'].shape[1], active)

    def head(self, p, x):
        return _mm(self.norm(x, p['olmoe_norm_f_w']), p['olmoe_head_w'])

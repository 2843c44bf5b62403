"""Block descriptions: what ``DecodeEngine`` needs to know about a
decoder's layer to serve it.

The engine owns the loop over layers, the page pools, where K/V rows
are written and which attention op reads them (dense for a whole-prompt
prefill, ``chunked_prefill_attention`` for a chunk, ``paged_attention``
for a decode step).  A block description supplies the rest, once, as
pure functions of the weights (a ``{name: array}`` dict the engine
hands in as an operand of every compiled program, never a constant):

- ``names(n_layers)``      the weights it reads, by their fixed names;
- ``sizes(params)``        ``d_model`` and ``vocab_size`` from shapes;
- ``embed(p, tokens, positions)`` -> x [T, D] float32;
- ``qkv(p, x, i, positions)`` -> q [T, H, Dh], and k, v [T, H * Dh] as
  the cache holds a position (the engine casts them to the pools'
  dtype, writes them, and attends over what it wrote);
- ``after_attention(p, x, ctx, i, active)`` -> (x, counts): everything
  between attention and the next layer; ``counts`` is [n_experts] int32
  (tokens routed to each expert among the rows where ``active``) or
  None for a block without experts;
- ``head(p, x)`` -> logits [T, V] float32.

``positions`` are absolute token positions [T]; a block with a learned
position table would index it in ``embed``, a rotary block turns q and k
in ``qkv`` — the engine's ``max_seq`` is then a setting, not a table's
row count.
"""
import jax.numpy as jnp

from ..ops.moe import (moe_counts, moe_experts, moe_route, rms_norm_math,
                       rotary_math)

__all__ = ['OlmoeBlock']


def _mm(x, w):
    """Matmul at the weights' precision: the activation takes the
    weight's dtype, the accumulator is float32."""
    return jnp.dot(x.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


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

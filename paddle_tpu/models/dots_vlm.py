"""The language model of dots.vlm1 (rednote-hilab/dots.vlm1.inst's
config.json, ``model_type`` dots_vlm: a DeepSeek-V3-shaped decoder, Liu
et al., arXiv:2412.19437): pre-RMSNorm, multi-head latent attention
(low-rank queries, one compressed key/value latent and one rotary key a
position, YaRN rotary frequencies, interleaved pairing), ``first_dense``
leading layers with a dense SwiGLU FFN and then routed experts under
the grouped sigmoid router (``noaux_tc``: correction bias, groups,
renormalised and scaled weights) beside a shared expert; no biases,
untied head.  Not built: the multi-token-prediction module and the
vision tower.

Composed from the registry's ops: ``rms_norm``, ``rotary_embedding``
(its YaRN and interleaved attributes), ``flash_attention`` with the
layer's own softmax scale over keys and values expanded from the
latent (the form a whole-context pass takes; the decode engine's step
takes the absorbed form over its latent cache, ops/attention.py
``latent_paged_attention``), ``swish`` for the dense FFN and ``moe_ffn``
(its grouped router, held-experts and shared-expert attributes).  Every
parameter carries a FIXED name (``dots_*``); the decode engine serves
the same weights through ``inference.blocks.DotsVlmBlock``, pulled from
the scope by ``param_names``.  Serving only.

A chip may hold a SHARE of each layer's routed experts: ``n_experts``
stacked experts, ``first_expert ..``, of a router ``router_width`` wide.

``dtype`` is the weights' (matmul operands'): 'bfloat16' as the
checkpoint is published, or 'float32'.  The residual stream, the norms,
the softmaxes and the router stay float32 either way.
"""
import paddle_tpu as fluid
from paddle_tpu.ops.moe import yarn_mscale

from .olmoe import _attr, _linear

__all__ = ['build_logits', 'param_names', 'ATTENTION', 'DENSE', 'EXPERTS']

# per-layer parameter suffixes, in creation order
ATTENTION = ('in_norm_w', 'qa_w', 'q_norm_w', 'qb_w', 'kva_w', 'kv_norm_w',
             'kvb_w', 'o_w', 'post_norm_w')
DENSE = ('gate_w', 'up_w', 'down_w')
EXPERTS = ('router_w', 'gate_w', 'up_w', 'down_w', 'router_bias',
           'shared_gate_w', 'shared_up_w', 'shared_down_w')

YARN = {'factor': 40.0, 'beta_fast': 32.0, 'beta_slow': 1.0,
        'original_max': 4096}


def build_logits(vocab_size, seq_len=128, n_layers=3, first_dense=1,
                 d_model=64, n_heads=4, q_lora_rank=24, kv_lora_rank=16,
                 qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                 dense_size=128, router_width=32, n_experts=None,
                 first_expert=0, expert_size=32, shared_size=None, top_k=8,
                 n_group=8, topk_group=4, routed_scaling_factor=2.5,
                 norm_topk_prob=True, dtype='float32', init_std=0.02,
                 dense_init_std=None, expert_init_std=None,
                 shared_init_std=None, router_init_std=None,
                 router_bias_std=0.0, embed_init_std=None, eps=1e-6,
                 theta=10000.0, yarn=YARN, mscale_all_dim=1.0):
    """Inference graph: returns (src, logits, counts) with logits
    [B, T, V] float32 and counts a list, one entry an expert layer, of
    [n_experts + 1] int32 routing counts (the held experts', then the
    assignments to experts held elsewhere).  ``n_experts`` (default: the
    router's width) experts are held, ``first_expert ..``.  ``init_std``
    seeds every matrix but the dense FFN's, the experts', the shared
    expert's, the router's, its bias and the embedding, which take the
    ``*_std`` of their name (default: the same; the bias 0)."""
    layers = fluid.layers
    std = lambda v: init_std if v is None else v
    n_experts = router_width if n_experts is None else n_experts
    shared_size = expert_size if shared_size is None else shared_size
    nope, rope, vd, h = (qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                         n_heads)
    m = yarn_mscale(yarn['factor'], mscale_all_dim) if yarn else 1.0
    scale = (nope + rope) ** -0.5 * m * m
    rotary = dict(theta=theta, yarn=yarn, interleaved=True)
    src = layers.data(name='src', shape=[seq_len], dtype='int64')
    x = layers.embedding(input=src, size=[vocab_size, d_model],
                         dtype=dtype,
                         param_attr=_attr('dots_embed', std(embed_init_std)))
    if dtype != 'float32':
        x = layers.cast(x=x, dtype='float32')
    counts = []
    for i in range(n_layers):
        p = 'dots_l%d_' % i
        hn = layers.rms_norm(input=x, epsilon=eps,
                             param_attr=_attr(p + 'in_norm_w'))
        cq = layers.rms_norm(
            input=_linear(hn, p + 'qa_w', [d_model, q_lora_rank], dtype,
                          init_std),
            epsilon=eps, param_attr=_attr(p + 'q_norm_w'))
        q = layers.reshape(
            x=_linear(cq, p + 'qb_w', [q_lora_rank, h * (nope + rope)],
                      dtype, init_std),
            shape=[-1, seq_len, h, nope + rope])
        q_nope, q_rope = layers.split(q, [nope, rope], dim=3)
        kva = _linear(hn, p + 'kva_w', [d_model, kv_lora_rank + rope],
                      dtype, init_std)
        c_raw, r_raw = layers.split(kva, [kv_lora_rank, rope], dim=2)
        c_kv = layers.rms_norm(input=c_raw, epsilon=eps,
                               param_attr=_attr(p + 'kv_norm_w'))
        k_rope = layers.rotary_embedding(
            layers.reshape(x=r_raw, shape=[-1, seq_len, 1, rope]), **rotary)
        kv = layers.reshape(
            x=_linear(c_kv, p + 'kvb_w', [kv_lora_rank, h * (nope + vd)],
                      dtype, init_std),
            shape=[-1, seq_len, h, nope + vd])
        k_nope, v = layers.split(kv, [nope, vd], dim=3)
        q = layers.concat(
            [q_nope, layers.rotary_embedding(q_rope, **rotary)], axis=3)
        k = layers.concat(
            [k_nope, layers.expand(k_rope, expand_times=[1, 1, h, 1])],
            axis=3)
        ctx = _attention(q, k, v, scale)
        x = layers.elementwise_add(x=x, y=_linear(
            layers.reshape(x=ctx, shape=[-1, seq_len, h * vd]),
            p + 'o_w', [h * vd, d_model], dtype, init_std))
        hn = layers.rms_norm(input=x, epsilon=eps,
                             param_attr=_attr(p + 'post_norm_w'))
        if i < first_dense:
            s = std(dense_init_std)
            g = _linear(hn, p + 'gate_w', [d_model, dense_size], dtype, s)
            u = _linear(hn, p + 'up_w', [d_model, dense_size], dtype, s)
            y = _linear(layers.elementwise_mul(x=layers.swish(g), y=u),
                        p + 'down_w', [dense_size, d_model], dtype, s)
        else:
            y, c = layers.moe_ffn(
                input=hn, num_experts=n_experts, expert_size=expert_size,
                top_k=top_k, norm_topk_prob=norm_topk_prob, dtype=dtype,
                n_group=n_group, topk_group=topk_group,
                routed_scaling_factor=routed_scaling_factor,
                router_width=router_width, first_expert=first_expert,
                shared_size=shared_size,
                router_attr=_attr(p + 'router_w', std(router_init_std)),
                gate_attr=_attr(p + 'gate_w', std(expert_init_std)),
                up_attr=_attr(p + 'up_w', std(expert_init_std)),
                down_attr=_attr(p + 'down_w', std(expert_init_std)),
                bias_attr=_attr(p + 'router_bias', router_bias_std),
                shared_gate_attr=_attr(p + 'shared_gate_w',
                                       std(shared_init_std)),
                shared_up_attr=_attr(p + 'shared_up_w',
                                     std(shared_init_std)),
                shared_down_attr=_attr(p + 'shared_down_w',
                                       std(shared_init_std)))
            counts.append(c)
        x = layers.elementwise_add(x=x, y=y)
    x = layers.rms_norm(input=x, epsilon=eps,
                        param_attr=_attr('dots_norm_f_w'))
    logits = _linear(x, 'dots_head_w', [d_model, vocab_size], dtype,
                     init_std)
    return src, logits, counts


def _attention(q, k, v, scale):
    """Causal attention of q, k [B, T, H, Dqk] and v [B, T, H, Dv] under
    the layer's own softmax scale."""
    from paddle_tpu.layers.layer_helper import LayerHelper
    helper = LayerHelper('flash_attention')
    out = helper.create_tmp_variable(q.dtype)
    helper.append_op(type='flash_attention',
                     inputs={'Q': [q], 'K': [k], 'V': [v]},
                     outputs={'Out': [out]},
                     attrs={'causal': True, 'scale': float(scale)})
    return out


def param_names(n_layers, first_dense=1):
    """Every fixed parameter name ``build_logits`` creates, in layer
    order — the manifest the decode engine loads from a scope."""
    names = ['dots_embed']
    for i in range(n_layers):
        names.extend('dots_l%d_%s' % (i, s) for s in
                     ATTENTION + (DENSE if i < first_dense else EXPERTS))
    names.extend(['dots_norm_f_w', 'dots_head_w'])
    return names

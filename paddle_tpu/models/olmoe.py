"""OLMoE decoder-only LM (Muennighoff et al., arXiv:2409.02060; the
block of allenai/OLMoE-1B-7B's config.json): pre-RMSNorm, QK-norm over
the whole projected row, rotary positions, full multi-head attention
and a routed-expert FFN (64 experts, 8 a token at the published sizes,
not renormalised, no shared expert), no biases, untied head.

Composed from the registry's ops: ``rms_norm``, ``rotary_embedding``
(an op of its own, applied to q and k between the projection and the
attention op, so the attention ops stay the ones every model uses) and
``moe_ffn`` (ops/moe.py).  Every parameter carries a FIXED name
(``olmoe_*``); the decode engine serves the same weights through
``inference.blocks.OlmoeBlock``, pulled from the scope by
``param_names``.  Serving only: the train graph, ``ep`` sharding and
the all-to-all are not built here.

``dtype`` is the weights' (matmul operands'): 'bfloat16' as the
checkpoint is published, or 'float32'.  The residual stream, the norms,
the softmaxes and the router stay float32 either way.
"""
import paddle_tpu as fluid

__all__ = ['build_logits', 'param_names', 'PER_LAYER']

# per-layer parameter suffixes, in creation order
PER_LAYER = ('in_norm_w', 'q_w', 'k_w', 'v_w', 'q_norm_w', 'k_norm_w',
             'o_w', 'post_norm_w', 'router_w', 'gate_w', 'up_w',
             'down_w')


def _attr(name, std=None):
    from paddle_tpu.initializer import NormalInitializer
    from paddle_tpu.param_attr import ParamAttr
    return ParamAttr(name=name, initializer=None if std is None
                     else NormalInitializer(0.0, std))


def _linear(x, name, shape, dtype, std):
    """x [B, T, Din] (float32) times a ``dtype`` weight, float32 out."""
    layers = fluid.layers
    w = layers.create_parameter(shape=shape, dtype=dtype,
                                attr=_attr(name, std))
    if dtype != 'float32':
        x = layers.cast(x=x, dtype=dtype)
    y = layers.matmul(x=x, y=w)
    return layers.cast(x=y, dtype='float32') if dtype != 'float32' else y


def build_logits(vocab_size, seq_len=128, n_layers=2, d_model=128,
                 n_heads=4, n_experts=16, expert_size=64, top_k=8,
                 dtype='float32', init_std=0.02, expert_init_std=None,
                 router_init_std=None, embed_init_std=None, eps=1e-5,
                 theta=10000.0):
    """Inference graph: returns (src, logits, counts) with logits
    [B, T, V] float32 and counts a list of per-layer [E] int32 routing
    counts.  ``init_std`` seeds every matrix but the experts' three, the
    router and the embedding, which take ``expert_init_std``,
    ``router_init_std`` and ``embed_init_std`` (default: the same)."""
    layers = fluid.layers
    if d_model % n_heads:
        raise ValueError("d_model %d not divisible by n_heads %d"
                         % (d_model, n_heads))
    if expert_init_std is None:
        expert_init_std = init_std
    if router_init_std is None:
        router_init_std = init_std
    if embed_init_std is None:
        embed_init_std = init_std
    dh = d_model // n_heads
    src = layers.data(name='src', shape=[seq_len], dtype='int64')
    x = layers.embedding(input=src, size=[vocab_size, d_model],
                         dtype=dtype,
                         param_attr=_attr('olmoe_embed', embed_init_std))
    if dtype != 'float32':
        x = layers.cast(x=x, dtype='float32')
    counts = []
    for i in range(n_layers):
        p = 'olmoe_l%d_' % i
        h = layers.rms_norm(input=x, epsilon=eps,
                            param_attr=_attr(p + 'in_norm_w'))
        sq = [d_model, d_model]
        q = _linear(h, p + 'q_w', sq, dtype, init_std)
        k = _linear(h, p + 'k_w', sq, dtype, init_std)
        v = _linear(h, p + 'v_w', sq, dtype, init_std)
        q = layers.rms_norm(input=q, epsilon=eps,
                            param_attr=_attr(p + 'q_norm_w'))
        k = layers.rms_norm(input=k, epsilon=eps,
                            param_attr=_attr(p + 'k_norm_w'))
        heads = [-1, seq_len, n_heads, dh]
        q = layers.rotary_embedding(layers.reshape(x=q, shape=heads),
                                    theta=theta)
        k = layers.rotary_embedding(layers.reshape(x=k, shape=heads),
                                    theta=theta)
        flat = [-1, seq_len, d_model]
        ctx = fluid.nets.scaled_dot_product_attention(
            layers.reshape(x=q, shape=flat),
            layers.reshape(x=k, shape=flat), v, num_heads=n_heads,
            causal=True)
        x = layers.elementwise_add(
            x=x, y=_linear(ctx, p + 'o_w', sq, dtype, init_std))
        h = layers.rms_norm(input=x, epsilon=eps,
                            param_attr=_attr(p + 'post_norm_w'))
        y, c = layers.moe_ffn(
            input=h, num_experts=n_experts, expert_size=expert_size,
            top_k=top_k, dtype=dtype,
            router_attr=_attr(p + 'router_w', router_init_std),
            gate_attr=_attr(p + 'gate_w', expert_init_std),
            up_attr=_attr(p + 'up_w', expert_init_std),
            down_attr=_attr(p + 'down_w', expert_init_std))
        x = layers.elementwise_add(x=x, y=y)
        counts.append(c)
    x = layers.rms_norm(input=x, epsilon=eps,
                        param_attr=_attr('olmoe_norm_f_w'))
    logits = _linear(x, 'olmoe_head_w', [d_model, vocab_size], dtype,
                     init_std)
    return src, logits, counts


def param_names(n_layers):
    """Every fixed parameter name ``build_logits`` creates, in layer
    order — the manifest the decode engine loads from a scope."""
    names = ['olmoe_embed']
    for i in range(n_layers):
        names.extend('olmoe_l%d_%s' % (i, s) for s in PER_LAYER)
    names.extend(['olmoe_norm_f_w', 'olmoe_head_w'])
    return names

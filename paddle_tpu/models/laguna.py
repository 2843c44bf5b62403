"""Laguna-S-2.1 (poolside/Laguna-S-2.1's config.json): a decoder whose
layers alternate, one that reads every position behind it and then
three that read a WINDOW of the newest (``layer_types``), with more
query heads on the window layers than on the others over the same K/V
heads (``num_attention_heads_per_layer``), rotary positions that differ
by kind, a sigmoid output gate a head, a leading dense SwiGLU layer and
after it routed experts under a softmax router (the ``top_k`` weights
renormalised and scaled) beside a shared expert; no biases, untied head.

Serving only, and only through the decode engine: this module DECLARES
the parameters, each under a FIXED name (``laguna_*``) and in the
weights' dtype, for the startup program to seed; the layer's equations
are ``inference.blocks.LagunaBlock`` (windowed attention over grouped
K/V heads has no ``Program`` op here), which pulls the weights from the
scope by ``param_names``.

A chip may hold a SHARE of each layer's routed experts: ``n_experts``
stacked experts of a router ``router_width`` wide, and a slice of the
vocabulary.
"""
import paddle_tpu as fluid

from .olmoe import _attr

__all__ = ['build_logits', 'param_names', 'ATTENTION', 'DENSE', 'EXPERTS']

# per-layer parameter suffixes, in creation order
ATTENTION = ('in_norm_w', 'q_w', 'k_w', 'v_w', 'g_w', 'o_w', 'post_norm_w')
DENSE = ('gate_w', 'up_w', 'down_w')
EXPERTS = ('router_w', 'gate_w', 'up_w', 'down_w', 'shared_gate_w',
           'shared_up_w', 'shared_down_w')


def build_logits(vocab_size, heads, n_kv_heads=2, head_dim=16, d_model=64,
                 first_dense=1, dense_size=128, router_width=16,
                 n_experts=None, expert_size=32, shared_size=None,
                 dtype='float32', init_std=0.02, gate_init_std=None,
                 dense_init_std=None, expert_init_std=None,
                 shared_init_std=None, router_init_std=None,
                 embed_init_std=None):
    """Declare the parameters of ``len(heads)`` layers, layer i with
    ``heads[i]`` query heads (a window layer has more than a full one)
    over ``n_kv_heads`` K/V heads of ``head_dim``; returns their names
    (``param_names``).  ``n_experts`` (default: the router's width)
    experts are held.  ``init_std`` seeds every matrix but the gate's,
    the dense FFN's, the experts', the shared expert's, the router's and
    the embedding, which take the ``*_std`` of their name (default: the
    same); the norms start at 1."""
    layers = fluid.layers
    std = lambda v: init_std if v is None else v
    n_experts = router_width if n_experts is None else n_experts
    shared_size = expert_size if shared_size is None else shared_size

    def matrix(name, shape, s):
        layers.create_parameter(shape=shape, dtype=dtype,
                                attr=_attr(name, s))

    def ones(name):
        from paddle_tpu.initializer import ConstantInitializer
        from paddle_tpu.param_attr import ParamAttr
        layers.create_parameter(
            shape=[d_model], dtype='float32',
            attr=ParamAttr(name=name, initializer=ConstantInitializer(1.0)))

    matrix('laguna_embed', [vocab_size, d_model], std(embed_init_std))
    kv = n_kv_heads * head_dim
    for i, h in enumerate(heads):
        p = 'laguna_l%d_' % i
        ones(p + 'in_norm_w')
        matrix(p + 'q_w', [d_model, h * head_dim], init_std)
        matrix(p + 'k_w', [d_model, kv], init_std)
        matrix(p + 'v_w', [d_model, kv], init_std)
        matrix(p + 'g_w', [d_model, h], std(gate_init_std))
        matrix(p + 'o_w', [h * head_dim, d_model], init_std)
        ones(p + 'post_norm_w')
        if i < first_dense:
            s = std(dense_init_std)
            matrix(p + 'gate_w', [d_model, dense_size], s)
            matrix(p + 'up_w', [d_model, dense_size], s)
            matrix(p + 'down_w', [dense_size, d_model], s)
            continue
        s = std(expert_init_std)
        # the router stays float32 (ops/moe.py ``moe_route``)
        layers.create_parameter(
            shape=[d_model, router_width], dtype='float32',
            attr=_attr(p + 'router_w', std(router_init_std)))
        matrix(p + 'gate_w', [n_experts, d_model, expert_size], s)
        matrix(p + 'up_w', [n_experts, d_model, expert_size], s)
        matrix(p + 'down_w', [n_experts, expert_size, d_model], s)
        s = std(shared_init_std)
        matrix(p + 'shared_gate_w', [d_model, shared_size], s)
        matrix(p + 'shared_up_w', [d_model, shared_size], s)
        matrix(p + 'shared_down_w', [shared_size, d_model], s)
    ones('laguna_norm_f_w')
    matrix('laguna_head_w', [d_model, vocab_size], init_std)
    return param_names(len(heads), first_dense)


def param_names(n_layers, first_dense=1):
    """Every fixed parameter name ``build_logits`` creates, in layer
    order — the manifest the decode engine loads from a scope."""
    names = ['laguna_embed']
    for i in range(n_layers):
        names.extend('laguna_l%d_%s' % (i, s) for s in
                     ATTENTION + (DENSE if i < first_dense else EXPERTS))
    names.extend(['laguna_norm_f_w', 'laguna_head_w'])
    return names

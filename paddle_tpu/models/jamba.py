"""Jamba (ai21labs/AI21-Jamba2-3B's config.json, ``model_type`` jamba;
Lieber et al., arXiv:2403.19887; the block is ``transformers``'
``modeling_jamba.py``): a decoder whose layer i is attention where
``i % attn_layer_period == attn_layer_offset`` and a Mamba-1 mixer
(Gu & Dao, arXiv:2312.00752) everywhere else, each followed by a gated
MLP.  Attention is grouped-query with NO positional encoding; the Mamba
mixer carries Jamba's own RMSNorms on dt, B and C; no projection biases
(the convolution and dt have theirs); embedding and head are tied.

Serving only, and only through the decode engine: this module DECLARES
the parameters, each under a FIXED name (``jamba_*``) and in the
weights' dtype, for the startup program to seed; the layer's equations
are ``inference.blocks.JambaBlock``.  The engine traces like layers
once, under a ``lax.scan`` over each RUN of consecutive Mamba layers, so
a run's weights are declared STACKED, ``jamba_r<run>_<name>`` [layers
of the run, ...]: no second copy of 5 GB is made to stack them.  An
attention layer's are ``jamba_l<layer>_<name>``.

Channels are the minor dimension of every small Mamba tensor (``conv_w``
[K, Dc], ``a_log`` [N, Dc]; the published ones are [Dc, 1, K] and
[Dc, N]): ops/ssm.py says why.
"""
import math

import jax.numpy as jnp

import paddle_tpu as fluid

from .olmoe import _attr

__all__ = ['build_logits', 'param_names', 'finish_init', 'layer_kinds',
           'state_runs', 'STATE_LAYER', 'ATTENTION_LAYER']

# per-layer parameter suffixes, in creation order
MLP = ('post_norm_w', 'gate_w', 'up_w', 'down_w')
STATE_LAYER = ('in_norm_w', 'in_w', 'conv_w', 'conv_b', 'x_w', 'dt_norm_w',
               'b_norm_w', 'c_norm_w', 'dt_w', 'dt_b', 'a_log', 'd',
               'out_w') + MLP
ATTENTION_LAYER = ('in_norm_w', 'q_w', 'k_w', 'v_w', 'o_w') + MLP


def layer_kinds(n_layers, period, offset):
    """A kind a layer as ``JambaConfig`` computes it: ``'full'``
    (attention) where ``i % period == offset``, else ``'state'``."""
    return tuple('full' if i % period == offset else 'state'
                 for i in range(n_layers))


def state_runs(kinds):
    """The runs of consecutive state layers: [(first layer, layers)]."""
    runs = []
    for i, k in enumerate(kinds):
        if k != 'state':
            continue
        if runs and sum(runs[-1]) == i:
            runs[-1] = (runs[-1][0], runs[-1][1] + 1)
        else:
            runs.append((i, 1))
    return runs


def build_logits(vocab_size, n_layers=4, d_model=64, ffn_size=128,
                 n_heads=4, n_kv_heads=1, head_dim=None, d_inner=128,
                 d_state=16, d_conv=4, dt_rank=8, period=2, offset=1,
                 dtype='float32', init_std=0.02, embed_init_std=None,
                 dt_min=1e-3, dt_max=1e-1):
    """Declare the parameters of ``n_layers`` layers; returns their names
    (``param_names``).  ``init_std`` seeds every matrix, ``embed_init_std``
    the embedding (default: the same); norm weights and ``d`` start at
    1; the convolution's weight and bias are uniform in +-``d_conv``^-1/2
    (``torch.nn.Conv1d``'s own default, which Mamba keeps).  ``a_log``
    and ``dt_b`` are finished by ``finish_init`` once the startup
    program has run: ``dt_b`` is declared uniform in [log ``dt_min``,
    log ``dt_max``], the log of the step it will stand for."""
    layers = fluid.layers
    from paddle_tpu.initializer import (ConstantInitializer,
                                        UniformInitializer)
    from paddle_tpu.param_attr import ParamAttr
    head_dim = head_dim or d_model // n_heads

    def declare(name, shape, initializer, dt='float32'):
        layers.create_parameter(
            shape=list(shape), dtype=dt,
            attr=ParamAttr(name=name, initializer=initializer))

    def matrix(name, shape, s=init_std):
        layers.create_parameter(shape=list(shape), dtype=dtype,
                                attr=_attr(name, s))

    def constant(name, shape, value=1.0):
        declare(name, shape, ConstantInitializer(value))

    def mlp(p, lead):
        constant(p + 'post_norm_w', lead + (d_model,))
        matrix(p + 'gate_w', lead + (d_model, ffn_size))
        matrix(p + 'up_w', lead + (d_model, ffn_size))
        matrix(p + 'down_w', lead + (ffn_size, d_model))

    matrix('jamba_embed', (vocab_size, d_model),
           init_std if embed_init_std is None else embed_init_std)
    kinds = layer_kinds(n_layers, period, offset)
    bound = d_conv ** -0.5
    for r, (_first, n) in enumerate(state_runs(kinds)):
        p, lead = 'jamba_r%d_' % r, (n,)
        constant(p + 'in_norm_w', lead + (d_model,))
        matrix(p + 'in_w', lead + (d_model, 2 * d_inner))
        declare(p + 'conv_w', lead + (d_conv, d_inner),
                UniformInitializer(-bound, bound))
        declare(p + 'conv_b', lead + (d_inner,),
                UniformInitializer(-bound, bound))
        matrix(p + 'x_w', lead + (d_inner, dt_rank + 2 * d_state))
        constant(p + 'dt_norm_w', lead + (dt_rank,))
        constant(p + 'b_norm_w', lead + (d_state,))
        constant(p + 'c_norm_w', lead + (d_state,))
        matrix(p + 'dt_w', lead + (dt_rank, d_inner))
        declare(p + 'dt_b', lead + (d_inner,),
                UniformInitializer(math.log(dt_min), math.log(dt_max)))
        constant(p + 'a_log', lead + (d_state, d_inner), 0.0)
        constant(p + 'd', lead + (d_inner,))
        matrix(p + 'out_w', lead + (d_inner, d_model))
        mlp(p, lead)
    for i, k in enumerate(kinds):
        if k != 'full':
            continue
        p = 'jamba_l%d_' % i
        constant(p + 'in_norm_w', (d_model,))
        matrix(p + 'q_w', (d_model, n_heads * head_dim))
        matrix(p + 'k_w', (d_model, n_kv_heads * head_dim))
        matrix(p + 'v_w', (d_model, n_kv_heads * head_dim))
        matrix(p + 'o_w', (n_heads * head_dim, d_model))
        mlp(p, ())
    constant('jamba_norm_f_w', (d_model,))
    return param_names(n_layers, period, offset)


def param_names(n_layers, period, offset):
    """Every fixed parameter name ``build_logits`` creates: the manifest
    the decode engine loads from a scope."""
    kinds = layer_kinds(n_layers, period, offset)
    names = ['jamba_embed']
    for r in range(len(state_runs(kinds))):
        names.extend('jamba_r%d_%s' % (r, s) for s in STATE_LAYER)
    for i, k in enumerate(kinds):
        if k == 'full':
            names.extend('jamba_l%d_%s' % (i, s) for s in ATTENTION_LAYER)
    return names + ['jamba_norm_f_w']


def finish_init(params):
    """Mamba's own initialisation of the two tensors a startup program's
    initializers cannot write, in place in ``params`` (returned):
    ``a_log[n] = log(n + 1)`` for every channel (the state's N lanes
    decay at rates 1..N a unit of step), and ``dt_b`` the inverse
    softplus of the step whose log it was declared as, so that
    ``softplus(dt_b)`` is log-uniform in [``dt_min``, ``dt_max``]: a
    channel's slowest lane remembers ``1 / step`` tokens."""
    for name in list(params):
        if name.endswith('_a_log'):
            a = params[name]
            lane = jnp.log(jnp.arange(1, a.shape[-2] + 1, dtype=a.dtype))
            params[name] = jnp.broadcast_to(lane[:, None], a.shape)
        elif name.endswith('_dt_b'):
            step = jnp.exp(params[name])
            params[name] = step + jnp.log(-jnp.expm1(-step))
    return params

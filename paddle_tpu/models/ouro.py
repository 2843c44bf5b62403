"""Ouro (ByteDance/Ouro-2.6B's config.json, ``model_type`` ouro; "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741): a
decoder whose stack of layers runs ``total_ut_steps`` times a token over
ONE set of weights.  A layer is pre-RMSNorm attention (full multi-head,
rotary positions) and a SwiGLU FFN with a second RMSNorm on each
branch's way OUT ("sandwich"); the final norm closes every recurrence
and feeds the next; an exit gate (one sigmoid a token a recurrence)
reads the closed stream; no projection biases, untied head.

Serving only, and only through the decode engine: this module DECLARES
the parameters, each under a FIXED name (``ouro_*``) and in the weights'
dtype, for the startup program to seed; the layer's equations and the
walk over recurrences are ``inference.blocks.OuroBlock`` and
``DecodeEngine._layers``, which pull the weights from the scope by
``param_names``.  The weights are those of ``n_layers`` layers, however
often they run.
"""
import paddle_tpu as fluid

from .olmoe import _attr

__all__ = ['build_logits', 'param_names', 'PER_LAYER', 'NORMS']

# per-layer parameter suffixes, in creation order
PER_LAYER = ('in_norm_w', 'q_w', 'k_w', 'v_w', 'o_w', 'in_norm2_w',
             'post_norm_w', 'gate_w', 'up_w', 'down_w', 'post_norm2_w')
NORMS = ('in_norm_w', 'in_norm2_w', 'post_norm_w', 'post_norm2_w')


def build_logits(vocab_size, n_layers=2, d_model=64, ffn_size=128,
                 dtype='float32', init_std=0.02, embed_init_std=None,
                 branch_norm_init=1.0):
    """Declare the parameters of ``n_layers`` layers; returns their names
    (``param_names``).  ``init_std`` seeds every matrix and the exit
    gate's vector, ``embed_init_std`` the embedding (default: the same).
    The norms on a branch's way IN and the closing norm start at 1,
    those on its way OUT at ``branch_norm_init`` (the RMS of what a
    branch adds to the stream); the gate's bias at 0."""
    layers = fluid.layers
    from paddle_tpu.initializer import ConstantInitializer
    from paddle_tpu.param_attr import ParamAttr

    def matrix(name, shape, s=init_std):
        layers.create_parameter(shape=shape, dtype=dtype,
                                attr=_attr(name, s))

    def constant(name, value, shape=(d_model,)):
        layers.create_parameter(
            shape=list(shape), dtype='float32',
            attr=ParamAttr(name=name,
                           initializer=ConstantInitializer(value)))

    matrix('ouro_embed', [vocab_size, d_model],
           init_std if embed_init_std is None else embed_init_std)
    for i in range(n_layers):
        p = 'ouro_l%d_' % i
        constant(p + 'in_norm_w', 1.0)
        for name in ('q_w', 'k_w', 'v_w', 'o_w'):
            matrix(p + name, [d_model, d_model])
        constant(p + 'in_norm2_w', branch_norm_init)
        constant(p + 'post_norm_w', 1.0)
        matrix(p + 'gate_w', [d_model, ffn_size])
        matrix(p + 'up_w', [d_model, ffn_size])
        matrix(p + 'down_w', [ffn_size, d_model])
        constant(p + 'post_norm2_w', branch_norm_init)
    constant('ouro_norm_f_w', 1.0)
    # the exit gate stays float32 (one dot product a row a recurrence)
    layers.create_parameter(shape=[d_model], dtype='float32',
                            attr=_attr('ouro_exit_w', init_std))
    constant('ouro_exit_b', 0.0, shape=(1,))
    matrix('ouro_head_w', [d_model, vocab_size])
    return param_names(n_layers)


def param_names(n_layers):
    """Every fixed parameter name ``build_logits`` creates, in layer
    order — the manifest the decode engine loads from a scope."""
    names = ['ouro_embed']
    for i in range(n_layers):
        names.extend('ouro_l%d_%s' % (i, s) for s in PER_LAYER)
    names.extend(['ouro_norm_f_w', 'ouro_exit_w', 'ouro_exit_b',
                  'ouro_head_w'])
    return names

"""The system under test for a ``dots_vlm_serve`` configuration: the
program's dots.vlm1 language model (models/dots_vlm.py) served by one
``DecodeServer`` on one ``DecodeEngine`` through the engine's block
description, with the deployment's engine settings from the traffic
file.  Everything but the construction is decoder_serve.py's: the tap,
the requests, the replay.

The configuration gives this chip's share: ``n_routed_experts`` experts
held, ``first_expert ..``, of a router ``router_width`` wide, and a
slice of the vocabulary.

On a tree without the model the imports below fail, before any weight
or program exists: the cell then ends at once with a non-zero code.
"""
import numpy as np

from paddle_tpu.inference.blocks import DotsVlmBlock
from paddle_tpu.models import dots_vlm

from . import decoder_serve
from .decoder_serve import Request, Tap, buckets_for    # noqa: F401


def yarn_of(c):
    r = c['rope_scaling']
    return {'factor': r['factor'], 'beta_fast': r['beta_fast'],
            'beta_slow': r['beta_slow'],
            'original_max': r['original_max_position_embeddings']}


def decide_held(params, c, seed):
    """The correction bias of the experts HELD here, set so that their
    choice has a margin (the configuration's ``assumed`` says why): in
    every expert layer ``held_chosen_per_layer`` of them, drawn from the
    seed, get ``+held_bias`` and are among every token's 8, the others
    ``-held_bias`` and are among no token's; their WEIGHTS stay each
    token's sigmoid scores, and the experts held elsewhere keep their
    seeded bias and are chosen token by token."""
    import jax.numpy as jnp
    a, first, held = c['assumed'], c['first_expert'], c['n_routed_experts']
    rng = np.random.default_rng(seed)
    for i in range(c['first_k_dense_replace'], c['num_hidden_layers']):
        name = 'dots_l%d_router_bias' % i
        b = np.array(params[name])
        b[first:first + held] = -a['held_bias']
        b[first + rng.choice(held, a['held_chosen_per_layer'],
                             replace=False)] = a['held_bias']
        params[name] = jnp.asarray(b, params[name].dtype)
    return params


class Served(decoder_serve.Served):
    """Weights, engine and (after ``start``) server and tap."""

    def __init__(self, run, buckets):
        import paddle_tpu as fluid
        from paddle_tpu.inference.decode import DecodeEngine, extract_params
        c, e, a = run.config, run.traffic['engine'], run.config['assumed']
        self.run = run
        self.layers, self.heads = c['num_hidden_layers'], \
            c['num_attention_heads']
        shape = dict(
            qk_nope_head_dim=c['qk_nope_head_dim'],
            qk_rope_head_dim=c['qk_rope_head_dim'],
            v_head_dim=c['v_head_dim'], top_k=c['num_experts_per_tok'],
            n_group=c['n_group'], topk_group=c['topk_group'],
            routed_scaling_factor=c['routed_scaling_factor'],
            first_expert=c['first_expert'],
            first_dense=c['first_k_dense_replace'], eps=c['rms_norm_eps'],
            theta=c['rope_theta'], yarn=yarn_of(c),
            mscale_all_dim=c['rope_scaling']['mscale_all_dim'])
        block = DotsVlmBlock(self.heads, renormalize=c['norm_topk_prob'],
                             **shape)
        with run.phases('startup_program'):
            # the parameters are declared in the weights' dtype: no
            # float32 copy of them ever exists on the device
            scope = fluid.Scope()
            main_p, startup = fluid.Program(), fluid.Program()
            main_p.random_seed = startup.random_seed = \
                run.seed % (2 ** 31 - 1) + 1
            with fluid.program_guard(main_p, startup):
                dots_vlm.build_logits(
                    vocab_size=c['vocab_size'], seq_len=e['max_seq'],
                    n_layers=self.layers, d_model=c['hidden_size'],
                    n_heads=self.heads, q_lora_rank=c['q_lora_rank'],
                    kv_lora_rank=c['kv_lora_rank'],
                    dense_size=c['intermediate_size'],
                    router_width=c['router_width'],
                    n_experts=c['n_routed_experts'],
                    expert_size=c['moe_intermediate_size'],
                    shared_size=c['n_shared_experts']
                    * c['moe_intermediate_size'],
                    norm_topk_prob=c['norm_topk_prob'], dtype=c['dtype'],
                    init_std=a['init_std'],
                    dense_init_std=a['dense_init_std'],
                    expert_init_std=a['expert_init_std'],
                    shared_init_std=a['shared_init_std'],
                    router_init_std=a['router_init_std'],
                    router_bias_std=a['router_bias_std'],
                    embed_init_std=a['embed_init_std'], **shape)
            place = fluid.CPUPlace() if run.rehearse else fluid.TPUPlace(0)
            fluid.Executor(place).run(startup, scope=scope)
            self.params = decide_held(
                extract_params(scope, self.layers, block), c, run.seed)
        with run.phases('pool_allocation'):
            # the deployment's engine settings, all of them, are the
            # traffic file's ("arithmetic" is its note on the sizing)
            self.engine = DecodeEngine(
                self.params, n_layers=self.layers, n_heads=self.heads,
                prefill_bucket=max(buckets), dtype=c['kv_dtype'],
                block=block,
                **{k: v for k, v in e.items() if k != 'arithmetic'})
            # (the whole bucket ladder stays: this deployment prefills in
            # chunks, whose ragged remainders fall into every chunk
            # bucket, and warm-up compiles those and no whole-prompt one)
        with run.phases('compile_and_warm_execution'):
            self.engine.warmup()
        self.server = self.tap = None

"""The system under test for a ``laguna_serve`` configuration: the
program's Laguna-S-2.1 decoder (models/laguna.py declares the weights,
inference/blocks.py ``LagunaBlock`` is the layer) served by one
``DecodeServer`` on one ``DecodeEngine``, with the deployment's engine
settings from the traffic file.  The tap and the requests are
decoder_serve.py's; the construction and ``replay`` are here, because a
stream's pages are a pair with this engine: its pages of the whole
context, and its ring in the window layers' group.

The configuration gives this chip's share: ``num_experts`` experts
held, ``first_expert ..``, of a router ``router_width`` wide, and a
slice of the vocabulary; and it runs the first ``num_hidden_layers``
entries of the published per-layer lists.

On a tree without the model the imports below fail, before any weight
or program exists: the cell then ends at once with a non-zero code.
"""
import numpy as np

from paddle_tpu.inference.blocks import LagunaBlock
from paddle_tpu.models import laguna

from . import decoder_serve
from .decoder_serve import Request, Tap, buckets_for    # noqa: F401

KINDS = {'full_attention': 'full', 'sliding_attention': 'window'}


def spec_of(c):
    """What the weights' shapes do not say, for the block and (the same
    dict, as ``n_heads``) for the reference: a kind a layer, the window,
    the router's ten and its scale, the share, and the rotary settings
    a kind."""
    n, dh = c['num_hidden_layers'], c['head_dim']
    rope = {}
    for name, r in c['rope_parameters'].items():
        kind = {'theta': float(r['rope_theta']),
                'lanes': int(dh * r.get('partial_rotary_factor', 1))}
        if r['rope_type'] == 'yarn':
            kind['factor'] = float(r['attention_factor'])
            kind['yarn'] = {
                'factor': float(r['factor']),
                'beta_fast': float(r['beta_fast']),
                'beta_slow': float(r['beta_slow']),
                'original_max': int(r['original_max_position_embeddings'])}
        rope[KINDS[name]] = kind
    return {'kinds': tuple(KINDS[k] for k in c['layer_types'][:n]),
            'heads': tuple(c['num_attention_heads_per_layer'][:n]),
            'kv_heads': c['num_key_value_heads'],
            'window': c['sliding_window'],
            'top_k': c['num_experts_per_tok'],
            'scale': c['moe_routed_scaling_factor'],
            'first_expert': c['first_expert'], 'rope': rope}


def decide_held(params, c, seed):
    """Decide the HELD experts' choice in the router's columns (the
    configuration's ``assumed`` says why): lane ``bias_lane`` of the
    stream is made a constant (the embedding's column is
    ``bias_lane_value``, and no branch writes to the lane), and the held
    experts' router columns read that lane alone, ``+held_logit_gain``
    for ``held_chosen_per_layer`` of them a layer, drawn from the seed
    (among every token's ten), ``-held_logit_gain`` for the others
    (among no token's).  Their WEIGHTS stay each token's renormalised
    softmax scores, and the experts held elsewhere keep their seeded
    columns and are chosen token by token."""
    import jax.numpy as jnp
    a, first, held = c['assumed'], c['first_expert'], c['num_experts']
    lane, rng = a['bias_lane'], np.random.default_rng(seed)

    def put(name, index, value):
        params[name] = params[name].at[index].set(
            jnp.asarray(value, params[name].dtype))

    put('laguna_embed', (slice(None), lane), a['bias_lane_value'])
    dense = [i for i, k in enumerate(c['mlp_layer_types']) if k == 'dense']
    for i in range(c['num_hidden_layers']):
        n = 'laguna_l%d_' % i
        put(n + 'o_w', (slice(None), lane), 0.0)
        put(n + 'down_w', (Ellipsis, lane), 0.0)
        if i in dense:
            continue
        put(n + 'shared_down_w', (slice(None), lane), 0.0)
        column = np.zeros(params[n + 'router_w'].shape[0], np.float32)
        column[lane] = -a['held_logit_gain']
        put(n + 'router_w', (slice(None), slice(first, first + held)),
            column[:, None])
        chosen = first + rng.choice(held, a['held_chosen_per_layer'],
                                    replace=False)
        put(n + 'router_w', (lane, chosen), a['held_logit_gain'])
    return params


class Served(decoder_serve.Served):
    """Weights, engine and (after ``start``) server and tap."""

    def __init__(self, run, buckets):
        import paddle_tpu as fluid
        from paddle_tpu.inference.decode import DecodeEngine, extract_params
        c, e, a = run.config, run.traffic['engine'], run.config['assumed']
        self.run = run
        self.layers = c['num_hidden_layers']
        # what the harness hands the reference as ``n_heads``: the
        # heads, layer by layer, and the rest of ``spec_of``
        self.heads = spec = spec_of(c)
        dense = sum(k == 'dense' for k in c['mlp_layer_types'][:self.layers])
        block = LagunaBlock(
            spec['heads'], spec['kv_heads'], c['head_dim'], spec['kinds'],
            spec['window'], spec['rope'], top_k=spec['top_k'],
            routed_scaling_factor=spec['scale'],
            first_expert=spec['first_expert'], first_dense=dense,
            eps=c['rms_norm_eps'])
        with run.phases('startup_program'):
            # the parameters are declared in the weights' dtype: no
            # float32 copy of them ever exists on the device
            scope = fluid.Scope()
            main_p, startup = fluid.Program(), fluid.Program()
            main_p.random_seed = startup.random_seed = \
                run.seed % (2 ** 31 - 1) + 1
            with fluid.program_guard(main_p, startup):
                laguna.build_logits(
                    vocab_size=c['vocab_size'], heads=spec['heads'],
                    n_kv_heads=spec['kv_heads'], head_dim=c['head_dim'],
                    d_model=c['hidden_size'], first_dense=dense,
                    dense_size=c['intermediate_size'],
                    router_width=c['router_width'],
                    n_experts=c['num_experts'],
                    expert_size=c['moe_intermediate_size'],
                    shared_size=c['shared_expert_intermediate_size'],
                    dtype=c['dtype'], init_std=a['init_std'],
                    gate_init_std=a['gate_init_std'],
                    dense_init_std=a['dense_init_std'],
                    expert_init_std=a['expert_init_std'],
                    shared_init_std=a['shared_init_std'],
                    router_init_std=a['router_init_std'],
                    embed_init_std=a['embed_init_std'])
            place = fluid.CPUPlace() if run.rehearse else fluid.TPUPlace(0)
            fluid.Executor(place).run(startup, scope=scope)
            self.params = decide_held(
                extract_params(scope, self.layers, block), c, run.seed)
        with run.phases('pool_allocation'):
            # the deployment's engine settings, all of them, are the
            # traffic file's ("arithmetic" is its note on the sizing)
            self.engine = DecodeEngine(
                self.params, n_layers=self.layers, n_heads=max(spec['heads']),
                prefill_bucket=max(buckets), dtype=c['kv_dtype'],
                block=block,
                **{k: v for k, v in e.items() if k != 'arithmetic'})
            # (the whole bucket ladder stays: this deployment prefills in
            # chunks, whose ragged remainders fall into every chunk
            # bucket, and warm-up compiles those and no whole-prompt one)
        with run.phases('compile_and_warm_execution'):
            self.engine.warmup()
        self.server = self.tap = None

    def replay(self, prompt, n_new):
        """decoder_serve.py's replay with a stream's pages of both
        groups: prefill the way this deployment's server does, then
        decode through the pages by hand, greedy."""
        eng = self.engine
        span = len(prompt) + n_new
        pages = (eng.cache.alloc(-(-span // eng.page_size)),
                 eng.cache.window.alloc(eng.ring_for(span)))
        if eng.chunked:
            for lo, hi in eng.chunk_spans(len(prompt)):
                first = eng.prefill_chunk(prompt[lo:hi], pages, lo)
        else:
            first = eng.prefill_into(np.asarray(prompt), pages)
        rows = [first]
        toks = [int(np.argmax(rows[0]))]
        for j in range(n_new - 1):
            pt = np.tile(eng.idle_row, (eng.max_streams, 1))
            pt[0] = eng.table_row(pages)
            t_in = np.zeros((eng.max_streams,), np.int64)
            t_in[0] = toks[-1]
            ctx = np.zeros((eng.max_streams,), np.int32)
            ctx[0] = len(prompt) + j
            rows.append(eng.step(t_in, pt, ctx)[1][0])
            toks.append(int(np.argmax(rows[-1])))
        eng.cache.free(pages[0])
        eng.cache.window.free(pages[1])
        return np.stack(rows), toks

"""The system under test for an ``olmoe_serve`` configuration: the
program's OLMoE decoder (models/olmoe.py) served by one ``DecodeServer``
on one ``DecodeEngine`` through the engine's block description, with the
deployment's engine settings from the traffic file.  Everything but the
construction is decoder_serve.py's: the tap, the requests, the replay.

On a tree without the model the imports below fail, before any weight
or program exists: the cell then ends at once with a non-zero code.
"""
from paddle_tpu.inference.blocks import OlmoeBlock
from paddle_tpu.models import olmoe

from . import decoder_serve
from .decoder_serve import Request, Tap, buckets_for    # noqa: F401


class Served(decoder_serve.Served):
    """Weights, engine and (after ``start``) server and tap."""

    def __init__(self, run, buckets):
        import paddle_tpu as fluid
        from paddle_tpu.inference.decode import DecodeEngine, extract_params
        c, e = run.config, run.traffic['engine']
        self.run = run
        self.layers, self.heads = c['num_hidden_layers'], \
            c['num_attention_heads']
        block = OlmoeBlock(self.heads, top_k=c['num_experts_per_tok'],
                           eps=c['rms_norm_eps'], theta=c['rope_theta'],
                           renormalize=c['norm_topk_prob'])
        with run.phases('startup_program'):
            # the parameters are declared in the weights' dtype: no
            # float32 copy of them ever exists on the device
            scope = fluid.Scope()
            main_p, startup = fluid.Program(), fluid.Program()
            main_p.random_seed = startup.random_seed = \
                run.seed % (2 ** 31 - 1) + 1
            with fluid.program_guard(main_p, startup):
                olmoe.build_logits(
                    vocab_size=c['vocab_size'],
                    seq_len=e['max_seq'], n_layers=self.layers,
                    d_model=c['hidden_size'], n_heads=self.heads,
                    n_experts=c['num_experts'],
                    expert_size=c['intermediate_size'],
                    top_k=c['num_experts_per_tok'], dtype=c['dtype'],
                    init_std=c['assumed']['init_std'],
                    expert_init_std=c['assumed']['expert_init_std'],
                    router_init_std=c['assumed']['router_init_std'],
                    embed_init_std=c['assumed']['embed_init_std'],
                    eps=c['rms_norm_eps'], theta=c['rope_theta'])
            place = fluid.CPUPlace() if run.rehearse else fluid.TPUPlace(0)
            fluid.Executor(place).run(startup, scope=scope)
            self.params = extract_params(scope, self.layers, block)
        with run.phases('pool_allocation'):
            # the deployment's engine settings, all of them, are the
            # traffic file's ("arithmetic" is its note on the sizing)
            self.engine = DecodeEngine(
                self.params, n_layers=self.layers, n_heads=self.heads,
                prefill_bucket=max(buckets), dtype=c['kv_dtype'],
                block=block,
                **{k: v for k, v in e.items() if k != 'arithmetic'})
            self.engine.buckets = [b for b in self.engine.buckets
                                   if b in buckets]
        with run.phases('compile_and_warm_execution'):
            self.engine.warmup()
        self.server = self.tap = None

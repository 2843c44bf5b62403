"""The system under test for an ``ouro_serve`` configuration: the
program's Ouro decoder (models/ouro.py declares the weights,
inference/blocks.py ``OuroBlock`` is the layer, ``DecodeEngine._layers``
walks the layers ``total_ut_steps`` times over them) served by one
``DecodeServer`` on one ``DecodeEngine``, with the deployment's engine
settings from the traffic file.  Everything but the construction is
decoder_serve.py's: the tap, the requests, the replay (a stream's pages
are one list: every cache slot reads the same page table).

On a tree without the model the imports below fail, before any weight
or program exists: the cell then ends at once with a non-zero code.
"""
from paddle_tpu.inference.blocks import OuroBlock
from paddle_tpu.models import ouro

from . import decoder_serve
from .decoder_serve import Request, Tap, buckets_for    # noqa: F401


class Served(decoder_serve.Served):
    """Weights, engine and (after ``start``) server and tap."""

    def __init__(self, run, buckets):
        import paddle_tpu as fluid
        from paddle_tpu.inference.decode import DecodeEngine, extract_params
        c, e, a = run.config, run.traffic['engine'], run.config['assumed']
        self.run = run
        self.layers = c['num_hidden_layers']
        # what the harness hands the reference as ``n_heads``
        self.heads = {'heads': c['num_attention_heads'],
                      'ut_steps': c['total_ut_steps']}
        block = OuroBlock(
            c['num_attention_heads'], ut_steps=c['total_ut_steps'],
            early_exit_threshold=c['early_exit_threshold'],
            eps=c['rms_norm_eps'], theta=c['rope_theta'])
        with run.phases('startup_program'):
            # the parameters are declared in the weights' dtype: no
            # float32 copy of them ever exists on the device
            scope = fluid.Scope()
            main_p, startup = fluid.Program(), fluid.Program()
            main_p.random_seed = startup.random_seed = \
                run.seed % (2 ** 31 - 1) + 1
            with fluid.program_guard(main_p, startup):
                ouro.build_logits(
                    vocab_size=c['vocab_size'], n_layers=self.layers,
                    d_model=c['hidden_size'],
                    ffn_size=c['intermediate_size'], dtype=c['dtype'],
                    init_std=a['init_std'],
                    embed_init_std=a['embed_init_std'],
                    branch_norm_init=a['branch_norm_init'])
            place = fluid.CPUPlace() if run.rehearse else fluid.TPUPlace(0)
            fluid.Executor(place).run(startup, scope=scope)
            self.params = extract_params(scope, self.layers, block)
        with run.phases('pool_allocation'):
            # the deployment's engine settings, all of them, are the
            # traffic file's ("arithmetic" is its note on the sizing)
            self.engine = DecodeEngine(
                self.params, n_layers=self.layers,
                n_heads=c['num_attention_heads'],
                prefill_bucket=max(buckets), dtype=c['kv_dtype'],
                block=block,
                **{k: v for k, v in e.items() if k != 'arithmetic'})
            # (the whole bucket ladder stays: this deployment prefills in
            # chunks, whose ragged remainders fall into every chunk
            # bucket, and warm-up compiles those and no whole-prompt one)
        with run.phases('compile_and_warm_execution'):
            self.engine.warmup()
        self.server = self.tap = None

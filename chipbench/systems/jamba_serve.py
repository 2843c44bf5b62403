"""The system under test for a ``jamba_serve`` configuration: the
program's Jamba decoder (models/jamba.py declares the weights,
inference/blocks.py ``JambaBlock`` is the layer: Mamba mixers that keep
a state a STREAM beside attention layers that cache K/V a position)
served by one ``DecodeServer`` on one ``DecodeEngine``, with the
deployment's engine settings from the traffic file.  The tap and the
requests are decoder_serve.py's; the construction, ``buckets_for`` and
``replay`` are here, because an engine call of this block is told which
SLOT's state a chunk continues: a stream's pages go in as the pair
(pages, slot), the slot being the stream's row of a decode step.

``buckets_for``: this deployment prefills in chunks, so no whole-prompt
program is ever compiled and the engine's bucket ladder only has to
reach a chunk.  The harness also pads the REFERENCE's sequence to the
largest bucket; prompts longer than ``REFERENCE_PAD`` are served (in
chunks, like every prompt) and not compared, so their buckets are left
out and the reference runs at 2048 positions, where it would run at
16384 (4.3 GB of float32 logits over this vocabulary).  The ``check``
requests must fit: the constructor says so where they do not.

On a tree without the model the imports below fail, before any weight
or program exists: the cell then ends at once with a non-zero code.
"""
import numpy as np

from paddle_tpu.inference.blocks import JambaBlock
from paddle_tpu.models import jamba

from . import decoder_serve
from .decoder_serve import Request, Tap    # noqa: F401

REFERENCE_PAD = 2048


def buckets_for(page_size, lengths):
    return decoder_serve.buckets_for(
        page_size, [min(int(n), REFERENCE_PAD) for n in lengths])


def spec_of(c):
    """What the weights' shapes do not say, for the block and (the same
    dict, as ``n_heads``) for the reference."""
    return {'heads': c['num_attention_heads'],
            'kv_heads': c['num_key_value_heads'],
            'period': c['attn_layer_period'],
            'offset': c['attn_layer_offset']}


def seeded_params(c, seed, place):
    """The configuration's weights from ``seed``: the startup program,
    then Mamba's own initialisation of ``a_log`` and ``dt_b``."""
    import paddle_tpu as fluid
    from paddle_tpu.inference.decode import extract_params
    a, d = c['assumed'], c['hidden_size']
    scope = fluid.Scope()
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = seed % (2 ** 31 - 1) + 1
    with fluid.program_guard(main_p, startup):
        jamba.build_logits(
            vocab_size=c['vocab_size'], n_layers=c['num_hidden_layers'],
            d_model=d, ffn_size=c['intermediate_size'],
            n_heads=c['num_attention_heads'],
            n_kv_heads=c['num_key_value_heads'],
            head_dim=d // c['num_attention_heads'],
            d_inner=c['mamba_expand'] * d, d_state=c['mamba_d_state'],
            d_conv=c['mamba_d_conv'], dt_rank=c['mamba_dt_rank'],
            period=c['attn_layer_period'], offset=c['attn_layer_offset'],
            dtype=c['dtype'], init_std=a['init_std'],
            embed_init_std=a['embed_init_std'], dt_min=a['dt_min'],
            dt_max=a['dt_max'])
    fluid.Executor(place).run(startup, scope=scope)
    return jamba.finish_init(extract_params(
        scope, c['num_hidden_layers'], block_of(c)))


def block_of(c):
    return JambaBlock(
        c['num_attention_heads'], c['num_key_value_heads'],
        c['hidden_size'] // c['num_attention_heads'],
        c['attn_layer_period'], c['attn_layer_offset'],
        eps=c['rms_norm_eps'])


class Served(decoder_serve.Served):
    """Weights, engine and (after ``start``) server and tap."""

    def __init__(self, run, buckets):
        import paddle_tpu as fluid
        from paddle_tpu.inference.decode import DecodeEngine
        c, e = run.config, run.traffic['engine']
        self.run = run
        self.layers = c['num_hidden_layers']
        # what the harness hands the reference as ``n_heads``
        self.heads = spec_of(c)
        longest = max(k['prompt_tokens'] + k['output_tokens']
                      for k in run.traffic['check'])
        if longest > max(buckets):
            raise ValueError(
                'a check request of %d tokens does not fit the %d the '
                'reference is run at' % (longest, max(buckets)))
        with run.phases('startup_program'):
            # the parameters are declared in the weights' dtype: no
            # float32 copy of them ever exists on the device
            self.params = seeded_params(
                c, run.seed,
                fluid.CPUPlace() if run.rehearse else fluid.TPUPlace(0))
        with run.phases('pool_allocation'):
            # the deployment's engine settings, all of them, are the
            # traffic file's ("arithmetic" is its note on the sizing)
            self.engine = DecodeEngine(
                self.params, n_layers=self.layers,
                n_heads=c['num_attention_heads'],
                prefill_bucket=max(buckets), dtype=c['kv_dtype'],
                block=block_of(c),
                **{k: v for k, v in e.items() if k != 'arithmetic'})
        with run.phases('compile_and_warm_execution'):
            self.engine.warmup()
        self.server = self.tap = None

    def replay(self, prompt, n_new, slot=0):
        """decoder_serve.py's replay with the stream's slot: prefill in
        chunks into ``slot``'s state the way this deployment's server
        does, then decode through the pages and that state by hand,
        greedy, as row ``slot`` of the step."""
        eng = self.engine
        span = len(prompt) + n_new
        pages = eng.cache.alloc(-(-span // eng.page_size))
        for lo, hi in eng.chunk_spans(len(prompt)):
            first = eng.prefill_chunk(prompt[lo:hi], (pages, slot), lo)
        rows = [first]
        toks = [int(np.argmax(rows[0]))]
        for j in range(n_new - 1):
            pt = np.tile(eng.idle_row, (eng.max_streams, 1))
            pt[slot] = eng.table_row(pages)
            t_in = np.zeros((eng.max_streams,), np.int64)
            t_in[slot] = toks[-1]
            ctx = np.zeros((eng.max_streams,), np.int32)
            ctx[slot] = len(prompt) + j
            rows.append(eng.step(t_in, pt, ctx)[1][slot])
            toks.append(int(np.argmax(rows[-1])))
        eng.cache.free(pages)
        return np.stack(rows), toks

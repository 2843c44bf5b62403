"""What the dots-vlm1 cell added to the benchmark, on the CPU: the two
copies of the plain reference are one text, ``flops_mla.py`` against hand
counts at toy shapes, every new reader returns None on a run without its
inputs (a parent commit's spans) and the right number on a synthetic one
(a trace of three decode steps and two carried chunks, a ring of
``decode.step`` / ``decode.prefill_chunk`` spans with the share's
arguments), and the cell's files say what the issue named."""
import json
import os
import types

import pytest

from chipbench import flops_mla
from chipbench.metrics import (held_experts_step_roofline, held_steps,
                               mla_decode_roofline, program_op_share)
from chipbench.tests.test_dots_vlm_chip import CONTROLS, compared
from chipbench.tests.test_program_spans import Ring
from chipbench.tests.test_rehearse import BENCH, ROOT
from paddle_tpu.observability import timeline

CELL = 'dots-vlm1_serve_doc64_chunked'
NEW_METRICS = ('mla.step_share', 'mla.chunk_share',
               'kernels.mla_decode_roofline', 'experts.held_step_share',
               'experts.held_chunk_share',
               'kernels.held_experts_step_roofline',
               'experts.held_tokens_per_expert', 'experts.local_hit_share',
               'device.doc64c_idle_share', 'device.doc64c_peak_hbm_gb')
KERNEL = 'latent_paged_attention_live_pages'
# the kernel over the decode rows: all of it in a step, by shape in a chunk
DECODE_ROWS = {'step': KERNEL, 'chunk': KERNEL + r'.*f32\[4,4,16\]'}
EXPERTS = r'\[2,\d+,16\]'
T_OPEN, T_HOST_END, T_A = 50.0, 99.0, 100.0
MS = 1_000_000
TOY = {'kv_lora_rank': 16, 'qk_rope_head_dim': 8, 'num_attention_heads': 4,
       'num_hidden_layers': 3, 'first_k_dense_replace': 1,
       'n_routed_experts': 2, 'num_experts_per_tok': 8,
       'kv_dtype': 'float32',
       'device_programs': {'step': 'jit_step', 'chunk': 'jit_chunk',
                           'prefill': 'jit_prefill'}}


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(ROOT, 'tests', 'reference_dots_vlm.py')) as f, \
            open(os.path.join(ROOT, 'chipbench', 'reference',
                              'dots_vlm.py')) as g:
        assert f.read() == g.read()


@pytest.mark.parametrize('control', sorted(CONTROLS))
def test_the_cells_comparison_sees_the_held_experts(control):
    """The comparison that decides ``correct`` (``kinds/serving.py
    build``), at the rehearsal's toy widths: correct as the cell runs,
    not correct once the reference's held experts are other experts than
    the engine's (the chip test of the same name runs the published
    widths)."""
    from chipbench.reference import dots_vlm as ref
    why, errs = compared(True, CONTROLS[control])
    if CONTROLS[control] is None:
        assert why == [] and max(errs) < 1e-4
    else:
        assert why and min(errs) > 2 * ref.LOGITS_TOL


def toy_params():
    import jax.numpy as jnp
    d, f, e = 32, 8, 2
    shapes = {'gate_w': (e, d, f), 'up_w': (e, d, f), 'down_w': (e, f, d),
              'shared_gate_w': (d, 12), 'shared_up_w': (d, 12),
              'shared_down_w': (12, d), 'router_w': (d, 32),
              'router_bias': (32,)}
    return {'dots_l1_' + n: jnp.zeros(
        s, jnp.float32 if n.startswith('router') else jnp.bfloat16)
        for n, s in shapes.items()}


def test_flops_mla_against_hand_counts():
    # 2 x 4 heads x ((16 + 8) for the score + 16 for the sum) a position
    assert flops_mla.latent_row(TOY) == 24
    assert flops_mla.mla_decode_flops(TOY, 100, 3) == 3 * 100 * 2 * 4 * 40
    assert flops_mla.mla_decode_bytes(TOY, 100, 3, 2) == 3 * 100 * 24 * 2
    # the published widths: 278,528 FLOPs and 1152 bytes a position
    real = {'kv_lora_rank': 512, 'qk_rope_head_dim': 64,
            'num_attention_heads': 128}
    assert flops_mla.mla_decode_flops(real, 1, 1) == 278528
    assert flops_mla.mla_decode_bytes(real, 1, 1, 2) == 1152
    # two routing layers, 1.5 touched held experts, 3 rows, 5 held
    # assignments a layer
    need = flops_mla.held_experts_step_bytes(toy_params(), 1, 2, 1.5, 3, 5)
    assert need == 2 * (1.5 * 3 * 32 * 8 * 2 + 2 * 32 * 12 * 2
                        + 32 * 32 * 4 + 32 * 4
                        + 4 * (2 * 3 * 32 + 2 * 2 * (3 * 12 + 5 * 8)))
    # the same call's FLOPs: router 3 x 32 x 32, 5 assignments x three
    # 32 x 8 products, the shared gate and up 3 x 2 x 32 x 12, twice each
    assert flops_mla.held_experts_step_flops(toy_params(), 1, 2, 3, 5) \
        == 2 * 2 * (3 * 32 * 32 + 5 * 3 * 32 * 8 + 3 * 2 * 32 * 12)


def synthetic_trace():
    """Three ``jit_step`` executions of 10 ms (2 ms in the latent
    kernel, 5 in the held experts) and two ``jit_chunk`` of 20 ms (8 in
    the kernel over the chunk's rows, 2 over the carried rows, 6 in the
    held experts), between two marks."""
    mods, ops, t = [['jit_chipbench_marker(1)', 0, 1000]], [], 1 * MS
    for k in range(3):
        mods.append(['jit_step(7)', t, 10 * MS])
        ops += [['%s.%d:f32[4,4,16]' % (KERNEL, k), t, 2 * MS],
                ['fusion.3:kOutput:f32[2,4,16]', t + 2 * MS, 5 * MS],
                ['fusion.9:kLoop:f32[4,64]', t + 7 * MS, 3 * MS]]
        t += 20 * MS
    for k in range(2):
        mods.append(['jit_chunk(9)', t, 20 * MS])
        ops += [['%s.%d:f32[3,32,16]' % (KERNEL, k), t, 8 * MS],
                ['fusion.4:kOutput:f32[2,20,16]', t + 8 * MS, 6 * MS],
                ['%s.%d:f32[4,4,16]' % (KERNEL, 2 + k), t + 14 * MS, 2 * MS],
                ['fusion.5:kLoop:f32[20,64]', t + 16 * MS, 4 * MS]]
        t += 20 * MS
    mods.append(['jit_chipbench_marker(1)', t, 1000])
    return {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Modules', 'events': mods},
        {'name': 'XLA Ops', 'events': ops}]}]}


@pytest.fixture
def run():
    ring = Ring()
    share = dict(moe_all_assignments=48, moe_held_assignments=4,
                 moe_held_touched=1.5, moe_max_load=2, kv_live_pages=9,
                 kv_table_pages=32, kv_latent_live_positions=100)
    # untraced window: 10 plain steps of 3 rows (3 x 8 x 2 layers = 48
    # assignments, 4 of them on the 2 held experts), 5 carried chunks
    # (their counts include the chunk's 16 rows), a chunk that carried
    # no step, a step from before the share's arguments
    for k in range(10):
        ring.add('decode.step', T_OPEN + k, T_OPEN + k + 0.01, **share)
    for k in range(5):
        ring.add('decode.prefill_chunk', T_OPEN + 20 + k, T_OPEN + 20.02 + k,
                 tokens=16, bucket=16, step_rows=3,
                 **dict(share, moe_all_assignments=304,
                        moe_held_assignments=28))
    ring.add('decode.prefill_chunk', T_OPEN + 30, T_OPEN + 30.02, tokens=16,
             bucket=16, step_rows=0, moe_all_assignments=256,
             moe_held_assignments=16, moe_held_touched=2.0, moe_max_load=9)
    ring.add('decode.step', T_OPEN + 31, T_OPEN + 31.01, kv_live_pages=9)
    # traced seconds: three steps, two chunks that carried one
    for k in range(3):
        ring.add('decode.step', T_A + 0.001 + 0.02 * k,
                 T_A + 0.012 + 0.02 * k, **share)
    for k in range(2):
        ring.add('decode.prefill_chunk', T_A + 0.061 + 0.02 * k,
                 T_A + 0.08 + 0.02 * k, tokens=16, bucket=16, step_rows=3,
                 **dict(share, moe_all_assignments=304,
                        moe_held_assignments=28))
    obs = {'trace': synthetic_trace(), 'marks': (T_A, T_A + 0.101),
           't_open': T_OPEN, 't_host_end': T_HOST_END,
           'params': toy_params(), 'layers': 3, 'slots': 4}
    yield types.SimpleNamespace(
        obs=obs, peaks={'hbm_bytes_per_s': 1e9, 'bf16_flops_per_s': 1e10},
        config=TOY)
    timeline.reset()


def test_the_new_readers_on_a_synthetic_run(run):
    assert program_op_share.read(run, 'step', KERNEL) == pytest.approx(20.0)
    assert program_op_share.read(run, 'chunk', KERNEL) == pytest.approx(50.0)
    assert program_op_share.read(run, 'step', EXPERTS) == pytest.approx(50.0)
    assert program_op_share.read(run, 'chunk', EXPERTS) == pytest.approx(30.0)
    # 4 held assignments a step over 2 held experts x 2 routing layers
    assert held_steps.read(run, 'tokens_per_expert') == pytest.approx(1.0)
    # steps and carried chunks: (10 x 4 + 5 x 28) of (10 x 48 + 5 x 304)
    assert held_steps.read(run, 'local_hit_share') \
        == pytest.approx(100.0 * 180 / 2000)
    # 100 live positions x 3 layers: 7200 bytes x 4 (f32) / 1e9 = 28.8 us
    # by bytes, 96000 FLOPs / 1e10 = 9.6 us by operations; 2 ms of the
    # kernel over the decode rows in each of 3 steps and 2 carried chunks
    assert mla_decode_roofline.read(run, DECODE_ROWS) \
        == pytest.approx(100.0 * (3 * 100 * 24 * 4 / 1e9) / 2e-3)
    # a step: 3 rows, 2 held assignments a layer; a carried chunk: 19
    # rows, 14; each bound by the larger of its bytes and its FLOPs;
    # 5 ms of the experts a step, 6 a chunk
    p = run.obs['params']
    need = 3 * max(
        flops_mla.held_experts_step_bytes(p, 1, 2, 1.5, 3, 2) / 1e9,
        flops_mla.held_experts_step_flops(p, 1, 2, 3, 2) / 1e10) \
        + 2 * max(
        flops_mla.held_experts_step_bytes(p, 1, 2, 1.5, 19, 14) / 1e9,
        flops_mla.held_experts_step_flops(p, 1, 2, 19, 14) / 1e10)
    assert held_experts_step_roofline.read(
        run, {'step': EXPERTS, 'chunk': EXPERTS}) \
        == pytest.approx(100.0 * need / (3 * 5e-3 + 2 * 6e-3))
    # plain steps alone, as a program without carried chunks gives them
    assert mla_decode_roofline.read(run, {'step': KERNEL}) \
        == pytest.approx(100.0 * (3 * 100 * 24 * 4 / 1e9) / 2e-3)


def test_the_new_readers_without_their_inputs():
    """A run without a trace, and a program whose spans lack the share's
    arguments (the parent commit): nothing to read, no error."""
    ring = Ring()
    ring.add('decode.step', T_OPEN + 1, T_OPEN + 1.01, moe_assignments=40,
             moe_touched=14.0, moe_max_load=5, kv_live_pages=3)
    ring.add('decode.step', T_A + 0.001, T_A + 0.012, moe_assignments=40,
             moe_touched=14.0, moe_max_load=5)
    bare = types.SimpleNamespace(
        obs={'t_open': T_OPEN, 't_host_end': T_HOST_END,
             'marks': (T_A, T_A + 1.0)}, peaks=None, config=TOY)
    assert held_steps.read(bare, 'tokens_per_expert') is None
    assert held_steps.read(bare, 'local_hit_share') is None
    both = {'step': EXPERTS, 'chunk': EXPERTS}
    assert mla_decode_roofline.read(bare, DECODE_ROWS) is None
    assert held_experts_step_roofline.read(bare, both) is None
    bare.obs.update(trace=synthetic_trace(), layers=3, params=toy_params())
    bare.peaks = {'hbm_bytes_per_s': 1.0, 'bf16_flops_per_s': 1.0}
    assert mla_decode_roofline.read(bare, DECODE_ROWS) is None
    assert held_experts_step_roofline.read(bare, both) is None
    timeline.reset()


def test_the_cell_is_the_one_named():
    """The traffic the issue fixed, the metric files beside their
    declarations, and the configuration's cuts beside what was
    published."""
    cell = next(w for w in BENCH['workloads'] if w['name'] == CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) \
        == ('dots-vlm1', 'serve_doc64_chunked', 1)
    with open(os.path.join(ROOT, 'chipbench', 'traffic',
                           cell['traffic'] + '.json')) as f:
        t = json.load(f)
    e = {k: v for k, v in t['engine'].items() if k != 'arithmetic'}
    assert e == {'page_size': 16, 'num_pages': 16384, 'max_streams': 64,
                 'max_seq': 4096, 'prefix_cache': False,
                 'prefill_chunk_tokens': 256}
    assert (t['kind'], t['settle_seconds'], t['trace_seconds']) \
        == ('open_loop', 20.0, 3.0)
    assert t['prompt_tokens'] == {'dist': 'log_uniform', 'lo': 512,
                                  'hi': 3072}
    assert t['output_tokens'] == {'dist': 'log_uniform', 'lo': 128,
                                  'hi': 768}
    assert [(c['prompt_tokens'], c['output_tokens']) for c in t['check']] \
        == [(96, 6), (1500, 6)]
    assert 'rate_sweep' in t and t['rate_per_s'] > 0
    declared = {m['name']: m for m in BENCH['per_layer']}
    for name in NEW_METRICS:
        assert declared[name]['workloads'] == [CELL]
        with open(os.path.join(ROOT, 'chipbench', 'metrics',
                               name + '.json')) as f:
            reader = json.load(f)['reader']
        assert os.path.exists(os.path.join(ROOT, 'chipbench', 'metrics',
                                           reader + '.py'))
    entry = next(c for c in BENCH['configs'] if c['name'] == 'dots-vlm1')
    with open(os.path.join(ROOT, entry['file'])) as f:
        c = json.load(f)
    assert c['reduced'] == entry['reduced'] == [
        'num_hidden_layers', 'first_k_dense_replace', 'n_routed_experts',
        'vocab_size']
    assert c['published'] == {'num_hidden_layers': 61,
                              'first_k_dense_replace': 3,
                              'n_routed_experts': 256, 'vocab_size': 129280}
    assert [c[k] for k in c['reduced']] == [6, 1, 16, 16160]
    # every width as published
    assert (c['hidden_size'], c['num_attention_heads'], c['q_lora_rank'],
            c['kv_lora_rank'], c['qk_nope_head_dim'], c['qk_rope_head_dim'],
            c['v_head_dim'], c['intermediate_size'],
            c['moe_intermediate_size'], c['router_width'], c['n_group'],
            c['topk_group'], c['num_experts_per_tok'],
            c['routed_scaling_factor'], c['rope_scaling']['factor']) \
        == (7168, 128, 1536, 512, 128, 64, 128, 18432, 2048, 256, 8, 4, 8,
            2.5, 40)
    assert len(c['departures']) >= 3 and '16 chips' in c['deployment']

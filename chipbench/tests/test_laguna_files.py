"""What the laguna-s-2.1 cell added to the benchmark, on the CPU: the
manifest's rules on what it added (every ``why`` and ``source`` 1-200
printable ASCII characters, names, lists only appended to), the
configuration's cuts and its arithmetic recomputed from its keys, the
two copies of the plain reference one text, ``flops_gqa.py`` against
hand counts, every new reader None on a run without its inputs (a
parent commit's spans) and the right number on a synthetic one, and the
cell's comparison with its four controls at the rehearsal's toy
widths."""
import json
import os
import re
import types

import pytest

from chipbench import flops_gqa
from chipbench.metrics import (gqa_decode_roofline, held_steps,
                               program_op_share, programs_op_share,
                               window_live_share)
from chipbench.tests.test_laguna_chip import CELL, CONTROLS, compared
from chipbench.tests.test_program_spans import Ring
from chipbench.tests.test_rehearse import BENCH, ROOT
from paddle_tpu.observability import timeline

NEW_METRICS = ('attention.full_step_share', 'attention.window_step_share',
               'attention.chunk_share', 'kernels.gqa_decode_roofline',
               'cache.window_live_share', 'device.code32c_idle_share',
               'device.code32c_peak_hbm_gb')
APPENDED_TO = ('loadgen.late_p99_ms', 'server.batch_occupancy',
               'server.ttft_p50_ms', 'server.ttft_p90_ms',
               'decode.step_device_ms', 'decode.prefill_share',
               'server.queue_wait_p90_ms', 'server.tick_self_ms',
               'server.prefill_stall_p95_ms', 'prefill.useful_token_share',
               'decode.step_launch_gap_ms', 'decode.step_return_gap_ms')
OLDER_CELLS = ['opt-1.3b_serve_chat', 'olmoe-1b-7b_serve_chat32_chunked',
               'dots-vlm1_serve_doc64_chunked']
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')


def config():
    entry = next(c for c in BENCH['configs'] if c['name'] == 'laguna-s-2.1')
    with open(os.path.join(ROOT, entry['file'])) as f:
        return entry, json.load(f)


def line(text):
    """1 to 200 printable ASCII characters on one line."""
    return 1 <= len(text) <= 200 and all(32 <= ord(ch) < 127 for ch in text)


def test_the_manifest_rules_on_what_was_added():
    entry, _c = config()
    cell = next(w for w in BENCH['workloads'] if w['name'] == CELL)
    for text in (entry['why'], entry['source'], cell['why']):
        assert line(text), (len(text), text)
    assert set(entry) == {'name', 'source', 'file', 'reduced', 'why'}
    assert set(cell) == {'name', 'config', 'traffic', 'chips', 'why'}
    for name in [entry['name'], cell['name'], cell['config'],
                 cell['traffic']] + entry['reduced'] + list(NEW_METRICS):
        assert NAME.match(name), name
    assert entry['file'].startswith('chipbench/') and cell['chips'] == 1
    # one configuration and one cell, both at the end of their lists
    assert [w['name'] for w in BENCH['workloads']] == OLDER_CELLS + [CELL]
    assert BENCH['configs'][-1] is entry and len(BENCH['configs']) == 4
    declared = {m['name']: m for m in BENCH['per_layer']}
    names = [m['name'] for m in BENCH['per_layer']]
    assert names[-len(NEW_METRICS):] == list(NEW_METRICS)
    assert len(set(names)) == len(names)
    for name in NEW_METRICS:
        m = declared[name]
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
        assert m['workloads'] == [CELL] and m['moves'] == 'itl_p95_ms'
        assert UNIT.match(m['unit']) and m['better'] in ('lower', 'higher')
        assert m['source'] in ('device_trace', 'program_span',
                               'program_counter', 'host_clock')
        assert line(m['layer'])
        with open(os.path.join(ROOT, 'chipbench', 'metrics',
                               name + '.json')) as f:
            reader = json.load(f)['reader']
        assert os.path.exists(os.path.join(ROOT, 'chipbench', 'metrics',
                                           reader + '.py'))
    # the lists the cell joined: appended to, nothing else changed
    for name in APPENDED_TO:
        assert declared[name]['workloads'][-1] == CELL
        assert declared[name]['workloads'][:-1] == [
            w for w in OLDER_CELLS if w in declared[name]['workloads']]
    itl = next(m for m in BENCH['end_to_end'] if m['name'] == 'itl_p95_ms')
    assert itl['workloads'] == OLDER_CELLS + [CELL] and itl['bound'] == 0.07
    assert BENCH['run_seconds'] == 40
    assert os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) < 64 * 1024


def test_the_configuration_is_the_catalogs_cut_as_stated():
    entry, c = config()
    assert c['reduced'] == entry['reduced'] == [
        'num_hidden_layers', 'num_experts', 'vocab_size']
    assert c['published'] == {'num_hidden_layers': 48, 'num_experts': 256,
                              'vocab_size': 100352}
    assert [c[k] for k in c['reduced']] == [9, 8, 12544]
    assert entry['source'] in c['source']
    # every width as published, and the per-layer lists whole
    assert (c['hidden_size'], c['intermediate_size'], c['head_dim'],
            c['num_attention_heads'], c['num_key_value_heads'],
            c['moe_intermediate_size'], c['shared_expert_intermediate_size'],
            c['num_experts_per_tok'], c['sliding_window'],
            c['moe_routed_scaling_factor'], c['router_width'],
            c['rms_norm_eps'], c['max_position_embeddings']) \
        == (3072, 12288, 128, 48, 8, 1024, 1024, 10, 512, 2.5, 256, 1e-6,
            1048576)
    assert len(c['layer_types']) == len(c['mlp_layer_types']) \
        == len(c['num_attention_heads_per_layer']) == 48
    assert c['layer_types'][:9] == ['full_attention'] + [
        'sliding_attention'] * 3 + ['full_attention'] + [
        'sliding_attention'] * 3 + ['full_attention']
    assert c['num_attention_heads_per_layer'][:9] == [
        48, 72, 72, 72, 48, 72, 72, 72, 48]
    assert c['mlp_layer_types'][:9] == ['dense'] + ['sparse'] * 8
    full = c['rope_parameters']['full_attention']
    assert (full['factor'], full['rope_theta'], full['attention_factor'],
            full['partial_rotary_factor'],
            full['original_max_position_embeddings']) \
        == (128, 500000, 1.4852030263919618, 0.5, 8192)
    assert c['rope_parameters']['sliding_attention']['rope_theta'] == 10000
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(x) for x in f if '"Laguna-S-2.1"' in x)
        assert entry['source'] == row['source_url']
        for key, value in row['config'].items():
            assert key in c['reduced'] or c[key] == value, key
    for key in ('router_scores', 'no_qk_norm_no_shared_gate',
                'rotary_pairs', 'gate', 'dtype', 'init'):
        assert len(c['assumed'][key]) > 40
    assert len(c['departures']) >= 3 and '32 chips' in c['deployment']


def test_the_reductions_arithmetic_from_the_keys():
    _entry, c = config()
    with open(os.path.join(ROOT, 'chipbench', 'traffic',
                           'serve_code32_chunked.json')) as f:
        e = json.load(f)['engine']
    d, dh, hkv = c['hidden_size'], c['head_dim'], c['num_key_value_heads']
    held, f, fs = c['num_experts'], c['moe_intermediate_size'], \
        c['shared_expert_intermediate_size']

    def layer(heads, dense):
        attention = 2 * d * heads * dh + 2 * d * hkv * dh + d * heads
        if dense:
            return attention + 3 * d * c['intermediate_size']
        return attention + d * c['router_width'] + 3 * d * fs \
            + held * 3 * d * f
    said = c['reduction']['parameters_M']
    n = c['num_hidden_layers']
    layers = [layer(h, k == 'dense') for h, k in zip(
        c['num_attention_heads_per_layer'][:n], c['mlp_layer_types'][:n])]
    assert round(layers[1] / 1e6, 2) == said['sliding_expert_layer']
    assert round(layers[4] / 1e6, 2) == said['full_expert_layer']
    assert round(layers[0] / 1e6, 2) == said['dense_layer_0']
    ends = 2 * c['vocab_size'] * d
    assert round(ends / 1e6, 2) == said['embedding_and_head']
    assert round((sum(layers) + ends) / 1e6, 1) == said['total']
    assert '2.77 GB' in c['reduction']['bytes'] \
        and round(2 * (sum(layers) + ends) / 1e9, 2) == 2.77
    # the pools: a position caches 4096 B a layer
    row = 2 * hkv * dh * 2
    kinds = c['layer_types'][:n]
    full = kinds.count('full_attention') * (e['num_pages'] + 1) \
        * e['page_size'] * row
    assert e['num_pages'] == e['max_streams'] * e['max_seq'] // e['page_size']
    ring = -(-(c['sliding_window'] - 1 + e['prefill_chunk_tokens'])
             // e['page_size']) + 1
    window = kinds.count('sliding_attention') \
        * (e['max_streams'] * ring + 1) * e['page_size'] * row
    assert ring == 65 and row == 4096
    assert round(full / 1e9, 2) == 6.85 and round(window / 1e9, 2) == 0.82
    for text in ('6.85 GB', '0.82 GB', '10.4 GB'):
        assert text in c['reduction']['bytes']
    assert round((2 * (sum(layers) + ends) + full + window) / 1e9, 1) \
        == 10.4
    # on the full group's table the window layers would not fit
    assert round(kinds.count('sliding_attention') * (e['num_pages'] + 1)
                 * e['page_size'] * row / 1e9, 1) == 13.7


def test_the_cell_is_the_one_named():
    cell = next(w for w in BENCH['workloads'] if w['name'] == CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) \
        == ('laguna-s-2.1', 'serve_code32_chunked', 1)
    with open(os.path.join(ROOT, 'chipbench', 'traffic',
                           cell['traffic'] + '.json')) as f:
        t = json.load(f)
    e = {k: v for k, v in t['engine'].items() if k != 'arithmetic'}
    assert e == {'page_size': 16, 'num_pages': 34816, 'max_streams': 32,
                 'max_seq': 17408, 'prefix_cache': False,
                 'prefill_chunk_tokens': 512}
    assert (t['kind'], t['settle_seconds'], t['trace_seconds']) \
        == ('open_loop', 20.0, 3.0)
    assert t['prompt_tokens'] == {'dist': 'log_uniform', 'lo': 1024,
                                  'hi': 16384}
    assert t['output_tokens'] == {'dist': 'log_uniform', 'lo': 128,
                                  'hi': 768}
    assert [(c['prompt_tokens'], c['output_tokens']) for c in t['check']] \
        == [(96, 6), (6000, 6)]
    assert 'rate_sweep' in t and t['rate_per_s'] > 0
    assert '1.25 tokens' in t['what'] and 'one rank' in t['what']


def test_the_two_copies_of_the_reference_are_one_text():
    """But for the readings of the tolerance, which the benchmark's copy
    carries at the end of its docstring."""
    with open(os.path.join(ROOT, 'tests', 'reference_laguna.py')) as f, \
            open(os.path.join(ROOT, 'chipbench', 'reference',
                              'laguna.py')) as g:
        mine, theirs = f.read(), g.read()
    cut = lambda s: s[:s.index('TOLERANCE.')] + s[s.index('"""\nimport'):]
    assert cut(mine) == cut(theirs)
    assert 'LOGITS_TOL = ' in theirs


@pytest.mark.parametrize('control', sorted(CONTROLS))
def test_the_cells_comparison_sees_what_the_model_is_made_of(control):
    """The comparison that decides ``correct`` (``kinds/serving.py
    build``), at the rehearsal's toy widths: correct as the cell runs,
    not correct once the reference ignores the window, leaves the gate
    out, drops the held experts or reads the next K/V head (the chip
    test of the same name runs the published widths)."""
    from chipbench.reference import laguna as ref
    why, errs = compared(True, CONTROLS[control])
    if CONTROLS[control] is None:
        assert why == [] and max(errs) < 1e-4
    else:
        # (the rehearsal's 5-token request never leaves its window)
        assert why and errs[1] > 2 * ref.LOGITS_TOL


TOY = {'num_hidden_layers': 5, 'head_dim': 16, 'num_key_value_heads': 2,
       'layer_types': ['full_attention'] + ['sliding_attention'] * 3
       + ['full_attention'] * 2,
       'num_attention_heads_per_layer': [4, 6, 6, 6, 4, 4],
       'kv_dtype': 'float32',
       'device_programs': {'step': 'jit_step', 'chunk': 'jit_chunk'}}
KERNEL = 'paged_attention_live_pages'
DECODE_ROWS = {'step': '(^|[^_])' + KERNEL, 'chunk': '(^|[^_])' + KERNEL}
T_OPEN, T_HOST_END, T_A = 50.0, 99.0, 100.0
MS = 1_000_000


def test_flops_gqa_against_hand_counts():
    assert flops_gqa.kinds(TOY) == {'full_attention': (2, 4),
                                    'sliding_attention': (3, 6)}
    assert flops_gqa.kv_row_bytes(TOY, 4) == 2 * 2 * 16 * 4
    # 100 positions on 2 full layers and 30 on 3 window layers
    assert flops_gqa.gqa_decode_bytes(TOY, 100, 30, 4) \
        == 256 * (2 * 100 + 3 * 30)
    assert flops_gqa.gqa_decode_flops(TOY, 100, 30) \
        == 4 * 16 * (2 * 4 * 100 + 3 * 6 * 30)


def synthetic_trace():
    """Three ``jit_step`` executions of 10 ms (2 ms in the kernel on the
    full layers' rows, 1 on the window layers') and two ``jit_chunk`` of
    20 ms (6 in the chunk kernel, 2 + 1 in the step kernel over the
    carried rows), between two marks."""
    mods, ops, t = [['jit_chipbench_marker(1)', 0, 1000]], [], 1 * MS
    for k in range(3):
        mods.append(['jit_step(7)', t, 10 * MS])
        ops += [['%s.%d:f32[4,6,1024]' % (KERNEL, k), t, 2 * MS],
                ['%s.%d:f32[4,9,1024]' % (KERNEL, 9 + k), t + 2 * MS, MS],
                ['fusion.9:kLoop:f32[4,64]', t + 3 * MS, 7 * MS]]
        t += 20 * MS
    for k in range(2):
        mods.append(['jit_chunk(9)', t, 20 * MS])
        ops += [['chunk_%s.%d:f32[2,8,192,128]' % (KERNEL, k), t, 6 * MS],
                ['%s.%d:f32[4,6,1024]' % (KERNEL, 20 + k), t + 6 * MS,
                 2 * MS],
                ['%s.%d:f32[4,9,1024]' % (KERNEL, 30 + k), t + 8 * MS, MS],
                ['fusion.5:kLoop:f32[20,64]', t + 9 * MS, 11 * MS]]
        t += 20 * MS
    mods.append(['jit_chipbench_marker(1)', t, 1000])
    return {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Modules', 'events': mods},
        {'name': 'XLA Ops', 'events': ops}]}]}


@pytest.fixture
def run():
    ring = Ring()
    share = dict(moe_all_assignments=48, moe_held_assignments=4,
                 moe_held_touched=1.0, moe_max_load=2, kv_live_pages=40,
                 kv_table_pages=32, kv_full_live_pages=40,
                 kv_window_live_pages=6, kv_full_live_positions=100,
                 kv_window_live_positions=30)
    for k in range(10):
        ring.add('decode.step', T_OPEN + k, T_OPEN + k + 0.01, **share)
    for k in range(5):
        ring.add('decode.prefill_chunk', T_OPEN + 20 + k, T_OPEN + 20.02 + k,
                 tokens=16, bucket=16, step_rows=3,
                 **dict(share, kv_window_live_pages=10))
    ring.add('decode.prefill_chunk', T_OPEN + 30, T_OPEN + 30.02, tokens=16,
             bucket=16, step_rows=0, moe_all_assignments=256,
             moe_held_assignments=16, moe_held_touched=2.0, moe_max_load=9)
    for k in range(3):
        ring.add('decode.step', T_A + 0.001 + 0.02 * k,
                 T_A + 0.012 + 0.02 * k, **share)
    for k in range(2):
        ring.add('decode.prefill_chunk', T_A + 0.061 + 0.02 * k,
                 T_A + 0.08 + 0.02 * k, tokens=16, bucket=16, step_rows=3,
                 **share)
    obs = {'trace': synthetic_trace(), 'marks': (T_A, T_A + 0.101),
           't_open': T_OPEN, 't_host_end': T_HOST_END, 'layers': 5,
           'slots': 4}
    yield types.SimpleNamespace(
        obs=obs, peaks={'hbm_bytes_per_s': 1e9, 'bf16_flops_per_s': 1e10},
        config=TOY)
    timeline.reset()


def declared(name):
    with open(os.path.join(ROOT, 'chipbench', 'metrics',
                           name + '.json')) as f:
        return json.load(f)['params']


def test_the_new_readers_on_a_synthetic_run(run):
    # the metric files' own patterns: 48- and 72-head calls by their shapes
    # the decode rows' kernel in the programs that run decode rows: 3
    # steps of 10 ms and 2 chunks of 20, 2 ms (full) and 1 (window) each
    assert programs_op_share.read(
        run, **declared('attention.full_step_share')) \
        == pytest.approx(100.0 * 10 / 70)
    assert programs_op_share.read(
        run, **declared('attention.window_step_share')) \
        == pytest.approx(100.0 * 5 / 70)
    # a window of chunks alone still reads
    assert programs_op_share.read(run, ['chunk'], KERNEL + r'.*,6,1024') \
        == pytest.approx(10.0)
    assert program_op_share.read(
        run, **declared('attention.chunk_share')) == pytest.approx(45.0)
    # (10 x 6 + 5 x 10) window pages over 15 x 40
    assert window_live_share.read(run) == pytest.approx(100.0 * 110 / 600)
    # a call: 256 B x (2 x 100 + 3 x 30) = 74240 B -> 74.24 us by bytes,
    # 64 x (800 + 540) FLOPs / 1e10 = 8.6 us by operations; 3 ms of the
    # step kernel in each of 3 steps and 2 carried chunks
    assert gqa_decode_roofline.read(
        run, **declared('kernels.gqa_decode_roofline')) \
        == pytest.approx(100.0 * (74240 / 1e9) / 3e-3)
    assert held_steps.read(run, 'local_hit_share') \
        == pytest.approx(100.0 * 4 / 48)


def test_the_new_readers_without_their_inputs():
    """A run without a trace, and a program whose spans lack the groups'
    counters (the parent commit): nothing to read, no error."""
    ring = Ring()
    ring.add('decode.step', T_OPEN + 1, T_OPEN + 1.01,
             moe_all_assignments=48, moe_held_assignments=4,
             moe_held_touched=1.0, kv_live_pages=3)
    ring.add('decode.step', T_A + 0.001, T_A + 0.012,
             moe_all_assignments=48, moe_held_assignments=4,
             moe_held_touched=1.0, kv_live_pages=3)
    bare = types.SimpleNamespace(
        obs={'t_open': T_OPEN, 't_host_end': T_HOST_END,
             'marks': (T_A, T_A + 1.0)}, peaks=None, config=TOY)
    assert window_live_share.read(bare) is None
    assert gqa_decode_roofline.read(bare, DECODE_ROWS) is None
    assert programs_op_share.read(bare, ['step', 'chunk'], KERNEL) is None
    bare.obs.update(trace=synthetic_trace(), layers=5)
    bare.peaks = {'hbm_bytes_per_s': 1.0, 'bf16_flops_per_s': 1.0}
    assert gqa_decode_roofline.read(bare, DECODE_ROWS) is None
    assert window_live_share.read(bare) is None
    del ring
    timeline.reset()

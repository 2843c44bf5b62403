"""What the ouro-2.6b cell added to the benchmark, on the CPU: the
manifest's rules on what it added (every ``why`` and ``source`` 1-200
printable ASCII characters by ``len``, names, lists only appended to),
the configuration's one cut and its arithmetic recomputed from its keys,
the two copies of the plain reference one text, ``flops_loop.py``
against hand counts, the new reader None on a run without its inputs (a
parent commit's spans) and the right number on a synthetic one, and the
cell's comparison with its four controls at the rehearsal's toy
widths."""
import json
import os
import types

import pytest

from chipbench import flops_loop
from chipbench.metrics import loop_steps, program_op_share
from chipbench.tests.test_laguna_files import NAME, UNIT, declared, line
from chipbench.tests.test_ouro_chip import CELL, CONTROLS, compared
from chipbench.tests.test_program_spans import Ring
from chipbench.tests.test_rehearse import BENCH, ROOT
from paddle_tpu.observability import timeline

NEW_METRICS = ('kernels.loop_step_roofline', 'kernels.loop_decode_roofline',
               'attention.loop_step_share', 'loop.passes_per_weight_layer',
               'loop.exit_mass_last', 'device.reason16_idle_share',
               'device.reason16_peak_hbm_gb')
APPENDED_TO = ('loadgen.late_p99_ms', 'server.batch_occupancy',
               'server.ttft_p50_ms', 'server.ttft_p90_ms',
               'decode.step_device_ms', 'decode.prefill_share',
               'server.queue_wait_p90_ms', 'server.tick_self_ms',
               'server.prefill_stall_p95_ms', 'prefill.useful_token_share',
               'decode.step_launch_gap_ms', 'decode.step_return_gap_ms')
OLDER_CELLS = ['opt-1.3b_serve_chat', 'olmoe-1b-7b_serve_chat32_chunked',
               'dots-vlm1_serve_doc64_chunked',
               'laguna-s-2.1_serve_code32_chunked']


def config():
    entry = next(c for c in BENCH['configs'] if c['name'] == 'ouro-2.6b')
    with open(os.path.join(ROOT, entry['file'])) as f:
        return entry, json.load(f)


def traffic():
    with open(os.path.join(ROOT, 'chipbench', 'traffic',
                           'serve_reason16_chunked.json')) as f:
        return json.load(f)


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
    # the older cells come first and in their order (a later PR may add
    # its own after this one)
    cells = [w['name'] for w in BENCH['workloads']]
    assert cells[:5] == OLDER_CELLS + [CELL]
    assert [c['name'] for c in BENCH['configs']][4] == 'ouro-2.6b'
    declared = {m['name']: m for m in BENCH['per_layer']}
    names = [m['name'] for m in BENCH['per_layer']]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + len(NEW_METRICS)] == list(NEW_METRICS)
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
        assert m['layer'] in {x['layer'] for x in BENCH['per_layer']
                              if x['name'] not in NEW_METRICS}
        with open(os.path.join(ROOT, 'chipbench', 'metrics',
                               name + '.json')) as f:
            reader = json.load(f)['reader']
        assert os.path.exists(os.path.join(ROOT, 'chipbench', 'metrics',
                                           reader + '.py'))
    # the lists the cell joined: appended to, nothing else changed
    for name in APPENDED_TO:
        on = declared[name]['workloads']
        assert on[:on.index(CELL)] == [w for w in OLDER_CELLS if w in on]
    itl = next(m for m in BENCH['end_to_end'] if m['name'] == 'itl_p95_ms')
    assert itl['workloads'][:5] == OLDER_CELLS + [CELL]
    assert itl['bound'] == 0.07 and BENCH['run_seconds'] == 40
    assert os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) < 64 * 1024
    # every why of the benchmark, by len
    for text in [c['why'] for c in BENCH['configs']] \
            + [w['why'] for w in BENCH['workloads']]:
        assert line(text), (len(text), text)


def test_the_configuration_is_the_catalogs_cut_as_stated():
    entry, c = config()
    assert c['reduced'] == entry['reduced'] == ['num_hidden_layers']
    assert c['published'] == {'num_hidden_layers': 48}
    assert c['num_hidden_layers'] == 12 and entry['source'] in c['source']
    assert (c['system'], c['reference']) == ('ouro_serve', 'ouro')
    # every width as published
    assert (c['hidden_size'], c['intermediate_size'], c['head_dim'],
            c['num_attention_heads'], c['num_key_value_heads'],
            c['vocab_size'], c['rms_norm_eps'], c['rope_theta'],
            c['max_position_embeddings'], c['total_ut_steps'],
            c['early_exit_threshold'], c['tie_word_embeddings'],
            c['rope_scaling'], c['hidden_act']) \
        == (2048, 5632, 128, 16, 16, 49152, 1e-6, 1000000, 65536, 4, 1,
            False, None, 'silu')
    assert c['layer_types'] == ['full_attention'] * 48
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(x) for x in f if '"Ouro-2.6B"' in x)
        assert entry['source'] == row['source_url']
        for key, value in row['config'].items():
            assert key in c['reduced'] or c[key] == value, key
    for key in ('sandwich_norms', 'closing_norm', 'no_biases',
                'rotary_pairs', 'exit_gate', 'exit_rule',
                'kv_per_recurrence', 'dtype', 'init'):
        assert len(c['assumed'][key]) > 40
    assert (c['assumed']['init_std'], c['assumed']['embed_init_std'],
            c['assumed']['branch_norm_init']) == (0.02, 1.0, 0.3)
    assert len(c['departures']) >= 3 and 'four pipeline stages' \
        in c['deployment']


def test_the_reductions_arithmetic_from_the_keys():
    _entry, c = config()
    e = traffic()['engine']
    said = c['reduction']['num_hidden_layers']
    d, f, v = c['hidden_size'], c['intermediate_size'], c['vocab_size']
    T, L = c['total_ut_steps'], c['num_hidden_layers']
    assert d == c['num_attention_heads'] * c['head_dim']
    layer = 4 * d * d + 3 * d * f + 4 * d
    ends = 2 * v * d
    total = L * layer + ends + d + d + 1
    assert (layer, ends, total) == (51388416, 201326592, 817991681)
    for text in ('51,388,416', '201,326,592', '817,991,681', '1.636 GB',
                 '2,667,974,657', '5.34 GB'):
        assert text in said, text
    assert round(2 * total / 1e9, 3) == 1.636
    assert 48 * layer + ends + 2 * d + 1 == 2667974657
    # a position: K and V of 2048 lanes in bf16, a slot a layer a
    # recurrence
    slot = 2 * c['num_key_value_heads'] * c['head_dim'] * 2
    assert slot == 8192 and T * L == 48 and T * L * slot == 393216
    assert '393,216 B' in said and '1,572,864 B' in said
    assert e['num_pages'] == e['max_streams'] * e['max_seq'] // e['page_size']
    pools = T * L * (e['num_pages'] + 1) * e['page_size'] * slot
    assert round(pools / 1e9, 3) == 9.67 and '9.670 GB' in said
    assert e['max_streams'] * e['max_seq'] == 24576 and '24,576' in said
    resident = 2 * total + pools
    assert round(resident / 1e9, 3) == 11.306 and '11.306 GB' in said
    assert round(100 * resident / 2 ** 34, 1) == 65.8 and '65.8%' in said
    assert resident > 0.25 * 16e9       # the floor for a new cell
    # what a step must read, by the benchmark's own count
    step = flops_loop.loop_step_bytes(c, 0, 2, 2)
    assert round(step / 1e9, 2) == 5.14 and '5.14 GB' in said
    assert round(step / 819e9 * 1e3, 1) == 6.3
    assert round(flops_loop.loop_decode_bytes(c, T * 6000, 2) / 1e9, 1) \
        == 2.4
    for text in ('9.670 GB', '24,576', '1.636 GB'):
        assert text in e['arithmetic'], text


def test_the_cell_is_the_one_named():
    cell = next(w for w in BENCH['workloads'] if w['name'] == CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) \
        == ('ouro-2.6b', 'serve_reason16_chunked', 1)
    t = traffic()
    e = {k: v for k, v in t['engine'].items() if k != 'arithmetic'}
    assert e == {'page_size': 16, 'num_pages': 1536, 'max_streams': 16,
                 'max_seq': 1536, 'prefix_cache': False,
                 'prefill_chunk_tokens': 256}
    assert (t['kind'], t['settle_seconds'], t['trace_seconds']) \
        == ('open_loop', 20.0, 3.0)
    assert t['prompt_tokens'] == {'dist': 'log_uniform', 'lo': 64,
                                  'hi': 512}
    assert t['output_tokens'] == {'dist': 'log_uniform', 'lo': 256,
                                  'hi': 1024}
    assert [(c['prompt_tokens'], c['output_tokens']) for c in t['check']] \
        == [(96, 6), (448, 6)]
    assert 'python3 -m chipbench.sweep' in t['rate_sweep']
    assert ('%g/s' % t['rate_per_s']) in cell['why']


def test_the_two_copies_of_the_reference_are_one_text():
    """But for the readings of the tolerance, which the benchmark's copy
    carries at the end of its docstring."""
    with open(os.path.join(ROOT, 'tests', 'reference_ouro.py')) as f, \
            open(os.path.join(ROOT, 'chipbench', 'reference',
                              'ouro.py')) as g:
        mine, theirs = f.read(), g.read()
    cut = lambda s: s[:s.index('TOLERANCE.')] + s[s.index('"""\nimport'):]
    assert cut(mine) == cut(theirs)
    assert 'LOGITS_TOL = ' in theirs


@pytest.mark.parametrize('control', sorted(CONTROLS))
def test_the_cells_comparison_sees_what_the_model_is_made_of(control):
    """The comparison that decides ``correct`` (``kinds/serving.py
    build``), at the rehearsal's toy widths: correct as the cell runs,
    not correct with one recurrence, without the norm between
    recurrences, without the norms on a branch's way out, or with every
    recurrence in recurrence 0's slots (the chip test of the same name
    runs the published widths)."""
    from chipbench.reference import ouro as ref
    why, errs = compared(True, CONTROLS[control])
    if CONTROLS[control] is None:
        assert why == [] and max(errs) < 1e-4
    else:
        assert why and min(errs) > 2 * ref.LOGITS_TOL


TOY = {'num_hidden_layers': 3, 'total_ut_steps': 4, 'hidden_size': 64,
       'head_dim': 16, 'num_attention_heads': 4, 'num_key_value_heads': 4,
       'intermediate_size': 96, 'vocab_size': 211, 'dtype': 'float32',
       'kv_dtype': 'float32',
       'device_programs': {'step': 'jit_step', 'chunk': 'jit_chunk'}}
KERNEL = 'paged_attention_live_pages'
T_OPEN, T_HOST_END, T_A = 50.0, 99.0, 100.0
MS = 1_000_000


def test_flops_loop_against_hand_counts():
    layer = (4 * 64 * 64 + 3 * 64 * 96) * 4 + 4 * 64 * 4
    assert flops_loop.layer_weight_bytes(TOY, 4) == layer
    assert flops_loop.head_bytes(TOY, 4) == 64 * 211 * 4
    assert flops_loop.kv_slot_bytes(TOY, 4) == 2 * 64 * 4
    # 4 recurrences x 100 live positions, read once a layer
    assert flops_loop.loop_decode_bytes(TOY, 400, 4) == 400 * 3 * 512
    assert flops_loop.loop_decode_flops(TOY, 400) == 4 * 16 * 4 * 3 * 400
    assert flops_loop.loop_step_bytes(TOY, 400, 4, 4) \
        == 4 * 3 * layer + 64 * 211 * 4 + 400 * 3 * 512


def synthetic_trace():
    """Three ``jit_step`` executions of 10 ms (3 ms in the kernel) and
    two ``jit_chunk`` of 20 ms (2 in the kernel over the carried rows),
    between two marks."""
    mods, ops, t = [['jit_chipbench_marker(1)', 0, 1000]], [], 1 * MS
    for k in range(3):
        mods.append(['jit_step(7)', t, 10 * MS])
        ops += [['%s.%d:f32[4,1,64]' % (KERNEL, k), t, 3 * MS],
                ['fusion.9:kLoop:f32[4,64]', t + 3 * MS, 7 * MS]]
        t += 20 * MS
    for k in range(2):
        mods.append(['jit_chunk(9)', t, 20 * MS])
        ops += [['%s.%d:f32[4,1,64]' % (KERNEL, 20 + k), t, 2 * MS],
                ['fusion.5:kLoop:f32[20,64]', t + 2 * MS, 18 * MS]]
        t += 20 * MS
    mods.append(['jit_chipbench_marker(1)', t, 1000])
    return {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Modules', 'events': mods},
        {'name': 'XLA Ops', 'events': ops}]}]}


@pytest.fixture
def run():
    ring = Ring()
    loop = dict(ut_steps=4, loop_passes=12, kv_loop_live_positions=400,
                loop_exit_mass=[0.5, 0.25, 0.125, 0.125], kv_live_pages=40,
                kv_table_pages=32)
    for k in range(10):
        ring.add('decode.step', T_OPEN + k, T_OPEN + k + 0.01, **loop)
    ring.add('decode.prefill_chunk', T_OPEN + 20, T_OPEN + 20.02,
             tokens=16, bucket=16, step_rows=3,
             **dict(loop, loop_exit_mass=[1.0, 0.0, 0.0, 0.0]))
    for k in range(3):
        ring.add('decode.step', T_A + 0.001 + 0.02 * k,
                 T_A + 0.012 + 0.02 * k, **loop)
    for k in range(2):
        ring.add('decode.prefill_chunk', T_A + 0.061 + 0.02 * k,
                 T_A + 0.08 + 0.02 * k, tokens=16, bucket=16, step_rows=3,
                 **dict(loop, kv_loop_live_positions=200))
    obs = {'trace': synthetic_trace(), 'marks': (T_A, T_A + 0.101),
           't_open': T_OPEN, 't_host_end': T_HOST_END}
    yield types.SimpleNamespace(
        obs=obs, peaks={'hbm_bytes_per_s': 1e9, 'bf16_flops_per_s': 1e10},
        config=TOY)
    timeline.reset()


def test_the_new_readers_on_a_synthetic_run(run):
    # plain steps of the untraced window only: 12 passes over 3 layers
    assert loop_steps.read(
        run, **declared('loop.passes_per_weight_layer')) == 4.0
    assert loop_steps.read(run, **declared('loop.exit_mass_last')) \
        == pytest.approx(12.5)
    # a step's bytes at 1e9 B/s over its 10 ms
    need = flops_loop.loop_step_bytes(TOY, 400, 4, 4)
    assert loop_steps.read(
        run, **declared('kernels.loop_step_roofline')) \
        == pytest.approx(100.0 * need / 1e9 / 10e-3)
    # 3 steps x 400 + 2 carrying chunks x 200 positions, 3 layers x 512 B
    # (bytes bind: 1536 B against 768 FLOPs / 10 a position), over
    # 3 x 3 ms + 2 x 2 ms in the kernel
    assert loop_steps.read(
        run, **declared('kernels.loop_decode_roofline')) \
        == pytest.approx(100.0 * (1600 * 1536 / 1e9) / 13e-3)
    # 3 ms of each 10 ms step in the kernel, whatever encloses it
    assert loop_steps.read(
        run, **declared('attention.loop_step_share')) == pytest.approx(30.0)
    run.obs['trace']['planes'][0]['lines'][1]['events'] += [
        ['while.2:bf16[100,8,64]', (1 + 20 * k) * MS, 9 * MS]
        for k in range(3)]
    assert loop_steps.read(
        run, **declared('attention.loop_step_share')) == pytest.approx(30.0)
    assert program_op_share.read(run, 'step', KERNEL) \
        == pytest.approx(100.0 * 9 / 57)


def test_the_new_readers_without_their_inputs():
    """A run without a trace, and a program whose spans lack the loop's
    counters (the parent commit, another block): nothing to read, no
    error."""
    ring = Ring()
    ring.add('decode.step', T_OPEN + 1, T_OPEN + 1.01, kv_live_pages=3)
    ring.add('decode.step', T_A + 0.001, T_A + 0.012, kv_live_pages=3)
    bare = types.SimpleNamespace(
        obs={'t_open': T_OPEN, 't_host_end': T_HOST_END,
             'marks': (T_A, T_A + 1.0)}, peaks=None, config=TOY)
    params = [declared(n) for n in NEW_METRICS[:5]]
    for p in params:
        assert loop_steps.read(bare, **p) is None
    bare.obs.update(trace=synthetic_trace())
    bare.peaks = {'hbm_bytes_per_s': 1.0, 'bf16_flops_per_s': 1.0}
    for p in params[:2] + params[3:]:
        assert loop_steps.read(bare, **p) is None
    # (a kernel's share of its program reads the trace alone)
    assert loop_steps.read(bare, **params[2]) == pytest.approx(30.0)
    del ring
    timeline.reset()

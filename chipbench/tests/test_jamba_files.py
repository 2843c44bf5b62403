"""What the ai21-jamba2-3b cell added to the benchmark, on the CPU: the
manifest's rules on what it added (every ``why`` and ``source`` 1-200
printable ASCII characters by ``len``, names, lists only appended to),
the configuration's keys as the catalog publishes them and its
arithmetic recomputed from them (3,029 M parameters, the pools' bytes),
the two copies of the plain reference one text, ``flops_ssm.py`` against
hand counts, the new reader None on a run without its inputs (a parent
commit's spans) and the right number on a synthetic one, and the cell's
comparison with its six controls at the rehearsal's toy widths."""
import json
import os
import types

import pytest

from chipbench import flops_ssm
from chipbench.metrics import ssm_steps
from chipbench.tests.test_jamba_chip import CELL, CONTROLS, compared
from chipbench.tests.test_laguna_files import NAME, UNIT, declared, line
from chipbench.tests.test_program_spans import Ring
from chipbench.tests.test_rehearse import BENCH, ROOT
from paddle_tpu.observability import timeline

NEW_METRICS = ('ssm.chunk_share', 'ssm.step_share',
               'kernels.ssm_scan_roofline', 'kernels.ssm_step_roofline',
               'attention.mqa_step_share', 'cache.state_share',
               'device.longdoc64_idle_share', 'device.longdoc64_peak_hbm_gb')
APPENDED_TO = ('loadgen.late_p99_ms',
               'server.ttft_p50_ms', 'server.ttft_p90_ms',
               'decode.prefill_share',
               'server.queue_wait_p90_ms', 'server.tick_self_ms',
               'prefill.useful_token_share')
# readers of PLAIN steps: a tick with a prompt pending is a chunk, and at
# this cell's rate the chunks alone keep the chip busy 81% of the time, so
# 40% of traced stretches of 3 s hold no plain step (the driver's first
# held none) and 2% of untraced windows of 37 s hold none either
# (PERF.md section 7): the cell is on none of these lists
NOT_JOINED = ('decode.step_device_ms', 'decode.step_launch_gap_ms',
              'decode.step_return_gap_ms', 'server.batch_occupancy',
              'server.prefill_stall_p95_ms')
OLDER_CELLS = ['opt-1.3b_serve_chat', 'olmoe-1b-7b_serve_chat32_chunked',
               'dots-vlm1_serve_doc64_chunked',
               'laguna-s-2.1_serve_code32_chunked',
               'ouro-2.6b_serve_reason16_chunked']


def config():
    entry = next(c for c in BENCH['configs'] if c['name'] == 'ai21-jamba2-3b')
    with open(os.path.join(ROOT, entry['file'])) as f:
        return entry, json.load(f)


def traffic():
    with open(os.path.join(ROOT, 'chipbench', 'traffic',
                           'serve_longdoc64_chunked.json')) as f:
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
    assert cells[:6] == OLDER_CELLS + [CELL]
    assert [c['name'] for c in BENCH['configs']][5] == 'ai21-jamba2-3b'
    by_name = {m['name']: m for m in BENCH['per_layer']}
    names = [m['name'] for m in BENCH['per_layer']]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + len(NEW_METRICS)] == list(NEW_METRICS)
    assert len(set(names)) == len(names)
    for name in NEW_METRICS:
        m = by_name[name]
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
    # a share of a roofline is named for its kernel, in percent
    for name in NEW_METRICS:
        if 'roofline' in name:
            assert name.endswith('_roofline') and by_name[name]['unit'] == '%'
    # the lists the cell joined: appended to, nothing else changed
    for name in APPENDED_TO:
        on = by_name[name]['workloads']
        assert on[:on.index(CELL)] == [w for w in OLDER_CELLS if w in on]
    for name in NOT_JOINED:
        assert by_name[name]['workloads'] == OLDER_CELLS
    itl = next(m for m in BENCH['end_to_end'] if m['name'] == 'itl_p95_ms')
    assert itl['workloads'][:6] == OLDER_CELLS + [CELL]
    assert itl['bound'] == 0.07 and BENCH['run_seconds'] == 40
    assert os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) < 64 * 1024
    # every why of the benchmark, by len
    for text in [c['why'] for c in BENCH['configs']] \
            + [w['why'] for w in BENCH['workloads']]:
        assert line(text), (len(text), text)


def test_the_configuration_is_the_catalogs_and_nothing_is_cut():
    entry, c = config()
    assert c['reduced'] == entry['reduced'] == []
    assert c['num_hidden_layers'] == 28 and entry['source'] == c['source']
    assert line(c['source'])
    assert (c['system'], c['reference']) == ('jamba_serve', 'jamba')
    assert (c['hidden_size'], c['intermediate_size'],
            c['num_attention_heads'], c['num_key_value_heads'],
            c['vocab_size'], c['rms_norm_eps'], c['attn_layer_period'],
            c['attn_layer_offset'], c['mamba_d_state'], c['mamba_d_conv'],
            c['mamba_dt_rank'], c['mamba_expand'], c['mamba_conv_bias'],
            c['mamba_proj_bias'], c['num_experts'],
            c['tie_word_embeddings'], c['max_position_embeddings']) \
        == (2560, 8192, 20, 1, 65536, 1e-6, 14, 7, 16, 4, 160, 2, True,
            False, 1, True, 262144)
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(x) for x in f if '"AI21-Jamba2-3B"' in x)
        assert entry['source'] == row['source_url']
        for key, value in row['config'].items():
            assert c[key] == value, key
    assert (c['dtype'], c['kv_dtype'], c['state_dtype']) \
        == ('bfloat16', 'bfloat16', 'float32')
    for key in ('layer_order', 'head_dim', 'no_positions', 'mlp_everywhere',
                'dtype', 'state_dtype', 'layout', 'init'):
        assert len(c['assumed'][key]) > 40
    a = c['assumed']
    assert (a['init_std'], a['embed_init_std'], a['dt_min'], a['dt_max']) \
        == (0.02, 1.0, 1e-3, 1e-1)
    assert len(c['departures']) >= 3 and 'nothing is cut' in c['deployment']
    # the order of the layer types as JambaConfig computes it
    from paddle_tpu.models.jamba import layer_kinds, state_runs
    kinds = layer_kinds(28, c['attn_layer_period'], c['attn_layer_offset'])
    assert [i for i, k in enumerate(kinds) if k == 'full'] == [7, 21]
    assert state_runs(kinds) == [(0, 7), (8, 13), (22, 6)]


def test_the_arithmetic_from_the_keys():
    _entry, c = config()
    e, said = traffic()['engine'], c['arithmetic']
    d, f, v = c['hidden_size'], c['intermediate_size'], c['vocab_size']
    dc, n = c['mamba_expand'] * d, c['mamba_d_state']
    k, r = c['mamba_d_conv'], c['mamba_dt_rank']
    heads, dh = c['num_attention_heads'], d // c['num_attention_heads']
    mixer = d * 2 * dc + (dc * k + dc) + dc * (r + 2 * n) + (r + 2 * n) \
        + (r * dc + dc) + dc * n + dc + dc * d
    mlp = 3 * d * f
    attention = 2 * d * heads * dh + 2 * d * c['num_key_value_heads'] * dh
    assert (mixer, mlp, attention) == (41241792, 62914560, 13762560)
    mamba_layer, attention_layer = mixer + mlp + 2 * d, attention + mlp + 2 * d
    layers = flops_ssm.state_layers(c)
    assert layers == 26
    total = layers * mamba_layer + 2 * attention_layer + v * d + d
    assert total == said['parameters'] == 3029337472
    assert round(2 * total / 1e9, 2) == 6.06
    for text, where in (('41,241,792', 'mamba_mixer'), ('62,914,560', 'mlp'),
                        ('13,762,560', 'attention_mixer'),
                        ('104,161,472', 'layers'), ('76,682,240', 'layers'),
                        ('3,029,337,472', 'model'), ('6.06 GB', 'model')):
        assert text in said[where], text
    # the state a stream holds, and the pools
    state = (n + k - 1) * dc * 4
    assert state == said['state_bytes_per_stream_layer'] == 389120
    assert flops_ssm.stream_state_bytes(c) == layers * state == 10117120
    pool = (e['max_streams'] + 1) * layers * state
    assert pool == 657612800 and '657,612,800' in said['state']
    assert flops_ssm.kv_position_bytes(c) == 1024
    assert e['num_pages'] == e['max_streams'] * e['max_seq'] // e['page_size']
    pages = (e['num_pages'] + 1) * e['page_size'] \
        * flops_ssm.kv_position_bytes(c)
    assert pages == 1140981760 and '1,140,981,760' in said['kv']
    resident = 2 * total + pages + pool
    assert round(resident / 1e9, 2) == 7.86 and '7.86 GB' in said['resident']
    assert round(100 * resident / 2 ** 34, 1) == 45.7 \
        and '45.7%' in said['resident']
    assert resident > 0.25 * 16e9       # the floor for a new cell
    for text in ('1.141 GB', '0.658 GB', '6.06 GB', '45.7%'):
        assert text in e['arithmetic'], text


def test_the_cell_is_the_one_named():
    cell = next(w for w in BENCH['workloads'] if w['name'] == CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) \
        == ('ai21-jamba2-3b', 'serve_longdoc64_chunked', 1)
    t = traffic()
    e = {k: v for k, v in t['engine'].items() if k != 'arithmetic'}
    assert e == {'page_size': 128, 'num_pages': 8704, 'max_streams': 64,
                 'max_seq': 17408, 'prefix_cache': False,
                 'prefill_chunk_tokens': 512}
    assert (t['kind'], t['settle_seconds'], t['trace_seconds']) \
        == ('open_loop', 20.0, 3.0)
    assert t['prompt_tokens'] == {'dist': 'log_uniform', 'lo': 1024,
                                  'hi': 16384}
    assert t['output_tokens'] == {'dist': 'log_uniform', 'lo': 128,
                                  'hi': 768}
    assert [(c['prompt_tokens'], c['output_tokens']) for c in t['check']] \
        == [(1300, 6), (300, 6), (1030, 6), (40, 6)]
    assert 'python3 -m chipbench.sweep' in t['rate_sweep']
    assert ('%g/s' % t['rate_per_s']) in cell['why']
    # the check requests fit what the reference is run at
    from chipbench.systems import jamba_serve
    assert max(jamba_serve.buckets_for(128, [300, 1300, 16384])) \
        == jamba_serve.REFERENCE_PAD == 2048 >= 1306


def test_the_two_copies_of_the_reference_are_one_text():
    """But for the readings of the tolerance, which the benchmark's copy
    carries at the end of its docstring."""
    with open(os.path.join(ROOT, 'tests', 'reference_jamba.py')) as f, \
            open(os.path.join(ROOT, 'chipbench', 'reference',
                              'jamba.py')) as g:
        mine, theirs = f.read(), g.read()
    cut = lambda s: s[:s.index('TOLERANCE.')] + s[s.index('"""\nimport'):]
    assert cut(mine) == cut(theirs)
    assert 'LOGITS_TOL = ' in theirs and "'highest'" in theirs


@pytest.mark.parametrize('control', sorted(CONTROLS))
def test_the_cells_comparison_sees_what_the_model_is_made_of(control):
    """The comparison that decides ``correct`` (``kinds/serving.py
    build``), at the rehearsal's toy widths: correct as the cell runs,
    not correct with any one of the six things wrong (the chip test of
    the same name runs the published widths)."""
    from chipbench.reference import jamba as ref
    why, errs = compared(True, CONTROLS[control])
    if CONTROLS[control] is None:
        assert why == [] and max(errs) < 1e-4
    else:
        assert why and max(errs) > 2 * ref.LOGITS_TOL


def test_a_mamba_branch_is_a_visible_share_of_the_stream():
    """As seeded, at the rehearsal's widths: the RMS of what a Mamba
    mixer adds over the RMS of the stream it joins, layer 0 (the
    configuration's ``assumed.init`` gives the chip's)."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as fluid
    from chipbench.reference import jamba as ref
    from chipbench.systems.jamba_serve import seeded_params, spec_of
    _entry, c = config()
    c = dict(c, **c['rehearse'])
    p = seeded_params(c, 7, fluid.CPUPlace())
    spec = spec_of(c)
    x = p['jamba_embed'][jnp.asarray(
        np.random.default_rng(0).integers(1, c['vocab_size'], 48))]
    w = ref.layer_weights(p, 0, spec)
    y = ref.mamba_mixer(w, ref._rms(x, w['in_norm_w']), spec)
    share = float(jnp.sqrt(jnp.mean(y * y)) / jnp.sqrt(jnp.mean(x * x)))
    assert 0.05 < share < 2.0


TOY = {'num_hidden_layers': 6, 'attn_layer_period': 4, 'attn_layer_offset': 2,
       'hidden_size': 64, 'num_attention_heads': 4, 'num_key_value_heads': 1,
       'mamba_expand': 2, 'mamba_d_state': 8, 'mamba_d_conv': 4,
       'state_dtype': 'float32', 'kv_dtype': 'float32',
       'device_programs': {'step': 'jit_step', 'chunk': 'jit_chunk'}}
SCAN = 'selective_scan'
ROWS = 'add_dynamic-update-slice_fusion.7:kLoop:f32[13,65,16,5120]'
T_OPEN, T_HOST_END, T_A = 50.0, 99.0, 100.0
MS = 1_000_000


def test_flops_ssm_against_hand_counts():
    assert flops_ssm.state_layers(TOY) == 5 and flops_ssm.d_inner(TOY) == 128
    assert flops_ssm.state_bytes(TOY) == 8 * 128 * 4
    assert flops_ssm.token_bytes(TOY) == 4 * (3 * 128 + 16)
    # 2 chunks holding 21 valid tokens: the state in and out a chunk
    assert flops_ssm.scan_bytes(TOY, 2, 21) \
        == 5 * (2 * 2 * 4096 + 21 * 1600)
    assert flops_ssm.step_bytes(TOY, 3) == 5 * 3 * (2 * 4096 + 1600)
    assert flops_ssm.stream_state_bytes(TOY) == 5 * 128 * 4 * 11
    assert flops_ssm.kv_position_bytes(TOY) == 1 * 2 * 16 * 4


def synthetic_trace():
    """Three ``jit_step`` executions of 10 ms (2 ms in the rows' state
    update) and two ``jit_chunk`` of 20 ms (4 in the scan, 1 in the
    carried rows' update, all inside a ``while``), between two marks."""
    mods, ops, t = [['jit_chipbench_marker(1)', 0, 1000]], [], 1 * MS
    for k in range(3):
        mods.append(['jit_step(7)', t, 10 * MS])
        ops += [['while.3:f32[64,2560]', t, 10 * MS],
                [ROWS, t, 2 * MS],
                ['fusion.9:kLoop:f32[64,2560]', t + 2 * MS, 8 * MS]]
        t += 20 * MS
    for k in range(2):
        mods.append(['jit_chunk(9)', t, 20 * MS])
        ops += [['while.5:f32[576,2560]', t, 20 * MS],
                ['%s.%d:kCustom:f32[13,65,16,5120]' % (SCAN, k), t, 4 * MS],
                [ROWS, t + 4 * MS, 1 * MS],
                ['fusion.5:kLoop:f32[576,2560]', t + 5 * MS, 15 * MS]]
        t += 20 * MS
    mods.append(['jit_chipbench_marker(1)', t, 1000])
    return {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Modules', 'events': mods},
        {'name': 'XLA Ops', 'events': ops}]}]}


@pytest.fixture
def run():
    ring = Ring()
    per_stream = flops_ssm.stream_state_bytes(TOY)
    rows = dict(ssm_live_slots=3, ssm_state_bytes=3 * per_stream,
                kv_live_pages=40, kv_table_pages=32)
    for k in range(10):
        ring.add('decode.step', T_OPEN + k, T_OPEN + k + 0.01, **rows)
    ring.add('decode.prefill_chunk', T_OPEN + 20, T_OPEN + 20.02,
             tokens=16, bucket=16, step_rows=0, ssm_scan_tokens=16,
             ssm_from_zero=True)
    for k in range(3):
        ring.add('decode.step', T_A + 0.001 + 0.02 * k,
                 T_A + 0.012 + 0.02 * k, **rows)
    for k in range(2):
        ring.add('decode.prefill_chunk', T_A + 0.061 + 0.02 * k,
                 T_A + 0.08 + 0.02 * k, tokens=16 - 11 * k, bucket=16,
                 step_rows=2, ssm_scan_tokens=16 - 11 * k,
                 ssm_from_zero=not k, **dict(rows, ssm_live_slots=2))
    obs = {'trace': synthetic_trace(), 'marks': (T_A, T_A + 0.101),
           't_open': T_OPEN, 't_host_end': T_HOST_END}
    yield types.SimpleNamespace(
        obs=obs, peaks={'hbm_bytes_per_s': 1e9, 'bf16_flops_per_s': 1e10},
        config=TOY, traffic={'engine': {'page_size': 8}})
    timeline.reset()


def test_the_new_readers_on_a_synthetic_run(run):
    read = lambda name: ssm_steps.read(run, **declared(name))
    # 4 ms of each 20 ms chunk in the scan, whatever encloses it
    assert read('ssm.chunk_share') == pytest.approx(20.0)
    # 3 x 2 ms of 3 steps and 2 x 1 ms of 2 chunks, over 30 + 40 ms
    assert read('ssm.step_share') == pytest.approx(100.0 * 8 / 70)
    # 2 chunks of 16 + 5 valid tokens at 1e9 B/s over 8 ms in the kernel
    assert read('kernels.ssm_scan_roofline') == pytest.approx(
        100.0 * flops_ssm.scan_bytes(TOY, 2, 21) / 1e9 / 8e-3)
    # 3 steps x 3 rows + 2 chunks x 2 rows over 6 + 2 ms
    assert read('kernels.ssm_step_roofline') == pytest.approx(
        100.0 * flops_ssm.step_bytes(TOY, 13) / 1e9 / 8e-3)
    # the untraced window's 10 steps: 3 streams' states against 40 pages
    state, kv = 3 * flops_ssm.stream_state_bytes(TOY), 40 * 8 * 128
    assert read('cache.state_share') == pytest.approx(
        100.0 * state / (state + kv))
    assert read('attention.mqa_step_share') == 0.0      # no such op here


def test_the_new_readers_without_their_inputs():
    """A run without a trace, and a program whose spans lack the state
    layers' counters (the parent commit, another block): nothing to
    read, no error."""
    ring = Ring()
    ring.add('decode.step', T_OPEN + 1, T_OPEN + 1.01, kv_live_pages=3)
    ring.add('decode.step', T_A + 0.001, T_A + 0.012, kv_live_pages=3)
    bare = types.SimpleNamespace(
        obs={'t_open': T_OPEN, 't_host_end': T_HOST_END,
             'marks': (T_A, T_A + 1.0)}, peaks=None, config=TOY,
        traffic={'engine': {'page_size': 8}})
    names = NEW_METRICS[:6]
    for name in names:
        assert ssm_steps.read(bare, **declared(name)) is None
    bare.obs.update(trace=synthetic_trace())
    bare.peaks = {'hbm_bytes_per_s': 1.0, 'bf16_flops_per_s': 1.0}
    for name in ('kernels.ssm_scan_roofline', 'kernels.ssm_step_roofline',
                 'cache.state_share'):
        assert ssm_steps.read(bare, **declared(name)) is None
    # (a kernel's share of its program reads the trace alone)
    assert ssm_steps.read(bare, **declared('ssm.chunk_share')) \
        == pytest.approx(20.0)
    del ring
    timeline.reset()

"""What the OLMoE cell added to the benchmark, on the CPU: the two
copies of the plain reference agree, every new reader returns None on a
run without its inputs and the right number on a synthetic one (a trace
of three decode steps, a prefill and two chunks, a ring of
``decode.step`` spans with the routing arguments), the cell's files say
what the issue named, and the cell rehearses through the chunked path
within the reference's bar."""
import json
import os
import sys
import types

import numpy as np
import pytest

from chipbench import flops_moe, program_spans as ps
from chipbench.metrics import (expert_load, experts_step_roofline,
                               program_op_share)
from chipbench.reference import olmoe as bench_reference
from chipbench.tests.test_program_spans import Ring
from chipbench.tests.test_rehearse import BENCH, ROOT, rehearse, tagged
from paddle_tpu.observability import timeline

PATTERN = 'ragged-dot|sort'
CELL = 'olmoe-1b-7b_serve_chat32_chunked'
NEW_METRICS = ('experts.step_share', 'experts.chunk_share',
               'experts.touched_share', 'experts.max_over_mean_load',
               'kernels.experts_step_roofline',
               'device.chat32c_idle_share', 'device.chat32c_peak_hbm_gb')
T_OPEN, T_HOST_END, T_A = 50.0, 99.0, 100.0
MS = 1_000_000


def toy_params(dtype=np.float32):
    import jax.numpy as jnp
    from paddle_tpu.models.olmoe import param_names
    rng, d, e, f, v = np.random.default_rng(0), 32, 16, 8, 53
    shapes = {'q_w': (d, d), 'k_w': (d, d), 'v_w': (d, d), 'o_w': (d, d),
              'router_w': (d, e), 'gate_w': (e, d, f), 'up_w': (e, d, f),
              'down_w': (e, f, d), 'olmoe_embed': (v, d),
              'olmoe_head_w': (d, v)}
    return {n: jnp.asarray(rng.normal(size=shapes.get(
        n if n in shapes else n.split('_', 2)[2], (d,))) * 0.2, dtype)
        for n in param_names(2)}


def test_the_two_copies_of_the_reference_agree():
    import jax.numpy as jnp
    sys.path.insert(0, os.path.join(ROOT, 'tests'))
    import reference_olmoe as repo_reference
    p, toks = toy_params(), jnp.arange(3, 20)
    a = bench_reference.logits(p, toks, n_layers=2, n_heads=4)
    b = repo_reference.logits(p, toks, n_layers=2, n_heads=4)
    assert a.shape == (17, 53) and np.array_equal(np.asarray(a),
                                                  np.asarray(b))
    assert bench_reference.LOGITS_TOL == repo_reference.LOGITS_TOL


def synthetic_trace():
    """Three ``jit_step`` executions of 10 ms (6 ms of them in the
    expert layers' operations) and one ``jit_prefill`` of 4 ms (1 ms),
    between two marks."""
    mods, ops, t = [['jit_chipbench_marker(1)', 0, 1000]], [], 1 * MS
    for k in range(3):
        mods.append(['jit_step(7)', t, 10 * MS])
        ops += [['ragged-dot-none.%d:f32[256,1024]' % k, t, 5 * MS],
                ['sort.3:s32[256]', t + 5 * MS, 1 * MS],
                ['fusion.9:kLoop:f32[32,2048]', t + 6 * MS, 4 * MS]]
        t += 20 * MS
    mods.append(['jit_prefill(8)', t, 4 * MS])
    ops += [['ragged-dot-none.1:f32[4096,1024]', t, 1 * MS],
            ['fusion.2:kOutput:f32[512,2048]', t + 1 * MS, 3 * MS]]
    for k in range(2):      # two chunks of 10 ms, 9 of them in the experts
        t += 10 * MS
        mods.append(['jit_chunk(9)', t, 10 * MS])
        ops += [['ragged-dot-none.%d:f32[1024,1024]' % k, t, 9 * MS],
                ['fusion.4:kLoop:f32[128,2048]', t + 9 * MS, 1 * MS]]
    mods.append(['jit_chipbench_marker(1)', t + 10 * MS, 1000])
    return {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Modules', 'events': mods},
        {'name': 'XLA Ops', 'events': ops}]}]}


@pytest.fixture
def run():
    ring = Ring()
    # untraced window: steps that touched 14 of 16 experts a layer, 40
    # assignments over 2 layers x 16 experts, at most 5 on one expert
    for k in range(10):
        ring.add('decode.step', T_OPEN + k, T_OPEN + k + 0.01,
                 moe_assignments=40, moe_touched=14.0, moe_max_load=5)
    ring.add('decode.step', T_OPEN + 20, T_OPEN + 20.01)   # routed none
    # traced seconds: three steps that touched 12
    for k in range(3):
        ring.add('decode.step', T_A + 0.001 + 0.02 * k,
                 T_A + 0.012 + 0.02 * k, moe_assignments=32,
                 moe_touched=12.0, moe_max_load=4)
    obs = {'trace': synthetic_trace(), 'marks': (T_A, T_A + 0.075),
           't_open': T_OPEN, 't_host_end': T_HOST_END,
           'params': toy_params(), 'layers': 2, 'slots': 4}
    yield types.SimpleNamespace(
        obs=obs, peaks={'hbm_bytes_per_s': 1e9},
        config={'device_programs': {'step': 'jit_step',
                                    'prefill': 'jit_prefill',
                                    'chunk': 'jit_chunk'},
                'num_experts': 16, 'num_hidden_layers': 2,
                'num_experts_per_tok': 8})
    timeline.reset()


def test_the_new_readers_on_a_synthetic_run(run, capsys):
    assert program_op_share.read(run, 'step', PATTERN) \
        == pytest.approx(60.0)
    assert program_op_share.read(run, 'prefill', PATTERN) \
        == pytest.approx(25.0)
    assert program_op_share.read(run, 'chunk', PATTERN) \
        == pytest.approx(90.0)
    assert expert_load.read(run, 'touched_share') \
        == pytest.approx(100 * 14 / 16.0)
    assert expert_load.read(run, 'max_over_mean') \
        == pytest.approx(5 * 32 / 40.0)
    # 12 touched experts a layer, 32 / (8 x 2) = 2 running rows, 6 ms a step
    need = flops_moe.experts_step_bytes(run.obs['params'], 2, 12.0, 2, 8)
    assert need == 2 * (12 * 3 * 32 * 8 * 4 + 32 * 16 * 4
                        + 4 * (2 * 2 * 32 + 2 * 2 * 2 * 8 * 8))
    assert experts_step_roofline.read(run, PATTERN) \
        == pytest.approx(100 * need / 1e9 / 6e-3)
    whole = flops_moe.moe_decode_step_bytes(
        run.obs['params'], 2, 12.0, 2, 8, cached_tokens=60,
        kv_bytes_per_token=2 * 2 * 32 * 4)
    total = sum(int(v.size) * 4 for v in run.obs['params'].values())
    experts = 2 * (3 * 16 * 32 * 8 + 32 * 16) * 4
    assert whole == need + total - experts - 53 * 32 * 4 + 2 * 32 * 4 \
        + 60 * 2 * 2 * 32 * 4


def test_the_new_readers_without_their_inputs():
    """A run without a trace, and a program without the routing
    arguments (the parent commit): nothing to read, no error."""
    Ring().add('decode.step', T_OPEN + 1, T_OPEN + 1.01)
    bare = types.SimpleNamespace(
        obs={'t_open': T_OPEN, 't_host_end': T_HOST_END,
             'marks': (T_A, T_A + 1.0)}, peaks=None,
        config={'device_programs': {'step': 'jit_step',
                                    'prefill': 'jit_prefill'},
                'num_experts': 16, 'num_hidden_layers': 2,
                'num_experts_per_tok': 8})
    assert program_op_share.read(bare, 'step', PATTERN) is None
    assert expert_load.read(bare, 'touched_share') is None
    assert expert_load.read(bare, 'max_over_mean') is None
    assert experts_step_roofline.read(bare, PATTERN) is None
    # with a trace but no routing arguments on the ring
    bare.obs['trace'], bare.peaks = synthetic_trace(), {'hbm_bytes_per_s': 1}
    bare.obs['layers'] = 2
    assert experts_step_roofline.read(bare, PATTERN) is None
    # and a trace in which the program did not run
    assert program_op_share.read(bare, 'prefill', 'no-such-op') == 0.0
    bare.config['device_programs']['pack'] = 'jit_pack'
    assert program_op_share.read(bare, 'pack', PATTERN) is None
    timeline.reset()


def test_the_cell_is_the_one_named():
    """The traffic the issue fixed, the metric files beside their
    declarations, and the configuration's one cut."""
    cell = next(w for w in BENCH['workloads'] if w['name'] == CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) \
        == ('olmoe-1b-7b', 'serve_chat32_chunked', 1)
    with open(os.path.join(ROOT, 'chipbench', 'traffic',
                           cell['traffic'] + '.json')) as f:
        t = json.load(f)
    e = {k: v for k, v in t['engine'].items() if k != 'arithmetic'}
    assert e == {'page_size': 16, 'num_pages': 2048, 'max_streams': 32,
                 'max_seq': 1024, 'prefix_cache': False,
                 'prefill_chunk_tokens': 128}
    assert (t['kind'], t['settle_seconds'], t['trace_seconds']) \
        == ('open_loop', 15.0, 3.0)
    assert t['prompt_tokens'] == {'dist': 'log_uniform', 'lo': 32, 'hi': 512}
    assert t['output_tokens'] == {'dist': 'log_uniform', 'lo': 64, 'hi': 512}
    assert t['rehearse']['engine']['prefill_chunk_tokens'] > 0
    declared = {m['name']: m for m in BENCH['per_layer']}
    for name in NEW_METRICS:
        assert declared[name]['workloads'] == [CELL]
        with open(os.path.join(ROOT, 'chipbench', 'metrics',
                               name + '.json')) as f:
            reader = json.load(f)['reader']
        assert os.path.exists(os.path.join(ROOT, 'chipbench', 'metrics',
                                           reader + '.py'))
    with open(os.path.join(ROOT, 'chipbench', 'configs',
                           'olmoe-1b-7b.json')) as f:
        c = json.load(f)
    assert c['reduced'] == ['num_hidden_layers']
    assert (c['num_hidden_layers'], c['hidden_size'], c['num_experts'],
            c['num_experts_per_tok'], c['intermediate_size'],
            c['vocab_size'], c['norm_topk_prob']) \
        == (8, 2048, 64, 8, 1024, 50304, False)


def test_the_cell_rehearses_through_the_chunked_path():
    """Toy widths, the traffic file's ``rehearse`` block: the check and
    every prompt of the window go through ``prefill_chunk``, and the
    system's logits meet the plain reference."""
    res, earlier = rehearse(ROOT, CELL, trace=1)
    assert res['correct'] is True and res['failed'] == 0, earlier
    ref = tagged(earlier, 'REFERENCE')
    assert ref['tol'] == bench_reference.LOGITS_TOL
    assert max(ref['logits_rel_err']) < 1e-4     # f32 on both sides here
    window = tagged(earlier, 'PROGRAM_SPANS')['window']
    assert window['decode.prefill_chunk']['n'] > 0
    assert 'decode.prefill_into' not in window
    assert tagged(earlier, 'WINDOW')['prefill_chunks'] \
        >= window['decode.prefill_chunk']['n']

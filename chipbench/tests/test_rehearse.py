"""On the CPU at toy widths: every cell runs end to end under
``--rehearse`` and prints no device metric; the plain references agree
with the system; a made-up cell is added by new files alone."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

with open(os.path.join(ROOT, 'BENCHMARK.json')) as _f:
    BENCH = json.load(_f)


def rehearse(root, workload, trace=0, seconds=4):
    p = subprocess.run(
        [sys.executable, '-m', 'chipbench.run', '--workload', workload,
         '--seed', '3000000019', '--seconds', str(seconds), '--trace',
         str(trace), '--rehearse'],
        cwd=root, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines[:-1]


with open(os.path.join(ROOT, 'chipbench', 'pending.json')) as _f:
    PENDING = json.load(_f)


def copy_with_pending(root):
    """A copy of the benchmark in ``root`` whose BENCHMARK.json also has
    the entries of pending.json: the cells whose files are in place but
    which are not cells yet (PERF.md, Open questions)."""
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, 'chipbench'),
                    os.path.join(root, 'chipbench'),
                    ignore=shutil.ignore_patterns('__pycache__'))
    os.symlink(os.path.join(ROOT, 'paddle_tpu'),
               os.path.join(root, 'paddle_tpu'))
    os.symlink(os.path.join(ROOT, 'native'), os.path.join(root, 'native'))
    bench = json.loads(json.dumps(BENCH))
    for section in ('configs', 'workloads', 'end_to_end', 'per_layer'):
        bench[section] += PENDING[section]
    return bench


@pytest.fixture(scope='module')
def pending_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp('pending') / 'copy')
    bench = copy_with_pending(root)
    with open(os.path.join(root, 'BENCHMARK.json'), 'w') as f:
        json.dump(bench, f)
    return root


def tagged(earlier, tag):
    return json.loads(next(line for line in earlier
                           if line.startswith(tag + ' ')).split(' ', 1)[1])


@pytest.mark.parametrize('cell', [w['name'] for w in BENCH['workloads']
                                  + PENDING['workloads']])
@pytest.mark.parametrize('trace', [0, 1])
def test_every_cell_rehearses(cell, trace, pending_root):
    here = any(w['name'] == cell for w in BENCH['workloads'])
    res, earlier = rehearse(ROOT if here else pending_root, cell, trace)
    assert res['correct'] is True, earlier
    assert res['rehearsal'] is True and res['metrics'] == {}
    assert res['device']['platform'] == 'cpu'
    assert res['attempted'] > 0 and res['failed'] == 0
    tags = {line.split(' ', 1)[0] for line in earlier}
    assert {'REFERENCE', 'FIFTHS', 'SETUP_PHASES', 'COMPILES'} <= tags
    over = 'WINDOW' in tags and tagged(earlier, 'WINDOW')['late_over_limit']
    if over:    # an open loop reports what its schedule was judged by
        assert 0 <= over['count'] <= res['attempted']
        assert over['limit_ms'] > 0
    ref = tagged(earlier, 'REFERENCE')
    # true f32 on both sides here: far inside the chip's tolerance
    if 'logits_rel_err' in ref:
        assert max(ref['logits_rel_err']) < 1e-4
    else:
        assert ref['rel_diff'] < 1e-4


def test_no_tpu_no_result():
    p = subprocess.run(
        [sys.executable, '-m', 'chipbench.run', '--workload',
         BENCH['workloads'][0]['name'], '--seed', '1', '--seconds', '1',
         '--trace', '0'], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS='cpu'),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith('{')
    assert 'needs 1 TPU chip' in p.stderr


def test_a_cell_is_added_by_new_files(tmp_path):
    """README.md's made-up cell: a configuration, a traffic mix, a
    per-layer metric with a reader of its own, and one workloads entry,
    in a copy of the benchmark; no file that was there is edited."""
    root = str(tmp_path / 'copy')
    bench = copy_with_pending(root)
    cb = os.path.join(root, 'chipbench')
    with open(os.path.join(cb, 'configs', 'opt-1.3b.json')) as f:
        cfg = json.load(f)
    cfg.update(name='opt-tiny', num_hidden_layers=3)
    with open(os.path.join(cb, 'configs', 'opt-tiny.json'), 'w') as f:
        json.dump(cfg, f)
    with open(os.path.join(cb, 'traffic', 'serve_chat.json')) as f:
        traffic = json.load(f)
    traffic['rehearse']['rate_per_s'] = 15.0
    # another deployment of the engine, by data alone: prefix cache and
    # chunked prefill on (the server then calls ``prefill_chunk``)
    traffic['rehearse']['engine'].update(prefix_cache=True,
                                         prefill_chunk_tokens=16)
    with open(os.path.join(cb, 'traffic', 'serve_rush.json'), 'w') as f:
        json.dump(traffic, f)
    with open(os.path.join(cb, 'metrics', 'server.steps_per_request.py'
                           .replace('server.', 'server_')), 'w') as f:
        f.write('def read(run):\n'
                '    done = run.obs.get("done")\n'
                '    return len(run.obs["steps"]) / len(done) '
                'if done else None\n')
    with open(os.path.join(cb, 'metrics',
                           'server.steps_per_request.json'), 'w') as f:
        json.dump({'reader': 'server_steps_per_request'}, f)
    bench['configs'].append({
        'name': 'opt-tiny', 'source': 'made up', 'reduced': [],
        'file': 'chipbench/configs/opt-tiny.json', 'why': 'a test'})
    bench['workloads'].append({
        'name': 'opt-tiny_serve_rush', 'config': 'opt-tiny',
        'traffic': 'serve_rush', 'chips': 1, 'why': 'a test'})
    for m in bench['end_to_end'] + bench['per_layer']:
        if 'opt-1.3b_serve_chat' in m.get('workloads', ()):
            m['workloads'].append('opt-tiny_serve_rush')
    bench['per_layer'].append({
        'name': 'server.steps_per_request', 'unit': 'steps',
        'better': 'lower', 'source': 'program_counter', 'layer': 'server',
        'moves': 'itl_p95_ms', 'workloads': ['opt-tiny_serve_rush']})
    with open(os.path.join(root, 'BENCHMARK.json'), 'w') as f:
        json.dump(bench, f)
    res, earlier = rehearse(root, 'opt-tiny_serve_rush', trace=1)
    assert res['correct'] is True, earlier
    window = tagged(earlier, 'WINDOW')
    assert window['prefill_chunks'] > 0 and window['completed'] >= 20
    # the tap's stamps follow the program's own in this mode too
    assert tagged(earlier, 'TAP_CHECK')['max_diff_ms'] < 5.0
    # the old cells still run from the same copy
    res, earlier = rehearse(root, 'opt-1.3b_serve_chat')
    assert res['correct'] is True, earlier

"""chip_smoke.py's contract, as far as a machine without a chip can
check it: no TPU means a non-zero exit before any model is built, a
failing phase means a non-zero exit, and the rehearsal drives every
phase end to end at toy size."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _run(*argv, timeout):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, 'chip_smoke.py')] + list(argv),
        capture_output=True, text=True, timeout=timeout, cwd=ROOT)


def test_no_tpu_exits_nonzero_before_building_a_model():
    proc = _run(timeout=120)
    assert proc.returncode not in (0, 1), proc.stderr[-2000:]
    assert proc.stdout == ''  # no phase ran, no result was printed
    assert 'no TPU' in proc.stderr and '"platform": "cpu"' in proc.stderr


def test_failing_phase_exits_nonzero(tmp_path, monkeypatch, capsys):
    def boom(smoke):
        raise RuntimeError('phase exploded')
    monkeypatch.setitem(chip_smoke.PHASES, 'train', boom)
    monkeypatch.setitem(chip_smoke.PHASES, 'serve',
                        lambda smoke: {'answered': 8})
    rc = chip_smoke.main(['--rehearse', '--phases', 'train,serve',
                          '--out', str(tmp_path)])
    assert rc == 1
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    by_phase = {ln['phase']: ln for ln in lines if 'phase' in ln}
    assert by_phase['train']['ok'] is False
    assert 'phase exploded' in by_phase['train']['error']
    # the phases after a failure still run and report
    assert by_phase['serve']['ok'] is True
    assert all(ln['rehearsal'] is True for ln in lines)
    assert lines[-1]['ok'] is False and lines[-1]['failed'] == ['train']


@pytest.mark.slow
def test_rehearsal_runs_every_phase(tmp_path):
    proc = _run('--rehearse', '--out', str(tmp_path), timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    done = {ln['phase']: ln['ok'] for ln in lines if 'ok' in ln
            and 'phase' in ln}
    assert done == {'train': True, 'serve': True, 'kernels': True,
                    'trace': True}
    assert lines[-1] == {'ok': True, 'rehearsal': True,
                         'device': lines[-1]['device']}
    assert all(ln['rehearsal'] is True for ln in lines)

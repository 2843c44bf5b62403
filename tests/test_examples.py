"""examples/ stay runnable: the cheapest one executes end-to-end, the
rest must at least parse (full runs are minutes-long book trainings)."""
import os
import py_compile
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'examples')


def test_all_examples_compile():
    for f in sorted(os.listdir(EXAMPLES)):
        if f.endswith('.py'):
            py_compile.compile(os.path.join(EXAMPLES, f), doraise=True)


def test_fit_a_line_example_runs():
    # pin CPU via the config API inside the child (the examples use
    # default_place(), which would otherwise grab a TPU if there is one)
    code = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
            "import runpy; runpy.run_path(%r, run_name='__main__')"
            % os.path.join(EXAMPLES, 'fit_a_line.py'))
    r = subprocess.run([sys.executable, '-c', code],
                       capture_output=True, timeout=600)
    out = r.stdout.decode()
    assert r.returncode == 0, r.stderr.decode()[-1500:]
    assert 'epoch 9' in out, out[-500:]

"""Test bootstrap: force a deterministic 8-virtual-device CPU platform so
parallel tests (dp/tp/pp/sp over a Mesh) run without TPU hardware.

Must run before jax initialises its backends, hence module scope here
(pytest imports conftest before test modules import jax).
"""
import os

os.environ['JAX_PLATFORMS'] = 'cpu'
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=8').strip()
os.environ.setdefault('PADDLE_TPU_SYNTH_DATA', '1')
# the suite (and every child process it starts) never reads or writes a
# persistent compile cache, so tier-1 cannot depend on a warm one; the
# tests that exercise the cache turn it on through `compile_cache`
os.environ['JAX_ENABLE_COMPILATION_CACHE'] = 'false'
os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        'markers',
        "slow: timing-sensitive/long tests excluded from tier-1 "
        "(-m 'not slow')")


@pytest.fixture
def hold_steps(monkeypatch):
    """``release = hold_steps(engine)``: the DecodeEngine's ``step`` waits
    until ``release()`` is called, so a test can submit more prompts while
    the first stream stands just before its first decode step (they then
    find it decoding, whatever the machine's load).  The engine's own
    ``step`` is back after the test."""
    import threading

    def hold(engine):
        released, step = threading.Event(), engine.step

        def held(*args):
            assert released.wait(60.0), 'the test never released the steps'
            return step(*args)
        monkeypatch.setattr(engine, 'step', held)
        return released.set
    return hold


@pytest.fixture
def compile_cache(tmp_path, monkeypatch):
    """A per-test persistent compile cache, placed the way a deployment
    places one (JAX_COMPILATION_CACHE_DIR), with jax's size and
    compile-time floors dropped so CPU-sized compiles persist.  Yields
    the directory; the suite-wide "cache off" state is restored after."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    d = tmp_path / 'jax_cache'
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(d))
    keep = {n: getattr(jax.config, n) for n in (
        'jax_enable_compilation_cache', 'jax_compilation_cache_dir',
        'jax_persistent_cache_min_compile_time_secs',
        'jax_persistent_cache_min_entry_size_bytes')}
    jax.config.update('jax_enable_compilation_cache', True)
    jax.config.update('jax_compilation_cache_dir', str(d))
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    cc.reset_cache()  # jax latches the directory at its first compile
    yield d
    for n, v in keep.items():
        jax.config.update(n, v)
    cc.reset_cache()


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs + a fresh global scope, like the
    reference's per-test Program() isolation."""
    import paddle_tpu as fluid
    from paddle_tpu.core import program as prog_mod
    from paddle_tpu.core import scope as scope_mod
    main, startup = fluid.Program(), fluid.Program()
    old_main = prog_mod.switch_main_program(main)
    old_startup = prog_mod.switch_startup_program(startup)
    old_scope = scope_mod._global_scope
    scope_mod._global_scope = scope_mod.Scope()
    np.random.seed(1234)
    yield
    prog_mod.switch_main_program(old_main)
    prog_mod.switch_startup_program(old_startup)
    scope_mod._global_scope = old_scope

"""Where XLA's persistent compilation cache lives.

One rule, resolved before the first compile: when
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
package sets no directory at all; when it is not, the cache goes to
``<checkout>/.jax_cache`` — a fixed path, because the path is part of
every entry's key and a directory that moves never hits.  jax's own
thresholds decide what is worth persisting (by default compiles of a
second or more).  The autotuner's winner files fall back to the same
directory (tuning/cache.py).
"""
import os

import jax

__all__ = ['compile_cache_dir', 'enable_compile_cache']

_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    '.jax_cache')


def compile_cache_dir():
    """The directory compiled executables persist in."""
    return os.environ.get('JAX_COMPILATION_CACHE_DIR') or _CHECKOUT_DIR


def enable_compile_cache():
    """Point jax at :func:`compile_cache_dir`.  Called by everything
    that compiles (Executor, the serving and decode engines) when it is
    constructed, i.e. before its first compile: jax latches the cache
    directory at the first compile of the process."""
    if os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        return
    if jax.config.jax_compilation_cache_dir != _CHECKOUT_DIR:
        jax.config.update('jax_compilation_cache_dir', _CHECKOUT_DIR)

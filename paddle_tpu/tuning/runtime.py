"""Runtime glue between the tuner, the executor, and the bench layer.

Keying: winners persist under (plan key, device kind, mesh), every
component computed under :func:`registry.base_env` — the environment a
fresh, untuned process with the same user configuration would see — so
process N's winners are found by process N+1's first lookup and a tuned
process recomputes the same key it stored under.

Apply: ``PADDLE_TPU_TUNE=cached`` makes the executor call
:func:`maybe_apply_cached` before a program's plan key is computed.
Winners apply as env overrides (registry.apply_persistent); every
flag-scope tunable is a plan-cache-key component, so the tuned plan
builds exactly as a fresh pre-tuned process would build it.  The lookup
is memoized per (program uid, version): steady-state calls cost one
env read and one dict hit.
"""
from . import cache as cache_mod
from . import registry
from . import roofline

__all__ = ['base_plan_key', 'device_kind', 'program_fingerprint',
           'cache_key_for', 'maybe_apply_cached', 'model_program',
           'reset']

_APPLIED = {}  # (program uid, version) -> winners dict or None


def reset():
    """Forget per-program apply memos (tests)."""
    _APPLIED.clear()


def base_plan_key(program):
    """pass_manager.plan_key under the base (untuned) environment."""
    from ..transpiler import pass_manager
    with registry.base_env():
        return pass_manager.plan_key(program)


def device_kind(place=None):
    """The accelerator identity component of the winner-cache key —
    winners tuned for one chip generation never apply to another."""
    try:
        if place is not None:
            d = place.jax_device()
        else:
            import jax
            d = jax.devices()[0]
        return getattr(d, 'device_kind', None) or d.platform
    except Exception:  # pragma: no cover - backend init failure
        return 'unknown'


def program_fingerprint(program):
    """Structural identity of ``program`` for the winner-cache key: the
    op-type multiset over its blocks plus the parameter count.  Stable
    across rebuilds and processes (op TYPES carry no name counters, so
    the Nth in-process rebuild of a bench model fingerprints like the
    first build in a fresh process), while distinct models — whose
    tuned winners must not cross — differ.  Deliberately excludes
    shapes: batch size is itself a searched tunable, so batch variants
    of one program share winners by design."""
    counts = {}
    nparam = 0
    try:
        for block in program.blocks:
            for op in block.ops:
                counts[op.type] = counts.get(op.type, 0) + 1
            for var in block.vars.values():
                if getattr(var, 'persistable', False):
                    nparam += 1
    except Exception:  # pragma: no cover - exotic program objects
        return None
    return (tuple(sorted(counts.items())), nparam)


def cache_key_for(program, place=None):
    """The persistent winner-cache key for ``program`` here and now."""
    from ..transpiler import pass_manager
    from ..distributed.mesh_flag import mesh_key
    with registry.base_env():
        pk = pass_manager.plan_key(program)
        mk = mesh_key()
    pk = (pk, program_fingerprint(program))
    return cache_mod.TuneCache.key(pk, device_kind(place), mk)


def maybe_apply_cached(program, place=None):
    """PADDLE_TPU_TUNE=cached executor hook: look up persisted winners
    for this program and apply them as env overrides (once per
    (program, version)).  Returns the winners applied, None on miss or
    when tuning is off.  Never raises — an unreadable cache runs
    untuned."""
    from ..flags import FLAGS
    if FLAGS.tune != 'cached':
        return None
    memo = (program._uid, program.version)
    if memo in _APPLIED:
        return _APPLIED[memo]
    winners = None
    try:
        tc = cache_mod.TuneCache()
        if tc.enabled():
            winners = tc.load(cache_key_for(program, place))
            if winners:
                winners = registry.apply_persistent(winners)
    except Exception:  # pragma: no cover - defensive: run untuned
        import logging
        logging.getLogger(__name__).warning(
            'tuning winner apply failed; running untuned',
            exc_info=True)
        winners = None
    _APPLIED[memo] = winners
    return winners


def model_program(program, fetch_names=(), feed_specs=None,
                  peak_tflops=None, hbm_gbps=None):
    """Modeled {'score', 'peak_bytes', 'cost'} for ``program`` under the
    CURRENT environment — call inside ``registry.applied(cfg)`` to
    price a candidate.  ``score`` is the modeled roofline step time in
    seconds; callers searching batch normalize it per example
    themselves.  Returns None when the pipeline produces no cost report
    (graph-opt level 0)."""
    from ..transpiler import pass_manager
    feed_names = tuple(sorted(feed_specs)) if feed_specs else ()
    _prog, rep = pass_manager.run_pipeline(
        program, fetch_names=tuple(fetch_names), feed_names=feed_names,
        feed_specs=feed_specs)
    cost = (rep or {}).get('cost')
    if not cost or not (cost.get('total') or {}).get('flops'):
        return None
    mem = cost.get('memory') or {}
    return {'score': roofline.modeled_step_s(
                cost, peak_tflops=peak_tflops, hbm_gbps=hbm_gbps),
            'peak_bytes': mem.get('peak_bytes'),
            'cost': cost}

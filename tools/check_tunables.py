"""Static consistency check for the autotuner's tunable registry.

Every tunable registered in ``paddle_tpu/tuning/registry.py`` must be
actually searchable and documented:

- a bounded, duplicate-free candidate domain (more than one value —
  a single-value "domain" is a constant wearing a tunable's name —
  and at most 64, so an exhaustive coordinate pass stays cheap);
- the shipped default inside the domain (the search baseline must be
  a legal candidate);
- every domain value accepted by the tunable's own ``coerce`` round
  trip (``coerce(encode(v)) == v``) — the env-var application path
  must not mangle the value it applies;
- a documented ``PADDLE_TPU_*`` override: either a flag declared in
  paddle_tpu/flags.py (flags get their own README row via
  check_flags_doc) or, for bench-scope tunables that ride env vars
  directly, the env spelling present in README.md;
- a non-empty subsystem and help string, so the roofline/tuning docs
  can say what the knob feeds.

Catches the drift mode where a PR hand-tunes a new constant without
registering it properly: an unbounded or undocumented knob is exactly
the "magic constant" this registry exists to eliminate.

Runs standalone (``python tools/check_tunables.py``, exit 1 on
failure) and in tier-1 via tools/lint_all.py auto-discovery.
"""
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

_MAX_DOMAIN = 64

# knobs that MUST stay registered — hand-set constants the PRs that
# introduced them promised to the autotuner.  Deleting a registration
# silently un-tunes the knob (the flag keeps working, the search just
# stops seeing it), so the lint pins a floor under the registry.
_REQUIRED = (
    'amp', 'mesh',
    'overlap', 'overlap_bucket_mb', 'pp_microbatches',
    'decode_page_size', 'decode_max_streams', 'decode_prefill_bucket',
    'decode_prefix_cache', 'decode_prefill_chunk_tokens',
    'decode_page_reserve',
)


def _pristine_flags():
    """A fresh, private instance of paddle_tpu/flags.py — the audit
    must see exactly the flags the module DECLARES, not whatever a
    long-lived process DEFINE_*'d into the global registry."""
    import importlib.util
    path = os.path.join(_REPO, 'paddle_tpu', 'flags.py')
    spec = importlib.util.spec_from_file_location(
        '_check_tunables_audit', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.FLAGS


def check():
    """Returns a list of human-readable error strings (empty = OK)."""
    from paddle_tpu.tuning import registry

    errors = []
    tunables = registry.registered_tunables()
    if not tunables:
        return ["tunable registry is empty — import order bug?"]

    readme_path = os.path.join(_REPO, 'README.md')
    try:
        with open(readme_path) as f:
            readme = f.read()
    except OSError as e:
        return ["cannot read README.md: %s" % e]
    flag_envs = {'PADDLE_TPU_' + name.upper()
                 for name in _pristine_flags().definitions()}

    seen = set()
    for t in tunables:
        where = "tunable %r" % t.name
        if t.name in seen:
            errors.append("%s registered twice" % where)
        seen.add(t.name)
        # bounded, duplicate-free domain with the default inside it
        if not isinstance(t.domain, tuple):
            errors.append("%s: domain must be a tuple, got %s"
                          % (where, type(t.domain).__name__))
            continue
        if len(t.domain) < 2:
            errors.append(
                "%s: domain %r has fewer than 2 candidates — a "
                "single-value domain is a constant, not a tunable"
                % (where, t.domain))
        if len(t.domain) > _MAX_DOMAIN:
            errors.append(
                "%s: domain has %d candidates (max %d) — an "
                "exhaustive coordinate pass must stay cheap; coarsen "
                "the grid" % (where, len(t.domain), _MAX_DOMAIN))
        if len(set(t.domain)) != len(t.domain):
            errors.append("%s: domain %r contains duplicates"
                          % (where, t.domain))
        if t.default not in t.domain:
            errors.append(
                "%s: default %r is not in the domain %r — the search "
                "baseline must be a legal candidate"
                % (where, t.default, t.domain))
        # the env-var application path must round-trip every candidate
        for v in t.domain:
            try:
                back = t.coerce(t.encode(v))
            except Exception as e:
                errors.append("%s: coerce(encode(%r)) raised %s: %s"
                              % (where, v, type(e).__name__, e))
                continue
            if back != v:
                errors.append(
                    "%s: coerce(encode(%r)) round-trips to %r — the "
                    "env override would apply a different value"
                    % (where, v, back))
        # documented override
        if not (t.env or '').startswith('PADDLE_TPU_'):
            errors.append("%s: env override %r must start with "
                          "PADDLE_TPU_" % (where, t.env))
        elif t.env not in flag_envs and t.env not in readme:
            errors.append(
                "%s: env override %s is neither a declared flag "
                "(paddle_tpu/flags.py) nor documented in README.md — "
                "an undocumented knob exists only for whoever read "
                "the diff" % (where, t.env))
        if not (t.subsystem or '').strip():
            errors.append("%s: empty subsystem" % where)
        if not (t.help or '').strip():
            errors.append("%s: empty help string" % where)
    for name in _REQUIRED:
        if name not in seen:
            errors.append(
                "required tunable %r is no longer registered — the "
                "knob still works as a flag but the autotuner can no "
                "longer search it; restore the register_tunable() "
                "call in paddle_tpu/tuning/registry.py" % name)
    return errors


def main():
    errors = check()
    for e in errors:
        print("check_tunables: %s" % e, file=sys.stderr)
    if errors:
        return 1
    from paddle_tpu.tuning import registry
    print("check_tunables: OK (%d tunables: bounded domains, "
          "documented overrides)"
          % len(registry.registered_tunables()))
    return 0


if __name__ == '__main__':
    sys.exit(main())

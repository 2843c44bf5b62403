"""Central registry of every hand-set performance tunable.

Each :class:`Tunable` names one knob the autotuner (tuning/search.py)
may search: a bounded finite candidate domain, the shipped default, the
subsystem that consumes it, and the documented ``PADDLE_TPU_*`` env
override through which a choice is applied.

Two scopes:

- ``'flag'`` tunables apply by setting their env var.  Every consumer
  re-reads its flag per plan build and the plan-affecting ones are
  components of the executor's composite plan-cache key
  (pass_manager.plan_key), so an applied override simply retraces — no
  subsystem needs tuner-specific plumbing.
- ``'bench'`` tunables (train batch, run_steps K) change the *program*
  or the call shape; the executor cannot apply them transparently, so
  the bench harness that builds the program consumes them (bench.py
  ``--tune search``).

Pinning: a tunable whose env var the USER set (rather than the tuner)
is pinned — the search skips it and the pinned value rides unchanged in
every candidate.  To pin a knob, export its env var before running the
tuner; to unpin, unset it.

tools/check_tunables.py lints this registry in tier-1 via lint_all:
bounded domains, defaults inside the domain, and a documented override
for every entry (declared flag or README-documented bench env var).
"""
import contextlib
import os

__all__ = ['Tunable', 'register_tunable', 'registered_tunables',
           'tunable', 'defaults', 'current_config', 'is_pinned',
           'applied', 'apply_persistent', 'tuner_applied_env',
           'base_env']

# env vars the TUNER set in this process (apply_persistent) — masked by
# base_env() so the winner-cache key is computed from the configuration
# a fresh, untuned process would also compute, and excluded from the
# pinned set (only a USER-set env var pins a tunable)
_TUNER_APPLIED = set()


class Tunable(object):
    """One searchable knob: name, bounded domain, default, subsystem,
    and the env override that applies a choice."""

    __slots__ = ('name', 'domain', 'default', 'subsystem', 'env',
                 'scope', 'help', 'feasible')

    def __init__(self, name, domain, default, subsystem, env,
                 scope='flag', help='', feasible=None):
        self.name = name
        self.domain = tuple(domain)
        self.default = default
        self.subsystem = subsystem
        self.env = env
        self.scope = scope
        self.help = help
        self.feasible = feasible  # optional value -> bool (device fit)

    def coerce(self, raw):
        """Parse an env-var string back to this tunable's value type."""
        if isinstance(self.default, bool):
            return raw.lower() in ('1', 'true', 'yes', 'on')
        return type(self.default)(raw)

    def encode(self, value):
        """The env-var string that applies ``value``."""
        return str(value)

    def __repr__(self):
        return 'Tunable(%r, domain=%r, default=%r, env=%r)' % (
            self.name, self.domain, self.default, self.env)


_REGISTRY = {}  # name -> Tunable, registration order preserved


def register_tunable(name, domain, default, subsystem, env,
                     scope='flag', help='', feasible=None):
    if name in _REGISTRY:
        raise ValueError('tunable %r already registered' % name)
    t = Tunable(name, domain, default, subsystem, env, scope=scope,
                help=help, feasible=feasible)
    _REGISTRY[name] = t
    return t


def registered_tunables():
    """Every registered tunable, in registration order."""
    return tuple(_REGISTRY.values())


def tunable(name):
    return _REGISTRY[name]


def defaults():
    """{name: shipped default} over the whole registry."""
    return {t.name: t.default for t in _REGISTRY.values()}


def is_pinned(t):
    """True when the USER set this tunable's env var — the tuner then
    treats the knob as fixed (skipped by the search, kept verbatim in
    every candidate).  Env vars the tuner itself applied do not pin."""
    return t.env in os.environ and t.env not in _TUNER_APPLIED


def current_config(tunables=None):
    """{name: effective value} — the env override when set (coerced to
    the default's type), the shipped default otherwise."""
    out = {}
    for t in (tunables or _REGISTRY.values()):
        raw = os.environ.get(t.env)
        if raw is None:
            out[t.name] = t.default
        else:
            try:
                out[t.name] = t.coerce(raw)
            except (TypeError, ValueError):
                out[t.name] = t.default
    return out


@contextlib.contextmanager
def applied(overrides):
    """Temporarily apply ``{name: value}`` via env vars (flag-scope AND
    bench-scope — both ride on env), restoring the prior environment on
    exit.  The search's candidate evaluation guard."""
    saved = {}
    try:
        for name, value in (overrides or {}).items():
            t = _REGISTRY[name]
            saved[t.env] = os.environ.get(t.env)
            os.environ[t.env] = t.encode(value)
        yield
    finally:
        for env, old in saved.items():
            if old is None:
                os.environ.pop(env, None)
            else:
                os.environ[env] = old


def apply_persistent(overrides, skip=()):
    """Apply winners for the rest of the process (PADDLE_TPU_TUNE=cached
    executor path): set each tunable's env var and remember that the
    TUNER did it, so base_env() can mask it back out of cache-key
    computation and is_pinned() keeps treating the knob as tunable.
    User-pinned tunables are never overwritten.  Returns the dict of
    overrides actually applied."""
    done = {}
    for name, value in (overrides or {}).items():
        t = _REGISTRY.get(name)
        if t is None or name in skip or is_pinned(t):
            continue
        os.environ[t.env] = t.encode(value)
        _TUNER_APPLIED.add(t.env)
        done[name] = value
    return done


def tuner_applied_env():
    return frozenset(_TUNER_APPLIED)


@contextlib.contextmanager
def base_env():
    """Mask every tuner-applied env var: inside this context the
    environment is what a fresh, untuned process with the same USER
    configuration would see.  The winner-cache key (runtime.py) is
    computed here, so a tuned process and a fresh one derive the same
    key for the same program — the zero-search-restart contract."""
    saved = {}
    try:
        for env in list(_TUNER_APPLIED):
            if env in os.environ:
                saved[env] = os.environ.pop(env)
        yield
    finally:
        os.environ.update(saved)


# ---------------------------------------------------------------------------
# the registrations — the hand-set constants the autotuner may search
# ---------------------------------------------------------------------------

def _mesh_feasible(spec):
    """A mesh candidate is feasible when the devices exist."""
    s = str(spec or '').strip()
    if not s:
        return True
    try:
        # ONE spec vocabulary: axis=size and compact axisN both parse
        from ..distributed.spec_layout import parse_mesh_spec
        axes = parse_mesh_spec(s)
    except ValueError:
        return False
    n = 1
    for _, size in axes:
        n *= max(int(size), 1)
    if n <= 1:
        return True
    try:
        import jax
        return n <= len(jax.devices())
    except Exception:  # pragma: no cover - no backend at all
        return False


register_tunable(
    'device_prefetch_chunk', (0, 1, 2, 4, 8, 16, 32),
    default=0, subsystem='runtime.prefetch',
    env='PADDLE_TPU_DEVICE_PREFETCH_CHUNK',
    help='steps per staged chunk of the device-resident '
         'double-buffered feed (0 = auto ~K/4)')
register_tunable(
    'amp', ('0', 'bf16', 'f16'),
    default='0', subsystem='transpiler.amp', env='PADDLE_TPU_AMP',
    help='mixed-precision mode the AMP pass applies per plan build')
register_tunable(
    'mesh', ('', 'dp=2', 'dp=4', 'dp=8', 'fsdp=2', 'fsdp=4', 'fsdp=8',
             'dp=2,tp=2', 'dp=2,fsdp=2', 'dp=4,fsdp=2'),
    default='', subsystem='transpiler.sharding', env='PADDLE_TPU_MESH',
    feasible=_mesh_feasible,
    help='SPMD dp/fsdp/tp split; candidates needing more devices than '
         'the backend exposes are infeasible and never measured')
register_tunable(
    'embed_bucket_tile', (4, 8, 16, 32, 64),
    default=8, subsystem='distributed.embedding',
    env='PADDLE_TPU_EMBED_BUCKET_TILE',
    help='tile alignment of the sharded-embedding per-shard id buckets')
register_tunable(
    'embed_cache_rows', (0, 256, 1024, 4096),
    default=0, subsystem='distributed.embedding',
    env='PADDLE_TPU_EMBED_CACHE_ROWS',
    help='hot-row embedding cache capacity (0 = no cache)')
register_tunable(
    'serving_max_wait_ms', (1.0, 2.0, 5.0, 10.0, 20.0),
    default=5.0, subsystem='inference.batching',
    env='PADDLE_TPU_SERVING_MAX_WAIT_MS',
    help='serving deadline flush: max ms the oldest queued request '
         'waits before a partial batch dispatches')
register_tunable(
    'serving_max_batch', (8, 16, 32, 64, 128),
    default=8, subsystem='inference.batching',
    env='PADDLE_TPU_SERVING_MAX_BATCH',
    help='serving bucket-ladder top (powers of two up to this)')
register_tunable(
    'overlap', (False, True),
    default=True, subsystem='transpiler.overlap',
    env='PADDLE_TPU_OVERLAP',
    help='collective-overlap scheduling pass on/off: bucket gradient '
         'allreduces and fire each as soon as its grads retire from '
         'the backward (off = one serial comm phase at the end)')
register_tunable(
    'overlap_bucket_mb', (4, 8, 16, 25, 50, 100),
    default=25, subsystem='transpiler.overlap',
    env='PADDLE_TPU_OVERLAP_BUCKET_MB',
    help='gradient-bucket size cap for the overlap pass: smaller '
         'buckets start communicating earlier but pay more per-op '
         'latency; larger ones amortize it but expose the tail')
register_tunable(
    'pp_microbatches', (2, 4, 8, 16, 32),
    default=4, subsystem='distributed.pipeline',
    env='PADDLE_TPU_PP_MICROBATCHES',
    help='microbatches per pipelined step: more shrink the 1F1B '
         'bubble (S-1)/(M+S-1) but each microbatch must still fill '
         'the MXU, and the batch must split evenly')
register_tunable(
    'train_batch', (16, 32, 64, 128, 256, 512),
    default=64, subsystem='bench', env='PADDLE_TPU_BENCH_BATCH',
    scope='bench',
    help='train batch size — changes the program, so only the bench '
         'harness (which rebuilds per candidate) can search it')
register_tunable(
    'run_steps_k', (20, 50, 100, 200, 500),
    default=100, subsystem='bench', env='PADDLE_TPU_BENCH_RUN_STEPS',
    scope='bench',
    help='steps per run_steps scan — amortizes the per-call dispatch '
         'round trip; consumed by the bench harness')
register_tunable(
    'decode_page_size', (8, 16, 32, 64, 128),
    default=16, subsystem='inference.decode',
    env='PADDLE_TPU_DECODE_PAGE_SIZE',
    help='KV-cache page granularity (tokens per page): small pages '
         'waste less on ragged tails but grow the page table; large '
         'pages read denser but strand capacity on short streams')
register_tunable(
    'decode_max_streams', (2, 4, 8, 16, 32),
    default=8, subsystem='inference.decode',
    env='PADDLE_TPU_DECODE_MAX_STREAMS',
    help='decode step width (streams batched per token step): wider '
         'amortizes the weight read across streams but multiplies '
         'the page pool the admission check must cover')
register_tunable(
    'decode_prefill_bucket', (32, 64, 128, 256, 512),
    default=128, subsystem='inference.decode',
    env='PADDLE_TPU_DECODE_PREFILL_BUCKET',
    help='prompt-length bucket ladder top for prefill (powers of two '
         'up to this, clamped to the model context): taller ladders '
         'pad long prompts less but compile more variants at warmup')
register_tunable(
    'decode_prefix_cache', (False, True),
    default=False, subsystem='inference.decode',
    env='PADDLE_TPU_DECODE_PREFIX_CACHE',
    help='radix-trie prefix reuse of KV pages: shared-prefix prompts '
         'skip the cached span\'s prefill MACs at the price of trie '
         'bookkeeping and chunked (per-grid) prefill dispatches')
register_tunable(
    'decode_prefill_chunk_tokens', (0, 32, 64, 128, 256),
    default=0, subsystem='inference.decode',
    env='PADDLE_TPU_DECODE_PREFILL_CHUNK_TOKENS',
    help='per-tick chunked-prefill token budget: smaller bounds the '
         'inter-token latency hit of a long-prompt admission, larger '
         'finishes prefill (TTFT) sooner; 0 = whole prefill per tick')
register_tunable(
    'decode_page_reserve', (0, 1, 2, 4, 8),
    default=2, subsystem='inference.decode',
    env='PADDLE_TPU_DECODE_PAGE_RESERVE',
    help='admission-time free-page watermark under incremental '
         'allocation: higher admits later but preempts growing '
         'streams less often when the pool runs tight')

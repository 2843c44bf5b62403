"""PassManager: one statically-checked rewrite pipeline over program IR.

Reference parity: the Fluid core threaded every ProgramDesc rewrite
through one transpiler discipline with framework.proto validation between
stages.  Here graph-opt (PR 3), AMP (PR 5), and the donation analysis
each grew their own copy/ordering/report conventions, glued together ad
hoc in core/executor.py — and every new rewrite (sharding propagation is
next, ROADMAP item 1) would have added a fourth.  This module folds them
into an explicit pipeline:

- every pass is **registered** (``@register_pass``) with a declared
  ``order``, a ``report_key``, and a kind (``rewrite`` | ``analysis``);
  tools/check_pass_registry.py statically audits the registry and
  cross-checks it against the verifier mutation-test matrix.  After
  AMP comes sharding propagation (order 85, transpiler/sharding.py,
  enabled by PADDLE_TPU_MESH — stamps per-op PartitionSpecs + the
  SPMD plan the executor pjit-lowers with), then the embedding-engine
  lowering (order 87, ``apply_embed_lowering`` — rewrites lookups over
  row-sharded tables onto the all-to-all + per-shard-apply route and
  prices their collectives); the analysis tail is
  donation (order 90), the static cost model (order 95,
  transpiler/cost_model.py — after AMP so low-precision bytes count,
  after sharding so the collective table is priced), then the
  liveness-based peak-memory model (order 96,
  transpiler/memory_model.py, nested under the cost report, dividing
  sharded residency by the plan's shard divisors).
- ``run_pipeline`` builds the plan for the current configuration
  (graph-opt level, AMP mode), runs each pass on an isolated copy — a
  crashing graph-opt or analysis pass is skipped with a per-pass report
  entry, it can no longer corrupt the program mid-rewrite; a crashing
  pass the caller asked for by flag (``_MUST_APPLY``: amp, sharding,
  embed_shard, overlap_collectives) re-raises — and runs the static
  verifier
  (transpiler/verify.py) after every pass (``every_pass``) or once at
  the end (``boundary``, default), attributing any failure to the
  offending pass.
- ``plan_key`` derives the ONE composite plan-cache key component from
  the pass configuration; core/executor.py embeds it in both the run and
  run_steps keys instead of hand-maintaining flag tuples.

The per-pass report list lands in
``Executor.last_graph_opt_report['passes']`` as
``{'name', 'ops_before', 'ops_after', 'wall_s', 'status', 'verify'}``.
"""
import collections
import copy
import time

from . import passes
from . import verify as verify_mod

__all__ = ['register_pass', 'registered_passes', 'build_plan',
           'run_pipeline', 'plan_key', 'resolve_level', 'PassDef',
           'IRVerificationError']

IRVerificationError = verify_mod.IRVerificationError

PassDef = collections.namedtuple(
    'PassDef', ['name', 'order', 'report_key', 'kind', 'enabled', 'fn'])

# name -> PassDef.  Orders are declared, unique, and audited by
# tools/check_pass_registry.py; the plan executes in ascending order.
PASSES = {}

# rewrite passes that decide what runs on the device.  Each is in the
# plan only because a flag asked for it (PADDLE_TPU_AMP,
# PADDLE_TPU_MESH), so a crash in one re-raises: skipping it would train
# in f32, or run replicated, under a configuration that says otherwise
_MUST_APPLY = frozenset(['amp', 'sharding', 'embed_shard',
                         'overlap_collectives'])

# test hook: {pass name -> fn(program)} applied to a pass's output
# before verification — the mutation tests corrupt exactly one pass and
# prove every_pass mode pins the failure to it.  Never set in production.
_TEST_CORRUPTORS = {}


def register_pass(name, order, report_key, kind='rewrite', enabled=None):
    """Register a pass.  ``fn(program, ctx) -> extra-report-dict`` must
    rewrite ``program`` in place (rewrite kind) or only read it
    (analysis kind); ``enabled(cfg)`` gates it per configuration."""
    if kind not in ('rewrite', 'analysis'):
        raise ValueError("pass kind must be rewrite|analysis")
    if any(p.order == order for p in PASSES.values()):
        raise ValueError("pass order %d already taken" % order)

    def deco(fn):
        if name in PASSES:
            raise ValueError("pass %r already registered" % name)
        PASSES[name] = PassDef(name, order, report_key, kind,
                               enabled or (lambda cfg: True), fn)
        return fn

    return deco


def registered_passes():
    return sorted(PASSES.values(), key=lambda p: p.order)


PassConfig = collections.namedtuple('PassConfig',
                                    ['level', 'amp_mode', 'mesh'])
# mesh defaults to None (off) so positional (level, amp) callers and
# the registry checker's build_plan(level, amp) probes keep working
PassConfig.__new__.__defaults__ = (None,)


class PassContext(object):
    """Shared state the passes read: fetch/feed sets, caller-pinned
    names, and the protected/no-fold sets (computed once per pipeline,
    exactly like the PR-3 driver did)."""

    def __init__(self, fetch_names, feed_names, pinned, amp_mode,
                 feed_specs=None, mesh_axes=None):
        self.fetch_names = tuple(fetch_names)
        self.feed_names = tuple(feed_names)
        self.pinned = set(pinned)
        self.amp_mode = amp_mode
        # normalized PADDLE_TPU_MESH axes tuple (('dp', 2), ...) or
        # None — the sharding-propagation pass's mesh config
        self.mesh_axes = tuple(mesh_axes) if mesh_axes else None
        # {name: (shape, dtype)} concrete feed shapes from the executor
        # — the cost-model pass seeds its shape propagation with them so
        # -1 batch dims resolve to the real batch
        self.feed_specs = dict(feed_specs or {})
        self.amp_report = None  # set by the amp pass
        self._protected = None
        self._no_fold = None

    def compute_protected(self, program):
        persist = passes._persistable_names(program)
        ctrl = passes._control_referenced_names(program)
        self._protected = (set(self.fetch_names) | set(self.feed_names)
                           | persist | ctrl | self.pinned)
        self._no_fold = persist | ctrl | self.pinned

    def protected(self, program):
        if self._protected is None:
            self.compute_protected(program)
        return self._protected

    def no_fold(self, program):
        if self._no_fold is None:
            self.compute_protected(program)
        return self._no_fold


# ---------------------------------------------------------------------------
# The registered passes (ported from transpiler/passes.py + amp.py).
# ---------------------------------------------------------------------------

@register_pass('dce', 10, 'dce', enabled=lambda cfg: cfg.level >= 1)
def _dce(program, ctx):
    n = passes.dce_pass(program, ctx.fetch_names, extra_live=ctx.pinned)
    return {'eliminated': n}


@register_pass('constant_fold', 20, 'fold',
               enabled=lambda cfg: cfg.level >= 2)
def _constant_fold(program, ctx):
    n = passes.constant_fold_pass(
        program, ctx.fetch_names, ctx.feed_names,
        protected=ctx.protected(program), no_fold=ctx.no_fold(program))
    return {'eliminated': n}


@register_pass('cse', 30, 'cse', enabled=lambda cfg: cfg.level >= 2)
def _cse(program, ctx):
    n = passes.cse_pass(program, ctx.fetch_names, ctx.feed_names,
                        protected=ctx.protected(program))
    return {'eliminated': n}


@register_pass('dce_sweep', 40, 'dce',
               enabled=lambda cfg: cfg.level >= 2)
def _dce_sweep(program, ctx):
    # folding/dedup can orphan their upstream producers
    n = passes.dce_pass(program, ctx.fetch_names, extra_live=ctx.pinned)
    return {'eliminated': n}


@register_pass('amp', 60, 'amp',
               enabled=lambda cfg: cfg.amp_mode is not None)
def _amp(program, ctx):
    from . import amp as amp_mod
    rewritten, report = amp_mod.apply_amp(program, mode=ctx.amp_mode)
    ctx.amp_report = report
    if rewritten is not program and report is not None:
        # apply_amp weaves its own copy; splice the result back into the
        # in-place contract the manager runs passes under
        program.blocks = rewritten.blocks
        for b in program.blocks:
            b.program = program
    return {'amp': report}


@register_pass('sharding', 85, 'sharding',
               enabled=lambda cfg: bool(cfg.mesh))
def _sharding(program, ctx):
    # after graph-opt and AMP (it must see exactly the ops that will
    # trace), before the analysis tail (cost prices its collective
    # table, memory divides by its shard divisors): propagate per-op
    # PartitionSpecs over the mesh and stamp the plan the executor
    # pjit-lowers with
    from . import sharding as sharding_mod
    return {'sharding': sharding_mod.apply_sharding(
        program, ctx.mesh_axes, fetch_names=ctx.fetch_names,
        feed_names=ctx.feed_names, feed_specs=ctx.feed_specs)}


@register_pass('embed_shard', 87, 'embed',
               enabled=lambda cfg: bool(cfg.mesh))
def _embed_shard(program, ctx):
    # right after sharding propagation (the embed registry it consumes
    # lives on program._sharding_plan), before the analysis tail so
    # the cost model prices the lookup all-to-alls it appends: lower
    # lookups over row-sharded tables to the all-to-all + per-shard
    # engine route (PADDLE_TPU_EMBED_SHARD; a no-op when the plan
    # registered no row-sharded tables)
    from . import sharding as sharding_mod
    return {'embed': sharding_mod.apply_embed_lowering(program)}


def _overlap_enabled():
    from . import overlap as overlap_mod
    return overlap_mod.overlap_enabled()


@register_pass('overlap_collectives', 88, 'overlap',
               enabled=lambda cfg: bool(cfg.mesh) and _overlap_enabled())
def _overlap_collectives(program, ctx):
    # after sharding + embed lowering (it buckets the gradient entries
    # of the finished collective table), before the analysis tail (the
    # cost model prices the bucket schedule's exposed-vs-overlapped
    # bytes, the memory model charges the in-flight bucket): order
    # gradient allreduce/reduce-scatter into retirement-ordered
    # size-bounded buckets and stamp the donation-safe grouping the
    # executor lowers with optimization_barrier
    from . import overlap as overlap_mod
    return {'overlap': overlap_mod.apply_overlap(
        program, feed_specs=ctx.feed_specs)}


@register_pass('donation', 90, 'donation', kind='analysis',
               enabled=lambda cfg: cfg.level >= 1)
def _donation(program, ctx):
    return {'donation': passes.analyze_donation(
        program, ctx.fetch_names, ctx.feed_names)}


@register_pass('cost_model', 95, 'cost', kind='analysis',
               enabled=lambda cfg: cfg.level >= 1)
def _cost_model(program, ctx):
    # runs AFTER graph-opt and AMP on purpose: eliminated ops cost
    # nothing and AMP-lowered values count their low-precision bytes
    from . import cost_model
    return {'cost': cost_model.analyze_cost(
        program, fetch_names=ctx.fetch_names,
        feed_specs=ctx.feed_specs)}


@register_pass('memory_model', 96, 'memory', kind='analysis',
               enabled=lambda cfg: cfg.level >= 1)
def _memory_model(program, ctx):
    # right after the cost model, same post-rewrite program and
    # feed-spec-seeded shapes (the memoized infer cache is warm from
    # the cost walk): modeled peak resident bytes + per-op live-bytes
    # timeline, reported under last_graph_opt_report['cost']['memory']
    from . import memory_model
    return {'memory': memory_model.analyze_memory(
        program, fetch_names=ctx.fetch_names,
        feed_specs=ctx.feed_specs)}


# ---------------------------------------------------------------------------
# plan building + the composite cache key
# ---------------------------------------------------------------------------

def resolve_level(program=None, level=None):
    """Effective graph-opt level: the flag (re-read per build), floored
    at 1 when memory_optimize()/release_memory() armed the pipeline for
    this program."""
    lv = passes._resolve_level(level)
    if program is not None and \
            getattr(program, '_graph_opt_requested', False):
        lv = max(lv, 1)
    return lv


def build_plan(level, amp_mode, mesh=None):
    cfg = PassConfig(level, amp_mode, mesh)
    return [p for p in registered_passes() if p.enabled(cfg)]


def plan_key(program=None):
    """The composite plan-cache key component derived from the pass
    configuration — the ONE code path both Executor.run and run_steps
    key their caches on.  Covers every knob that changes what a plan
    build produces: graph-opt level, AMP mode (+ loss-scale knobs),
    verify mode, the sparse optimizer-apply lowering baked into the
    traced ops, and the SPMD mesh (PADDLE_TPU_MESH) the sharding pass
    propagates and the executor pjit-lowers with."""
    from .amp import plan_key_component
    from ..distributed.mesh_flag import mesh_key
    from ..ops.pallas.table_update import sparse_apply_mode
    from .sharding import embed_plan_key
    from .overlap import overlap_plan_key
    from ..flags import FLAGS
    return ('pm', resolve_level(program), plan_key_component(),
            verify_mod.resolve_mode(None), sparse_apply_mode(),
            mesh_key(), embed_plan_key(), overlap_plan_key(),
            int(FLAGS.pp_microbatches or 0))


# ---------------------------------------------------------------------------
# the pipeline driver
# ---------------------------------------------------------------------------

def _amp_low(amp_mode):
    from .amp import LOW_DTYPE
    return LOW_DTYPE.get(amp_mode)


_FROM_FLAG = object()


def run_pipeline(program, fetch_names=(), feed_names=(), level=None,
                 amp_mode=_FROM_FLAG, verify=_FROM_FLAG,
                 extra_protected=(), feed_specs=None, mesh=_FROM_FLAG):
    """Run the registered pass plan over a copy of ``program``.

    Returns ``(program_out, report)``; the input program is never
    mutated, and with an empty plan (level 0, AMP off) the original
    comes back untouched.  ``amp_mode``/``verify``/``mesh`` default to
    their flags (PADDLE_TPU_AMP / PADDLE_TPU_VERIFY_IR /
    PADDLE_TPU_MESH); pass explicit values ('0' / 'off' / '') to pin
    them.  Raises IRVerificationError when the verifier rejects a pass
    output (every_pass) or the final program (boundary).  A graph-opt
    or analysis pass that *crashes* is skipped and reported; a crash in
    a ``_MUST_APPLY`` pass (the flag-requested rewrites that change
    what runs on the device) propagates.
    """
    from .amp import resolve_mode as amp_resolve
    from ..distributed.mesh_flag import mesh_axes_from_flag
    level = resolve_level(program, level)
    amp_mode = amp_resolve(None if amp_mode is _FROM_FLAG else amp_mode)
    mesh_axes = mesh_axes_from_flag(
        None if mesh is _FROM_FLAG else (mesh or ''))
    verify_mode = verify_mod.resolve_mode(
        None if verify is _FROM_FLAG else verify)
    fetch_names = tuple(fetch_names)
    feed_names = tuple(feed_names)
    plan = build_plan(level, amp_mode, mesh_axes)

    report = {
        'level': level,
        'ops_before': None,
        'ops_after': None,
        'eliminated': {},
        'pass_wall_s': 0.0,
        'passes': [],
        'verify': {'mode': verify_mode, 'checks': 0, 'wall_s': 0.0},
    }
    if not any(p.kind == 'rewrite' for p in plan):
        if verify_mode != 'off':
            tv = time.perf_counter()
            verify_mod.check_program(program, fetch_names, feed_names,
                                     require_op_seq=False)
            report['verify']['checks'] = 1
            report['verify']['wall_s'] = time.perf_counter() - tv
        return program, report

    t0 = time.perf_counter()
    pinned = set(extra_protected) | set(
        getattr(program, '_graph_opt_skip_set', None) or ())
    ctx = PassContext(fetch_names, feed_names, pinned, amp_mode,
                      feed_specs=feed_specs, mesh_axes=mesh_axes)

    p = copy.deepcopy(program)
    passes._stamp_op_seq(p.global_block())
    snapshot0 = verify_mod.pin_snapshot(p, fetch_names, feed_names)
    graph_opt_ran = level >= 1
    if graph_opt_ran:
        report['ops_before'] = len(p.global_block().ops)
    amp_applied = None

    applied = []  # rewrite passes that succeeded (deterministic replay)
    for pd in plan:
        n_before = len(p.global_block().ops)
        entry = {'name': pd.name, 'ops_before': n_before,
                 'ops_after': n_before, 'wall_s': 0.0,
                 'status': 'ok', 'verify': 'skipped'}
        report['passes'].append(entry)
        tp = time.perf_counter()
        snap = (verify_mod.pin_snapshot(p, fetch_names, feed_names)
                if pd.kind == 'rewrite' else None)
        try:
            # passes run IN PLACE on the one working copy — a second
            # copy per pass would put 5-6 full deepcopies on every
            # plan-cache miss; the crash path below pays the rebuild
            # instead, because crashing is the rare case
            frag = pd.fn(p, ctx) or {}
            corrupt = _TEST_CORRUPTORS.get(pd.name)
            if corrupt is not None:
                corrupt(p)
        except verify_mod.IRVerificationError:
            raise
        except Exception as e:
            entry['status'] = 'failed: %r' % (e,)
            entry['wall_s'] = time.perf_counter() - tp
            if pd.name in _MUST_APPLY:
                raise
            # the crashed pass may have died mid-mutation: rebuild the
            # working copy and replay the passes that already succeeded
            # (each is deterministic over the same input)
            p = copy.deepcopy(program)
            passes._stamp_op_seq(p.global_block())
            for prev in applied:
                prev.fn(p, ctx)
            continue
        entry['wall_s'] = time.perf_counter() - tp
        if pd.kind == 'rewrite':
            applied.append(pd)
            entry['ops_after'] = len(p.global_block().ops)
            if pd.name == 'amp' and ctx.amp_report is not None:
                amp_applied = _amp_low(amp_mode)
            if verify_mode == 'every_pass':
                tv = time.perf_counter()
                try:
                    verify_mod.check_program(
                        p, fetch_names, feed_names, require_op_seq=True,
                        amp_low=amp_applied, snapshot=snap,
                        pass_name=pd.name)
                except verify_mod.IRVerificationError:
                    entry['verify'] = 'failed'
                    raise
                else:
                    entry['verify'] = 'ok'
                finally:
                    report['verify']['checks'] += 1
                    report['verify']['wall_s'] += \
                        time.perf_counter() - tv
        # merge the pass's report fragment
        n = frag.get('eliminated')
        if n is not None:
            report['eliminated'][pd.report_key] = \
                report['eliminated'].get(pd.report_key, 0) + n
        if 'donation' in frag:
            report['donation'] = frag['donation']
        if 'amp' in frag and frag['amp'] is not None:
            report['amp'] = frag['amp']
        if frag.get('sharding') is not None:
            report['sharding'] = frag['sharding']
        if frag.get('embed') is not None:
            report['embed'] = frag['embed']
        if frag.get('overlap') is not None:
            report['overlap'] = frag['overlap']
        if frag.get('cost') is not None:
            report['cost'] = frag['cost']
        if frag.get('memory') is not None:
            # the memory model nests under the cost report — ONE
            # 'cost' entry carries the whole static-analysis story
            report.setdefault('cost', {})['memory'] = frag['memory']

    if graph_opt_ran:
        report['ops_after'] = len(p.global_block().ops)
    if verify_mode == 'boundary':
        tv = time.perf_counter()
        verify_mod.check_program(p, fetch_names, feed_names,
                                 require_op_seq=True,
                                 amp_low=amp_applied,
                                 snapshot=snapshot0)
        report['verify']['checks'] = 1
        report['verify']['wall_s'] = time.perf_counter() - tv
    report['pass_wall_s'] = time.perf_counter() - t0
    return p, report

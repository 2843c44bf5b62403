"""Executor: lowers a whole Program block into ONE jit-compiled XLA
computation.

Reference parity: paddle/framework/executor.{h,cc} + python fluid
executor.py.  The reference interprets a block op-by-op, dispatching a CUDA
kernel per op.  TPU-native design: the same block is *traced* op-by-op in
Python exactly once, producing a single fused HLO program that XLA compiles
for the MXU; parameters stay device-resident in the Scope and are donated
across steps, so a full train step (forward + backward + optimizer update)
is one device launch with zero host round-trips.
"""
import contextlib
import os
import re
import time
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..compile_cache import enable_compile_cache
from ..observability import timeline as _tlm
from . import datatypes
from .lod import LoDTensor
from .place import default_place
from .program import (LEN_SUFFIX, Program, Variable, default_main_program)
from .registry import get_op_impl
from .scope import Scope, global_scope

__all__ = ['Executor', 'global_scope', 'scope_guard']

from .scope import scope_guard  # re-export (parity with fluid.executor)

def _maybe_apply_tuned(program, place):
    """PADDLE_TPU_TUNE=cached: apply persisted autotuner winners for
    this program (tuning/runtime.py) BEFORE the mesh resolves and the
    plan key is computed — the applied env overrides are plan-cache-key
    components, so the tuned plan builds exactly as a fresh pre-tuned
    process would build it.  With tuning off (the default) this is one
    dict lookup: no import, no flag object, bitwise-identical paths."""
    if os.environ.get('PADDLE_TPU_TUNE') != 'cached':
        return
    try:
        from ..tuning import runtime as _trt
        _trt.maybe_apply_cached(program, place)
    except Exception:  # never let tuning break an untunable run
        import logging
        logging.getLogger(__name__).warning(
            'tuning cache apply failed; running untuned', exc_info=True)


class _ExecutorMetrics(object):
    """Handles into the observability registry for the executor layer.

    Created lazily on the first *enabled* use — with
    PADDLE_TPU_METRICS_ENABLED=0 nothing here is ever allocated, which
    is the zero-overhead contract the hot path relies on.  All metrics
    are host-side: they bracket the calls *into* compiled code, never
    run under a trace.
    """

    def __init__(self):
        r = _obs.registry()
        # .child() handles: one lock per event on the hot path, vs the
        # metric-level conveniences' label lookup + two locks per event
        self.plan_cache_hits = r.counter(
            'paddle_tpu_executor_plan_cache_hits_total',
            'Executor plan-cache lookups served from cache').child()
        self.plan_cache_misses = r.counter(
            'paddle_tpu_executor_plan_cache_misses_total',
            'Executor plan-cache lookups that built (traced) a new '
            'plan').child()
        self.compiles = r.counter(
            'paddle_tpu_executor_compiles_total',
            'first invocations of freshly built plans (each pays the '
            'XLA compile)').child()
        self.compile_seconds = r.histogram(
            'paddle_tpu_executor_compile_seconds',
            'wall time of the first invocation of a fresh plan '
            '(trace + XLA compile + dispatch)',
            buckets=_obs.DEFAULT_COMPILE_BUCKETS).child()
        self.runs = r.counter(
            'paddle_tpu_executor_runs_total',
            'Executor.run() calls').child()
        self.steps = r.counter(
            'paddle_tpu_executor_steps_total',
            'train/eval steps executed (run() counts one, '
            'run_steps(K) counts K)').child()
        self.feed_bytes = r.counter(
            'paddle_tpu_executor_feed_bytes_total',
            'bytes of feed data staged to the device').child()
        self.donated_state_bytes = r.counter(
            'paddle_tpu_executor_donated_state_bytes_total',
            'bytes of persistable state donated into compiled '
            'steps').child()
        self.graph_opt_ops_eliminated = r.counter(
            'paddle_tpu_graph_opt_ops_eliminated_total',
            'ops removed from traced programs by the graph-opt pass '
            'pipeline (DCE + constant folding + CSE), summed over '
            'plan builds').child()
        self.graph_opt_seconds = r.histogram(
            'paddle_tpu_graph_opt_seconds',
            'wall time of one graph-opt pipeline run (per plan-cache '
            'miss)', buckets=_obs.DEFAULT_COMPILE_BUCKETS).child()
        self.amp_ops_lowered = r.counter(
            'paddle_tpu_amp_ops_lowered_total',
            'ops rewritten to low-precision compute by the AMP pass '
            '(PADDLE_TPU_AMP), summed over plan builds').child()
        self.amp_skipped_steps = r.counter(
            'paddle_tpu_amp_skipped_steps_total',
            'training steps skipped by dynamic loss scaling '
            '(non-finite gradients; f16 mode only)').child()
        self.donated_feed_bytes = r.counter(
            'paddle_tpu_executor_donated_feed_bytes_total',
            'bytes of executor-staged feed buffers donated into '
            'compiled steps (XLA reuses them for the short-lived '
            'intermediates the donation analysis reports)').child()
        self.feed_blocking_puts = r.counter(
            'paddle_tpu_executor_feed_blocking_puts_total',
            'per-step feed staging operations on the run_steps '
            'critical path (device idle while the host stacks/'
            'transfers); with PADDLE_TPU_DEVICE_PREFETCH only the '
            'pipeline-priming chunk counts here').child()
        self.feed_prefetched_puts = r.counter(
            'paddle_tpu_executor_feed_prefetched_puts_total',
            'per-step feed chunks staged by the device-prefetch '
            'pipeline while a previous chunk was executing '
            '(overlapped, off the critical path)').child()
        self.feed_prefetched_bytes = r.counter(
            'paddle_tpu_executor_feed_prefetched_bytes_total',
            'bytes staged by the device-prefetch pipeline while a '
            'previous chunk was executing').child()
        self.ir_verify_failures = r.counter(
            'paddle_tpu_ir_verify_failures_total',
            'plan builds rejected by the static IR verifier '
            '(PADDLE_TPU_VERIFY_IR, transpiler/verify.py) — each one '
            'is a pass bug or a malformed program caught before '
            'tracing').child()
        self.collective_modeled_bytes = r.counter(
            'paddle_tpu_executor_collective_modeled_bytes_total',
            'modeled per-device ICI bytes moved by the collectives of '
            'executed SPMD steps (PADDLE_TPU_MESH; ring closed forms '
            'from the sharding pass + cost model), summed over steps '
            '— the communication half of the roofline').child()
        self.collectives_modeled = r.counter(
            'paddle_tpu_executor_collectives_modeled_total',
            'modeled collective operations (gradient allreduce, fsdp '
            'reduce-scatter/all-gather) executed inside SPMD steps, '
            'summed over steps').child()
        self.collective_exposed_bytes = r.counter(
            'paddle_tpu_executor_collective_exposed_bytes_total',
            'modeled ICI bytes NOT hidden behind compute: the exposed '
            'remainder of the overlap schedule (gradient-bucket '
            'allreduces past the backward+update window, pipeline '
            'ppermute sends past their stage tick), summed over steps '
            '— the serial communication tax the overlap pass could '
            'not remove').child()
        self.collective_overlapped_bytes = r.counter(
            'paddle_tpu_executor_collective_overlapped_bytes_total',
            'modeled ICI bytes hidden behind concurrent compute by '
            'the collective-overlap schedule '
            '(PADDLE_TPU_OVERLAP / transpiler/overlap.py), summed '
            'over steps').child()


_exec_metrics = None


def _em():
    global _exec_metrics
    if _exec_metrics is None:
        _exec_metrics = _ExecutorMetrics()
    return _exec_metrics


def _nbytes(arrays):
    """Total nbytes over a {name: array} dict (jax and numpy arrays both
    expose .nbytes; anything else counts 0)."""
    return sum(getattr(v, 'nbytes', 0) for v in arrays.values())


def _feed_aval_strs(feed_arrays):
    """The jax donation warning names each unusable buffer as
    ShapedArray(<dtype>[<d0>,<d1>,...]); precompute those strings for
    the donated feed buffers so _quiet_unused_donation can tell an
    expected feed-donation miss apart from a state-donation one."""
    out = set()
    for v in feed_arrays.values():
        dt = np.dtype(v.dtype).name
        out.add('ShapedArray(%s[%s])'
                % (dt, ','.join(str(d) for d in v.shape)))
    return out


@contextlib.contextmanager
def _quiet_unused_donation(feed_arrays=None):
    """Silence jax's "Some donated buffers were not usable" warning for
    one compiling invocation of a FEED-donating plan.  Donated feed
    buffers are executor-staged host data that is dead after the step —
    donating them is an ownership statement (and free aliasing headroom
    where an output happens to match); a feed shape rarely matches an
    output, so the warning is expected there and would fire on every
    fresh compile.  The warning is swallowed ONLY when every buffer it
    names matches a donated feed aval (best-effort: a state table that
    shares a feed's shape+dtype is indistinguishable in the message);
    anything else re-emits, because an unusable STATE donation is a
    real peak-HBM regression worth hearing about.  State-donating-only
    plans (feed_arrays falsy) are never filtered."""
    if not feed_arrays:
        yield
        return
    allowed = _feed_aval_strs(feed_arrays)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter('always')
        yield
    for w in caught:
        msg = str(w.message)
        if msg.startswith('Some donated buffers were not usable'):
            named = set(re.findall(r'ShapedArray\([^)]*\)',
                                   msg.split('\n', 1)[0]))
            if named and named <= allowed:
                continue
        warnings.warn_explicit(w.message, w.category, w.filename,
                               w.lineno)


def _shard_put(v, sh):
    """Place one value with a NamedSharding, passing through values
    already holding it (the steady-state no-op for device-resident
    state under a stable mesh)."""
    if isinstance(v, jax.Array) and getattr(v, 'sharding', None) == sh:
        return v
    return jax.device_put(v, sh)


def _pass_plan_key(program):
    """The composite pass-configuration component of every plan cache
    key — graph-opt level (with the memory_optimize floor), AMP mode
    (+ loss-scale knobs), verify mode, and the sparse apply
    lowering, all re-read per build so a flag flip is never served a
    stale trace.  ONE code path (transpiler/pass_manager.plan_key)
    feeds both the run and run_steps keys."""
    from ..transpiler import pass_manager
    return pass_manager.plan_key(program)


class ExecutionContext(object):
    """Per-trace context handed to op compute functions: PRNG derivation,
    access to the interpreter for ops that carry sub-blocks, and the
    enclosing program/block."""

    def __init__(self, program, block, rng_key, uid_prefix=0,
                 backend=None):
        self.program = program
        self.block = block
        self.rng_key = rng_key
        self.uid_prefix = uid_prefix
        self.op_index = 0
        # platform the enclosing jit targets ('tpu'/'cpu'): ops that pick
        # between a Pallas kernel and a lax fallback must key off THIS,
        # not jax.default_backend() — a CPUPlace run on a TPU-attached
        # host would otherwise compile Pallas kernels for CPU
        self.backend = backend or jax.default_backend()

    def rng(self, extra=0):
        """Deterministic per-op PRNG key: stable under the autodiff replay
        of forward ops (keys derive from op position, not call order)."""
        k = jax.random.fold_in(self.rng_key, self.uid_prefix)
        k = jax.random.fold_in(k, self.block.idx)
        k = jax.random.fold_in(k, self.op_index)
        if extra:
            k = jax.random.fold_in(k, extra)
        return k

    def sub_context(self, block):
        sub = ExecutionContext(self.program, block, self.rng_key,
                               self.uid_prefix + 1000,
                               backend=self.backend)
        return sub

    def run_block(self, block_idx, env):
        """Interpret a sub-block in-place over `env` (used by control-flow
        ops like conditional_block)."""
        block = self.program.blocks[block_idx]
        ctx = self.sub_context(block)
        _run_ops(block.ops, env, ctx)
        return env


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _clip_cotangent(x, lo, hi):
    """Identity whose backward clips the incoming gradient — the TPU-native
    realisation of fluid's ErrorClipByValue (clip.py error_clip_callback):
    instead of weaving a clip op into the grad-op chain, the clip rides the
    VJP of the var it guards."""
    return x


def _cc_fwd(x, lo, hi):
    return x, None


def _cc_bwd(lo, hi, _res, g):
    return (jnp.clip(g, lo, hi),)


_clip_cotangent.defvjp(_cc_fwd, _cc_bwd)


# optimizers with a true row-wise SelectedRows rule (ops/optim_ops.py
# sparse branches): a sentinel-gated grad row-set leaves their outputs
# bitwise-unchanged, so AMP skip-step can gate on the ids alone
_ROWWISE_SPARSE_OPS = frozenset({'sgd', 'adagrad', 'adam'})


def _run_one(op, env, ctx, op_index, frozen=()):
    impl = get_op_impl(op.type)
    ins = {}
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            if n not in env:
                raise KeyError(
                    "op %s reads %r which has no value; feed it, run the "
                    "startup program, or check op ordering" % (op.type, n))
            vals.append(env[n])
        ins[slot] = vals
    if impl.needs_env:
        ins['__env__'] = [env]
    # AMP f16 skip-step: an optimize-role op stamped with `amp_gate_var`
    # (transpiler/amp.py) keeps every output's OLD value when the
    # gradients of this step were non-finite — params, moments, and
    # counters all stand still, the textbook loss-scaling skip.
    # Dense updates gate on the outputs (jnp.where fuses into the
    # elementwise update for free).  SelectedRows grads gate on the IDS
    # instead: rows swap to the >=height sentinel on overflow (the PR-4
    # ragged-padding contract — the Pallas kernel skips them, XLA drops
    # the oob scatter), so no touched row exists and the donated
    # in-place table update stays in place; a full-table output where
    # would force XLA to keep the pre-update table live (copy + select,
    # O(table height)) on EVERY step, reverting the row-sparse win.
    gate = op.attrs.get('amp_gate_var')
    gate_val = olds = None
    if gate is not None and gate in env:
        from .selected_rows import SelectedRows
        gate_val = jnp.reshape(env[gate], ()).astype(bool)
        sparse_gated = False
        for slot, vals in list(ins.items()):
            if slot == '__env__':
                continue
            gated_vals = []
            for v in vals:
                if isinstance(v, SelectedRows):
                    v = SelectedRows(
                        jnp.where(gate_val, v.height, v.rows),
                        v.values, v.height)
                    sparse_gated = True
                gated_vals.append(v)
            ins[slot] = gated_vals
        if not (sparse_gated and op.type in _ROWWISE_SPARSE_OPS):
            olds = {n: env[n] for n in op.output_arg_names if n in env}
        # row-wise sparse ops need no output where: with every row at
        # the sentinel, the kernel/scatter writes nothing and the
        # outputs already equal the old state bitwise.  Optimizers that
        # DENSIFY sparse grads (momentum & co) still decay their state
        # on a zero grad, so they keep the output where — they pay the
        # O(height) pass either way.
    # per-op PRNG keys derive from the op's position; an op that survived
    # the graph-opt pipeline carries its PRE-pass position as `op_seq`,
    # so eliminating ops never shifts another op's RNG stream (dropout
    # masks are bitwise-identical with and without optimization)
    ctx.op_index = op.attrs.get('op_seq', op_index)
    outs = impl.compute(ctx, ins, op.attrs) or {}
    if '__env_update__' in outs:
        env.update(outs.pop('__env_update__')[0])
    for slot, names in op.outputs.items():
        vals = outs.get(slot, [])
        for n, v in zip(names, vals):
            if v is None:
                continue
            if n in frozen:
                # `n` is a differentiation point (calc_gradient wrt an
                # intermediate var): keep the injected leaf value so grads
                # attach to it rather than to its producer.
                continue
            if olds is not None and n in olds:
                v = jnp.where(gate_val, olds[n], v)
            try:
                var = ctx.block.var_recursive(n)
                if var.stop_gradient and not var.is_data:
                    v = jax.lax.stop_gradient(v)
                ec = getattr(var, 'error_clip', None)
                if ec is not None:
                    v = _clip_cotangent(v, float(ec.min), float(ec.max))
            except KeyError:
                pass
            env[n] = v


def _op_role(op):
    return op.attrs.get('op_role', 'forward')


def _tainted_slice(ops, k, param_names, ad_idxs):
    """Forward-role ops before index k on the dependency path from
    `param_names` to anything downstream (forward taint propagation)."""
    tainted = set(param_names)
    picked = []
    for j in range(k):
        if j in ad_idxs or _op_role(ops[j]) != 'forward':
            continue
        if set(ops[j].input_arg_names) & tainted:
            picked.append((j, ops[j]))
            tainted.update(ops[j].output_arg_names)
    return picked


def _run_ops(ops, env, ctx):
    """Interpret a list of ops with fluid program-order semantics.

    `autodiff` ops (appended by core/backward.py) replace the reference's
    per-op grad weaving (framework/backward.cc) with jax.value_and_grad:

    - The FIRST autodiff executes every preceding forward-role op inside its
      closure (one fused fwd+bwd HLO — the hot path for normal training) and
      publishes their outputs.  Exact, because no optimizer update precedes
      it.
    - LATER autodiff ops (multi-minimize programs: GAN, multi-loss) re-run
      only the subgraph tainted by their params, from a snapshot in which
      any already-applied optimizer updates are rolled back — so every
      gradient is taken at the values the single program-order forward saw,
      matching the reference executor exactly.
    - backward/optimize-role ops (grad clip, regularizers, sgd/adam, LR
      schedules) run at top level in program order.
    """
    ad_idxs = [i for i, op in enumerate(ops) if op.type == 'autodiff']
    first_ad = ad_idxs[0] if ad_idxs else None
    c1 = set()
    if first_ad is not None:
        c1 = {j for j in range(first_ad)
              if j not in ad_idxs and _op_role(ops[j]) == 'forward'}
    pre_update_vals = {}  # param name -> value before its first update
    for i, op in enumerate(ops):
        if op.type == 'autodiff':
            if i == first_ad:
                fwd = [(j, ops[j]) for j in sorted(c1)]
                _run_autodiff(op, fwd, env, ctx, {}, publish=True)
            else:
                fwd = _tainted_slice(ops, i, op.attrs['param_names'],
                                     set(ad_idxs))
                _run_autodiff(op, fwd, env, ctx, pre_update_vals,
                              publish=False)
        elif i in c1:
            continue  # runs inside the first autodiff closure
        else:
            if _op_role(op) == 'optimize':
                for n in op.output_arg_names:
                    if n in env and n not in pre_update_vals:
                        # (pre-update value, program index of the update):
                        # a later autodiff rolls `n` back only for forward
                        # ops that originally ran before this index
                        pre_update_vals[n] = (env[n], i)
            _run_one(op, env, ctx, i)


def _run_autodiff(ad_op, fwd_ops, env, ctx, pre_update_vals, publish):
    """fwd_ops: [(original_index, op)] forward slice for this autodiff."""
    param_names = list(ad_op.attrs['param_names'])
    grad_names = list(ad_op.attrs['grad_names'])
    loss_name = ad_op.attrs['loss_name']
    loss_scale = ad_op.attrs.get('loss_scale', 1.0)
    # AMP dynamic loss scaling (transpiler/amp.py f16 mode): the scale
    # is a persistable var, so it updates per step and rides the
    # run_steps scan carry; check_finite_and_unscale divides it back out
    # of the grads downstream.
    ls_var = ad_op.attrs.get('loss_scale_var')

    captured = dict(env)
    # Keep the POST-update value only when every forward op in this slice
    # that reads the var originally ran after its update (ops built after
    # a minimize() see the updated value in the reference executor too).
    # A slice whose reads straddle the update has no single consistent
    # value; we choose the pre-update one so gradients attach to the
    # values the pre-update forward saw (the common multi-loss pattern).
    for n, (val, upd_idx) in pre_update_vals.items():
        read_idxs = [j for j, op in fwd_ops if n in op.input_arg_names]
        if not read_idxs or min(read_idxs) < upd_idx:
            captured[n] = val
    written = set()
    for _, op in fwd_ops:
        written.update(op.output_arg_names)
    frozen = frozenset(set(param_names) & written)

    if any(n not in captured for n in param_names):
        # calc_gradient wrt an intermediate var: materialise its value with
        # one plain forward pass (XLA CSEs this against the grad pass).
        env_pre = dict(captured)
        for j, op in fwd_ops:
            _run_one(op, env_pre, ctx, j)
        for n in param_names:
            if n not in captured:
                captured[n] = env_pre[n]
                env[n] = env_pre[n]
    params = {n: captured[n] for n in param_names}

    def f(ps):
        env2 = dict(captured)
        env2.update(ps)
        # fluid's error_clip also guards leaf vars (fed data / Parameters):
        # they enter the VJP here as leaves, so the clip must ride their
        # injected value, not a producing op's output (there is none).
        for n in param_names:
            try:
                var = ctx.block.var_recursive(n)
            except KeyError:
                continue
            ec = getattr(var, 'error_clip', None)
            if ec is not None:
                env2[n] = _clip_cotangent(env2[n], float(ec.min),
                                          float(ec.max))
        for j, op in fwd_ops:
            _run_one(op, env2, ctx, j, frozen)
        loss = env2[loss_name]
        loss = jnp.sum(loss.astype(jnp.float32)) * loss_scale
        if ls_var is not None and ls_var in env2:
            loss = loss * jnp.reshape(
                jnp.asarray(env2[ls_var]).astype(jnp.float32), ())
        return loss, env2

    from ..transpiler.memory_optimize import get_remat_policy
    remat = get_remat_policy(ctx.program)
    if remat is not None:
        # P14 memory_optimize: backward recomputes activations instead of
        # keeping them live across the fused fwd+bwd
        f = remat(f)
    (_, env_fwd), grads = jax.value_and_grad(f, has_aux=True)(params)
    if publish:
        for n in written:
            if n in env_fwd:
                env[n] = env_fwd[n]
        if loss_name not in written and loss_name in env_fwd:
            env[loss_name] = env_fwd[loss_name]
    # overlap_collectives lowering: tie each bucket's gradients together
    # with one optimization_barrier — an identity (bitwise-same values,
    # donation-safe) that hands XLA's latency-hiding scheduler a
    # per-bucket dependency cut, so the bucket's allreduce/
    # reduce-scatter issues when ITS grads retire instead of after the
    # whole backward.  No attr (pass off / no mesh) -> path untouched.
    buckets = ad_op.attrs.get('overlap_buckets')
    if buckets:
        grad_to_param = dict(zip(grad_names, param_names))
        for bucket in buckets:
            pns = [grad_to_param[gn] for gn in bucket
                   if grad_to_param.get(gn) in grads]
            if not pns:
                continue
            vals = jax.lax.optimization_barrier(
                tuple(grads[pn] for pn in pns))
            for pn, v in zip(pns, vals):
                grads[pn] = v
    for pn, gn in zip(param_names, grad_names):
        g = grads[pn]
        env[gn] = g.astype(params[pn].dtype) if hasattr(g, 'astype') else g


def _to_feed_arrays(name, value, var):
    """Convert one feed entry to {name: array} (+ companion lengths for
    ragged feeds)."""
    out = {}
    if isinstance(value, jax.Array):
        # Already device-resident (staged by the caller or a prefetch
        # reader): pass through untouched — np.asarray here would drag it
        # back to host and re-upload it every step.
        out[name] = value
        return out
    if isinstance(value, LoDTensor):
        out[name] = _np_to_device_dtype(value.padded(), var)
        if value.is_ragged():
            out[name + LEN_SUFFIX] = np.asarray(value.lengths(),
                                                dtype=np.int32)
        return out
    if isinstance(value, tuple) and len(value) == 2 and var is not None \
            and var.lod_level > 0:
        data, lengths = value
        out[name] = _np_to_device_dtype(np.asarray(data), var)
        out[name + LEN_SUFFIX] = np.asarray(lengths, dtype=np.int32)
        return out
    out[name] = _np_to_device_dtype(np.asarray(value), var)
    return out


def _np_to_device_dtype(arr, var):
    """Narrow 64-bit host arrays to the 32-bit types TPUs run (x64 is
    disabled); honour the declared var dtype otherwise."""
    if arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    elif arr.dtype == np.int64:
        arr = arr.astype(np.int32)
    elif arr.dtype == np.uint64:
        arr = arr.astype(np.uint32)
    if var is not None and datatypes.is_float_dtype(var.dtype) and \
            arr.dtype.kind in 'fiu':
        want = datatypes.as_numpy_dtype(var.dtype)
        if want in (np.float64,):
            want = np.float32
        arr = arr.astype(want)
    return arr


def _convert_feed(block, feed):
    """One feed dict → {column name: array} through _to_feed_arrays
    (which may add companion columns like the LEN_SUFFIX lengths).
    The single home of that expansion for run(), run_steps and the
    chunked prefetch pre-validation — the paths must agree on the
    column set or a feed accepted by one is rejected by another."""
    fa = {}
    for name, value in feed.items():
        fa.update(_to_feed_arrays(name, value, block.vars.get(name)))
    return fa


def _feed_shape_error(name, shapes):
    """The run_steps shape contract, stated once for both the one-shot
    stack and the chunked pre-validation."""
    return ValueError(
        "run_steps feeds must agree in shape across steps (static "
        "shapes — one compiled scan), but %r varies: %s.  Pad "
        "batches to a common shape or fall back to per-step run()"
        % (name, sorted(shapes)))


def _feed_column_error(step, got, want):
    """The run_steps column-set contract (e.g. a LEN_SUFFIX companion
    fed in only SOME steps), stated once for both the one-shot stack
    and the chunked pre-validation."""
    return ValueError(
        "run_steps feeds must produce one column set across steps; "
        "step %d yields %s vs %s" % (step, sorted(got), sorted(want)))


def _stack_feed_col(name, vals):
    """Stack one feed column across K steps; the scan needs identical
    shapes per step (XLA static shapes), so say which feed broke the
    contract instead of letting np.stack fail opaquely."""
    shapes = {np.shape(v) for v in vals}
    if len(shapes) > 1:
        raise _feed_shape_error(name, shapes)
    return np.stack(vals)


def make_multi_step_fn(raw_fn, stacked, k):
    """The K-step lax.scan over a traced step function — the single home
    of the multi-step semantics shared by Executor.run_steps and
    parallel.api.run_steps_sharded: persistable state is the carry, the
    per-step PRNG folds (key0, global_step) exactly like K single runs,
    fetches stack along a leading K axis, and out-only state (written,
    not carried in) surfaces as its last-step value.  Out-only vars ride
    the carry too — seeded from zeros placeholders discovered with
    eval_shape at trace time — so each holds ONE buffer on device rather
    than a [K, ...] stack that keeps K-1 dead copies live in HBM."""
    def multi_fn(feed_one, xs_feeds, state_rw, state_ro, key0, t0):
        f0 = (jax.tree_util.tree_map(lambda a: a[0], xs_feeds)
              if stacked else feed_one)
        _, state_shape = jax.eval_shape(raw_fn, f0, state_rw, state_ro,
                                        key0)
        extra0 = {n: jnp.zeros(s.shape, s.dtype)
                  for n, s in state_shape.items() if n not in state_rw}

        def body(carry, xs_t):
            rw, extra, t = carry
            f_t = xs_t if stacked else feed_one
            key = jax.random.fold_in(key0, t)
            fetches, new_state = raw_fn(f_t, rw, state_ro, key)
            new_rw = {n: new_state[n] for n in rw if n in new_state}
            new_extra = {n: v for n, v in new_state.items()
                         if n not in new_rw}
            return (new_rw, new_extra, t + 1), tuple(fetches)

        (rw_f, extra_f, _), ys = jax.lax.scan(
            body, (state_rw, extra0, t0), xs_feeds,
            length=None if stacked else k)
        return ys, rw_f, extra_f

    return multi_fn


class Executor(object):
    def __init__(self, place=None):
        if isinstance(place, (list, tuple)):
            place = place[0]
        self.place = place if place is not None else default_place()
        enable_compile_cache()
        self._cache = {}
        self._plan_reports = {}  # plan key -> graph-opt report
        self._mesh_op_cache = {}
        self._step = 0
        self._plan_fresh = False  # set by _get_plan, read by run()
        # graph-opt report of the most recently looked-up plan (tracked
        # per plan key so cache hits restore the right one; None when
        # that plan was built with the pipeline off) — see
        # transpiler/passes.run_pipeline
        self.last_graph_opt_report = None
        # unified step report of the most recent run_steps call: the
        # measured phase walls (feed_s / feed_overlap_s / update_s /
        # compute_s residual, summing to ~wall_s) joined with the
        # static cost model's per-phase FLOPs/bytes under 'phases' —
        # the numbers behind benchmarks/common.py's
        # where-did-the-time-go table and every bench row's MFU
        self.last_step_report = None

    @property
    def last_run_steps_report(self):
        """Deprecated alias (one release): the run_steps breakdown now
        lives in ``last_step_report`` with the same keys (feed_s /
        feed_overlap_s / update_s / chunks) plus the timeline-derived
        wall/compute residuals and the cost-model phase annotations."""
        return self.last_step_report

    # ------------------------------------------------------------------
    def run(self,
            program=None,
            feed=None,
            fetch_list=None,
            feed_var_name='feed',
            fetch_var_name='fetch',
            scope=None,
            return_numpy=True,
            use_program_cache=True):
        try:
            return self._run_impl(program, feed, fetch_list,
                                  feed_var_name, fetch_var_name, scope,
                                  return_numpy, use_program_cache)
        except BaseException:
            # flight-recorder forensics (PADDLE_TPU_TRACE_DUMP_ON_ERROR):
            # flush the last-N-steps timeline ring before re-raising —
            # maybe_dump_on_error never raises and is a cached-bool
            # no-op when disarmed
            _tlm.maybe_dump_on_error()
            raise

    def _run_impl(self, program, feed, fetch_list, feed_var_name,
                  fetch_var_name, scope, return_numpy,
                  use_program_cache):
        if program is None:
            program = default_main_program()
        if not isinstance(program, Program):
            raise TypeError("Executor requires a Program, got %r" %
                            type(program))
        if scope is None:
            scope = global_scope()
        feed = feed or {}
        fetch_list = fetch_list or []
        fetch_names = [
            f.name if isinstance(f, Variable) else str(f) for f in fetch_list
        ]

        block = program.global_block()

        # PADDLE_TPU_TUNE=cached: persisted tuner winners apply here,
        # before mesh resolution and plan-key computation (one dict
        # lookup when tuning is off)
        _maybe_apply_tuned(program, self.place)

        # flight recorder (observability/timeline.py): one cached-bool
        # check when disarmed, phase events on the shared ring when
        # PADDLE_TPU_TRACE_DIR / _TRACE_DUMP_ON_ERROR armed it
        tl = _tlm.ring_if_armed()
        mesh, dev = self._mesh_and_dev(program)
        spmd = self._spmd_mesh(program) if mesh is None else None
        if tl is not None:
            tl.set_step(self._step)
            t_f0 = time.perf_counter()
        feed_arrays = _convert_feed(block, feed)
        # every buffer the executor stages itself this call (host data
        # in, device_put here) is dead the moment the step consumes it
        # — donate it so XLA reuses the memory for step intermediates.
        # This holds under a mesh too (the staging device_put below
        # creates executor-owned replicated/sharded buffers); only a
        # caller-staged jax.Array (where re-placement may alias the
        # caller's buffer) stays caller-owned and must NOT be donated.
        feed_donate = (bool(feed_arrays) and
                       not any(isinstance(v, jax.Array)
                               for v in feed_arrays.values()))
        if spmd is None:
            feed_arrays = self._stage_feed(feed_arrays, mesh, dev)
        # host-side feed work so far (convert + non-mesh staging);
        # the timeline event must NOT swallow the _get_plan call below
        # (trace + XLA compile) into the feed phase.  Clock reads stay
        # behind the armed guard (the disarmed zero-cost contract)
        t_conv = (time.perf_counter() - t_f0) if tl is not None else 0.0

        plan = self._get_plan(program, block, scope, feed_arrays,
                              tuple(fetch_names), use_program_cache,
                              mesh=mesh, feed_donate=feed_donate,
                              spmd_mesh=spmd)
        (fn, _raw, state_rw_names, state_ro_names, smeta) = plan

        t_s0 = time.perf_counter() if tl is not None else 0.0
        if smeta is not None:
            # sharded feed staging: each column lands on the mesh
            # already split per the propagated plan (batch over dp/
            # fsdp), so the pjit-lowered step starts from ICI-resident
            # shards instead of re-scattering a replicated copy
            feed_arrays = {n: _shard_put(v, smeta['feed_sh'][n])
                           for n, v in feed_arrays.items()}
        if tl is not None and feed_arrays:
            tl.record('executor.feed_stage', 'feed', t0=t_f0,
                      dur=t_conv + (time.perf_counter() - t_s0),
                      args={'bytes': _nbytes(feed_arrays),
                            'donated': feed_donate})

        if smeta is not None:
            state_rw = self._stage_state_spmd(scope, state_rw_names,
                                              smeta['rw_sh'],
                                              smeta.get('pads'))
            state_ro = self._stage_state_spmd(scope, state_ro_names,
                                              smeta['ro_sh'],
                                              smeta.get('pads'))
            rng_key = jax.device_put(self._rng_key(program),
                                     smeta['key_sh'])
        else:
            state_rw = self._stage_state(
                {n: scope.get(n) for n in state_rw_names}, mesh, dev)
            state_ro = self._stage_state(
                {n: scope.get(n) for n in state_ro_names}, mesh, dev)
            rng_key = jax.device_put(self._rng_key(program), dev)
        self._step += 1

        em = _em() if _obs.enabled() else None
        if em is not None:
            em.runs.inc()
            em.steps.inc()
            em.feed_bytes.inc(_nbytes(feed_arrays))
            em.donated_state_bytes.inc(_nbytes(state_rw))
            if feed_donate:
                em.donated_feed_bytes.inc(_nbytes(feed_arrays))

        # the span covers dispatch + scope update + (for return_numpy)
        # the host sync, so its histogram reads as per-call latency.
        # The donation-warning filter only arms on the compiling
        # invocation — the warning can only fire there, and
        # warnings.catch_warnings mutates process-global state, which
        # the cached steady-state dispatches must stay clear of
        fresh = self._plan_fresh
        self._plan_fresh = False
        with _obs.span('executor.run'), \
                _quiet_unused_donation(
                    feed_arrays if (feed_donate and fresh) else None):
            if tl is not None:
                t_d0 = time.perf_counter()
            if em is not None and fresh:
                # first invocation of a fresh plan: jit compiles
                # synchronously inside this call.  The inner span also
                # lands "executor.compile" on any running XLA trace
                with _obs.span('executor.compile'):
                    t0 = time.perf_counter()
                    fetches, new_state = fn(feed_arrays, state_rw,
                                            state_ro, rng_key)
                    em.compile_seconds.observe(time.perf_counter() - t0)
                em.compiles.inc()
            else:
                fetches, new_state = fn(feed_arrays, state_rw,
                                        state_ro, rng_key)
            if tl is not None:
                tl.record('executor.compile' if fresh
                          else 'executor.dispatch',
                          'compile' if fresh else 'compute', t0=t_d0,
                          dur=time.perf_counter() - t_d0,
                          args={'donated_state_bytes':
                                _nbytes(state_rw)})
                ms = _tlm.device_memory_stats(self._memory_device())
                if ms and ms.get('bytes_in_use') is not None:
                    tl.counter_sample('paddle_tpu.device_bytes_in_use',
                                      ms['bytes_in_use'])
            if smeta is not None:
                self._note_collectives(tl, 1)
            for n, v in new_state.items():
                scope.set(n, v)
            if return_numpy:
                fetches = [np.asarray(v) for v in fetches]
                if em is not None:
                    self._note_amp_skips(new_state, scope)
        return fetches

    def _note_amp_skips(self, new_state, scope):
        """Surface the on-device cumulative AMP skip counter (f16
        dynamic loss scaling) as a host-side metric.  Called only on
        return_numpy paths — the step already synced, so the [1] scalar
        read is a copy of a ready buffer, never a pipeline stall; async
        (return_numpy=False) callers catch up on their next synced call
        because the counter is cumulative.  The seen-watermark lives ON
        the scope (the counter is scope state): it dies with the scope,
        and two executors draining the same scope — e.g. one recreated
        after a checkpoint reload — share it instead of each re-adding
        the full historical count to the process-global metric."""
        from ..transpiler.amp import SKIPPED_STEPS_VAR
        v = new_state.get(SKIPPED_STEPS_VAR)
        if v is None:
            return
        cur = int(np.asarray(v).reshape(-1)[0])
        seen = getattr(scope, '_amp_skip_seen', 0)
        if cur > seen:
            _em().amp_skipped_steps.inc(cur - seen)
        scope._amp_skip_seen = cur

    # ------------------------------------------------------------------
    def _mesh_and_dev(self, program):
        """(mesh, placement) for a program: a program with a parallel_do
        op lowers to a shard_map over the active mesh; its jit then
        spans the mesh's devices, so every argument must stage
        replicated on the mesh (the reference analogue: the host drives
        the program, only parallel_do fans out to places).  The single
        home of the mesh-staging rule shared by run() and run_steps()."""
        mesh = self._active_mesh(program)
        dev = self.place.jax_device()
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            dev = NamedSharding(mesh, PartitionSpec())
        return mesh, dev

    @staticmethod
    def _stage_feed(feed_arrays, mesh, dev):
        """Commit feeds explicitly: an async device_put is ~10x faster
        than letting jit transfer numpy args in-line, and committed
        inputs pin the computation to the place without a
        jax.default_device context (which defeats jit's C++ fast-path
        dispatch).
        Already-staged jax.Arrays pass through untouched unless a mesh
        requires re-placement."""
        return {k: (v if isinstance(v, jax.Array) and mesh is None
                    else jax.device_put(v, dev))
                for k, v in feed_arrays.items()}

    @staticmethod
    def _stage_state(state, mesh, dev):
        if mesh is None:
            return state
        return {n: jax.device_put(v, dev) for n, v in state.items()}

    @staticmethod
    def _stage_state_spmd(scope, names, shardings, pads=None):
        """Stage persistable state per the plan's NamedShardings — the
        ONE staging rule all three SPMD call sites (run, run_steps,
        the prefetch path) share; steady-state re-stages are no-ops
        via the _shard_put pass-through.  ``pads`` (embed plans) maps a
        row-sharded table/accumulator to its sentinel-padded height:
        the first stage pads the stored [V, D] value to [V_pad, D]
        with zero rows (never gathered, never updated — the engine's
        buckets stop at the TRUE height), after which the padded
        buffer round-trips through the donated carry untouched."""
        out = {}
        for n in names:
            v = scope.get(n)
            padded = (pads or {}).get(n)
            if padded and getattr(v, 'ndim', 0) >= 1 and \
                    int(v.shape[0]) < int(padded):
                v = jnp.asarray(v)
                fill = jnp.zeros((int(padded) - int(v.shape[0]),)
                                 + tuple(v.shape[1:]), v.dtype)
                v = jnp.concatenate([v, fill])
            out[n] = _shard_put(v, shardings[n])
        return out

    def _spmd_mesh(self, program):
        """The PADDLE_TPU_MESH mesh for SPMD-lowering this program's
        whole train step, or None: the flag must parse to axes, and a
        program carrying its own parallel_do distribution keeps the
        explicit shard_map path (one distribution mechanism per
        program).  Mesh construction/caching lives in
        distributed/mesh_flag.py; the Mesh object participates in plan
        keys (its identity is stable per normalized spec)."""
        from ..distributed import mesh_flag
        axes = mesh_flag.mesh_axes_from_flag()
        if axes is None:
            return None
        pp_size = int(dict(axes).get('pp', 1))
        if pp_size > 1:
            # pp shards TIME, not tensors: a pipeline axis cannot be
            # lowered as one pjit program — it needs the 1F1B
            # schedule's per-stage branches and ppermute transfers.
            # Only TRAIN steps (programs carrying an autodiff op) are
            # refused; startup init and plain forwards run replicated
            # over the pipeline, i.e. with the time axis dropped
            if any(op.type == 'autodiff'
                   for b in program.blocks for op in b.ops):
                raise RuntimeError(
                    'PADDLE_TPU_MESH declares a pipeline axis '
                    '(pp=%d), which the single-program SPMD executor '
                    'cannot lower for a train step.  Route the '
                    'program through the 1F1B engine instead: '
                    'paddle_tpu.distributed.pipeline.from_mesh('
                    'program, ...) cuts stages at annotate_pp_cut() '
                    'boundaries and schedules microbatches — or drop '
                    'the pp axis (e.g. PADDLE_TPU_MESH=dp%d) to stay '
                    'on the plain SPMD path.' % (pp_size, pp_size))
            axes = tuple((n, s) for n, s in axes if n != 'pp')
            if not any(int(s) > 1 for _, s in axes):
                return None
        key = (program._uid, program.version)
        has_pdo = self._mesh_op_cache.get(key)
        if has_pdo is None:
            has_pdo = any(op.type == 'parallel_do'
                          for b in program.blocks for op in b.ops)
            self._mesh_op_cache[key] = has_pdo
        if has_pdo:
            return None
        return mesh_flag.mesh_for(axes)

    def _build_shard_meta(self, prog, mesh, feed_names, rw_names,
                          ro_names):
        """NamedShardings for one plan's jit boundary, from the
        sharding-propagation pass's plan (``prog._sharding_plan``):
        feeds per the propagated feed table (batch over dp/fsdp),
        persistable state per the param plan (fsdp shards params AND
        optimizer accumulators; tp follows the transpiler plan),
        everything unplanned replicated.  (A crashed sharding pass
        never gets here: the pass manager re-raises it.)"""
        from ..distributed import mesh_flag
        plan = getattr(prog, '_sharding_plan', None) or {}
        feeds = plan.get('feeds') or {}
        params = dict(plan.get('params') or {})
        # row-sharded embedding tables with a NON-divisible height:
        # stage sentinel-padded to the engine's shard-divisible height
        # (pads map state name -> padded rows).  Only when the embed
        # lowering actually rewrote the ops — an unlowered plan
        # (PADDLE_TPU_EMBED_SHARD off) must not feed padded tables to a
        # plain lookup, so those names stage replicated instead
        pads = {}
        embed = plan.get('embed') or {}
        for e in embed.values():
            if int(e['padded']) == int(e['height']):
                continue
            for n in e.get('state', ()):
                if plan.get('embed_lowered'):
                    pads[n] = int(e['padded'])
                else:
                    params.pop(n, None)
        return {
            'mesh': mesh,
            'plan': plan,
            'pads': pads,
            'feed_sh': {n: mesh_flag.named_sharding(mesh, feeds.get(n))
                        for n in feed_names},
            'rw_sh': {n: mesh_flag.named_sharding(mesh, params.get(n))
                      for n in rw_names},
            'ro_sh': {n: mesh_flag.named_sharding(mesh, params.get(n))
                      for n in ro_names},
            'key_sh': mesh_flag.named_sharding(mesh, None),
        }

    def _xs_shardings(self, smeta, names):
        """Per-column shardings for the [K, ...]-stacked run_steps
        feed: the per-step spec shifted one dim right (dim0 is the
        scan axis, never sharded)."""
        from ..distributed import mesh_flag
        feeds = smeta['plan'].get('feeds') or {}
        return {n: mesh_flag.named_sharding(
                    smeta['mesh'], (None,) + tuple(feeds.get(n) or ()))
                for n in names}

    def _note_collectives(self, tl, steps, compute_s=None):
        """Attribute the modeled ICI collectives of ``steps`` executed
        SPMD steps: counters (modeled bytes + collective ops) and one
        ``collective``-category timeline event, with an estimated wall
        when PADDLE_TPU_ICI_GBPS names a link bandwidth.  The numbers
        come from the cost model's pricing of the sharding pass's
        collective table, cached per plan in last_graph_opt_report.

        ``compute_s`` is the MEASURED compute wall for the ``steps``
        steps, when the caller has a synced one (run_steps does; the
        async single-step dispatch does not).  The overlap schedule the
        cost model priced at roofline-floor walls is pure arithmetic
        over the stamped bucket descriptors, so it is re-run here with
        every wall scaled by measured/modeled compute — same buckets,
        same serial-channel model, real time base — and the reported
        overlap fraction then describes the step that actually ran
        instead of the optimistic floor.  The fraction lands as a
        Chrome-trace counter series
        (``paddle_tpu.collective_overlap_pct``, 0-100) next to the
        collective event."""
        cost = (self.last_graph_opt_report or {}).get('cost') or {}
        coll = cost.get('collectives')
        if not coll or not coll.get('ici_bytes'):
            return None
        nbytes = int(coll['ici_bytes']) * int(steps)
        nops = len(coll.get('items') or ()) * int(steps)
        sched = coll.get('overlap')
        split = dict(coll.get('bytes') or {})
        frac = sched.get('overlap_fraction') if sched else None
        basis = 'modeled-roofline'
        if sched and sched.get('buckets') and compute_s \
                and compute_s > 0.0:
            modeled = float(coll.get('modeled_compute_s') or 0.0)
            if modeled > 0.0:
                from ..transpiler import cost_model as _cmod
                scale = (float(compute_s) / int(steps)) / modeled
                rerun = _cmod.overlap_schedule(
                    sched['buckets'],
                    float(sched['backward_s']) * scale,
                    float(sched['window_s']) * scale,
                    float(sched['ici_gbps']) * 1e9)
                frac = rerun['overlap_fraction']
                # only the gradient-bucket term is re-priced; every
                # other exposed byte (pp sends, unbucketed items)
                # keeps its static verdict
                exposed = max(0, int(split.get('exposed') or 0)
                              - int(sched.get('exposed_bytes') or 0)
                              + int(rerun['exposed_bytes']))
                split['exposed'] = min(exposed,
                                       int(split.get('total') or 0))
                split['overlapped'] = (int(split.get('total') or 0)
                                       - split['exposed'])
                basis = 'measured-compute'
        if _obs.enabled():
            em = _em()
            em.collective_modeled_bytes.inc(nbytes)
            em.collectives_modeled.inc(nops)
            if split:
                em.collective_exposed_bytes.inc(
                    int(split.get('exposed') or 0) * int(steps))
                em.collective_overlapped_bytes.inc(
                    int(split.get('overlapped') or 0) * int(steps))
        est = None
        from ..flags import FLAGS
        gbps = float(FLAGS.ici_gbps or 0.0)
        if gbps > 0:
            est = nbytes / (gbps * 1e9)
        out = {'ici_bytes': nbytes, 'collectives': nops,
               'est_wall_s': est, 'by_kind': coll.get('by_kind')}
        if frac is not None:
            mgbps = float(sched.get('ici_gbps') or 0.0)
            out['overlap_fraction'] = frac
            out['overlap_basis'] = basis
            out['exposed_bytes_per_step'] = int(split.get('exposed')
                                                or 0)
            out['overlapped_bytes_per_step'] = \
                int(split.get('overlapped') or 0)
            if mgbps > 0:
                out['exposed_est_wall_s'] = \
                    out['exposed_bytes_per_step'] / (mgbps * 1e9)
        if coll.get('pp'):
            out['pp'] = dict(coll['pp'])
        if tl is not None:
            args = {'modeled_ici_bytes': nbytes,
                    'collectives': nops,
                    'by_kind': dict(coll.get('by_kind') or {}),
                    'est_wall_s': est}
            if frac is not None:
                args['overlap_fraction'] = frac
                args['overlap_basis'] = basis
                args['exposed_bytes_per_step'] = \
                    out['exposed_bytes_per_step']
            if frac is not None:
                # counter samples are integer-valued (args['bytes']):
                # the fraction rides as a 0-100 percent series.
                # Sampled BEFORE the record event so the category's
                # latest event stays the attribution record
                tl.counter_sample(
                    'paddle_tpu.collective_overlap_pct',
                    round(frac * 100.0), cat='collective')
            tl.record('executor.collective', 'collective',
                      dur=est or 0.0, args=args)
        return out

    def _active_mesh(self, program):
        """The current mesh_guard mesh, when `program` contains an op
        that fans out over it (parallel_do) and the mesh is >1 device."""
        key = (program._uid, program.version)
        has = self._mesh_op_cache.get(key)
        if has is None:
            has = any(op.type == 'parallel_do'
                      for b in program.blocks for op in b.ops)
            self._mesh_op_cache[key] = has
        if not has:
            return None
        from ..parallel import api as _papi
        mesh = _papi.current_mesh()
        if mesh is None or mesh.devices.size <= 1:
            return None
        return mesh

    def _base_seed(self, program):
        seed = program.random_seed
        return seed if seed else id(self) % (2**31)

    def _rng_key(self, program):
        return jax.random.fold_in(
            jax.random.PRNGKey(self._base_seed(program)), self._step)

    def _analyze_state(self, program, scope, feed_names):
        """Classify persistable vars: `rw` (existing value, written → passed
        in and donated), `ro` (existing value, only read), `out` (written by
        the block — includes first-time writes, e.g. the startup program)."""
        written = set()
        read = set()
        for b in program.blocks:
            for op in b.ops:
                written.update(op.output_arg_names)
                read.update(op.input_arg_names)
        rw, ro, out = [], [], []
        for v in program.list_vars():
            if not v.persistable or v.name in feed_names:
                continue
            if v.name in written:
                out.append(v.name)
            if not scope.has(v.name):
                if v.name in read and v.name not in written:
                    raise RuntimeError(
                        "persistable var %r is read but has no value in "
                        "scope; run the startup program first" % v.name)
                continue
            if v.name in written:
                rw.append(v.name)
            elif v.name in read:
                ro.append(v.name)
        return tuple(sorted(rw)), tuple(sorted(ro)), tuple(sorted(out))

    def _get_plan(self, program, block, scope, feed_arrays, fetch_names,
                  use_cache, mesh=None, feed_donate=False,
                  spmd_mesh=None, mesh_off=False):
        feed_sig = tuple(
            (n, feed_arrays[n].shape, str(feed_arrays[n].dtype))
            for n in sorted(feed_arrays))
        state_rw_names, state_ro_names, state_out_names = \
            self._analyze_state(program, scope, set(feed_arrays))
        # mesh participates: a parallel_do program traced under a mesh
        # embeds that mesh's shard_map in the compiled step, and an
        # SPMD mesh (PADDLE_TPU_MESH) bakes its NamedShardings into the
        # jit boundary.  Scope
        # identity is its monotonic _uid, never id(): ids recycle after
        # gc and would alias a fresh scope's plans with a dead one's.
        # The pass configuration participates as ONE composite component
        # (pass_manager.plan_key): graph-opt level, AMP mode, verify
        # mode, sparse apply lowering, mesh spec — a flip of any
        # must not be
        # served a plan built under the old configuration.
        # feed_donate keys the donation variant: a plan jitted with the
        # feed argument donated must never serve a call whose feed
        # buffers the caller still owns.
        pm_key = _pass_plan_key(program)
        key = (program._uid, program.version, feed_sig, fetch_names,
               state_rw_names, state_ro_names, state_out_names,
               scope._uid, mesh, spmd_mesh, mesh_off, pm_key,
               feed_donate)
        if use_cache and key in self._cache:
            self._plan_fresh = False
            # keep the report describing THIS plan, not whichever plan
            # happened to miss last (one executor can serve many programs)
            self.last_graph_opt_report = self._plan_reports.get(key)
            if _obs.enabled():
                _em().plan_cache_hits.inc()
            return self._cache[key]
        # the caller (run) reads this flag to time the plan's first
        # invocation — the call that pays the XLA compile.  The jitted
        # fn itself stays a bare jax.jit object: wrapping it would break
        # the AOT consumers of compile() (fn.lower().compile()), and the
        # export path would fire a wrapper's timer mid-trace
        self._plan_fresh = True
        if _obs.enabled():
            _em().plan_cache_misses.inc()
        known = set()
        for b in program.blocks:
            known.update(b.vars)
            for op in b.ops:
                known.update(op.output_arg_names)
        for n in fetch_names:
            if n not in known and n not in feed_arrays:
                raise KeyError(
                    "fetch var %r is not produced by any op in the program "
                    "and is not fed" % n)

        # The managed pass pipeline (transpiler/pass_manager.py): graph
        # opt -> AMP -> donation analysis over a COPY of the block,
        # statically verified per PADDLE_TPU_VERIFY_IR.  A crashing
        # graph-opt or analysis pass is skipped inside the manager
        # (reported in last_graph_opt_report['passes']); a crash in a
        # flag-requested rewrite (AMP, sharding, embed, overlap) and a
        # VERIFIER rejection both propagate — the program the flags
        # describe is the one that traces, or nothing does.
        from ..transpiler import pass_manager
        from ..transpiler.verify import IRVerificationError
        try:
            prog, report = pass_manager.run_pipeline(
                program, fetch_names=fetch_names,
                feed_names=tuple(sorted(feed_arrays)),
                # concrete feed shapes seed the cost model's shape
                # propagation (declared -1 batch dims resolve to the
                # real batch, so FLOPs/bytes are exact per step).
                # mesh_off pins the sharding pass OFF for plans that
                # will jit WITHOUT in_shardings (compile()/compile_raw
                # AOT + serving consumers): a sharded analysis report
                # over an unsharded executable would under-state
                # per-device residency by the shard count
                feed_specs={n: (tuple(v.shape), str(v.dtype))
                            for n, v in feed_arrays.items()},
                **({'mesh': ''} if mesh_off else {}))
        except IRVerificationError:
            if _obs.enabled():
                _em().ir_verify_failures.inc()
            raise
        if report is not None and report['level'] <= 0 and \
                'amp' not in report:
            report = None  # nothing rewrote: legacy bypass contract
        self.last_graph_opt_report = report
        if report is not None:
            if report['ops_before'] is not None and _obs.enabled():
                em = _em()
                # count what the graph-opt passes actually removed, not
                # the before/after op delta — AMP weaves casts in after
                # the eliminations and would mask them
                em.graph_opt_seconds.observe(sum(
                    e['wall_s'] for e in report['passes']
                    if e['name'] != 'amp'))
                em.graph_opt_ops_eliminated.inc(
                    max(0, sum(report['eliminated'].values())))
            amp_report = report.get('amp')
            if amp_report is not None:
                # seed the dynamic-loss-scale state (f16 mode) so the
                # state analysis below sees live values — the user never
                # runs a startup program for pass-created vars
                for n, v in amp_report['state_defaults'].items():
                    if not scope.has(n):
                        scope.set(n, jnp.asarray(v))
                # the rewrite can add persistable state: re-derive the
                # rw/ro/out sets from the program that will actually
                # trace (the pre-rewrite sets only keyed the cache)
                state_rw_names, state_ro_names, state_out_names = \
                    self._analyze_state(prog, scope, set(feed_arrays))
                if _obs.enabled():
                    _em().amp_ops_lowered.inc(amp_report['ops_lowered'])
        backend = self.place.jax_device().platform

        def step_fn(feed_vals, state_rw, state_ro, rng_key):
            env = {}
            env.update(state_ro)
            env.update(state_rw)
            env.update(feed_vals)
            ctx = ExecutionContext(prog, prog.global_block(), rng_key,
                                   backend=backend)
            _run_ops(prog.global_block().ops, env, ctx)
            fetches = []
            for n in fetch_names:
                if n not in env:
                    raise KeyError("fetch var %r was never computed" % n)
                fetches.append(env[n])
            new_state = {n: env[n] for n in state_out_names if n in env}
            return fetches, new_state

        # state is always donated; the feed argument joins it when the
        # caller (run) proved this plan only ever sees executor-staged
        # feed buffers — the donated feeds are exactly the extra reuse
        # headroom the PR-3 donation analysis reports (short-lived
        # intermediates can land in the dead feed buffers instead of
        # growing peak HBM).  Under an SPMD mesh the same donation
        # applies THROUGH the pjit boundary (sharded feed and state
        # buffers are executor-staged too — run() proved ownership
        # before asking for the donating variant).
        smeta = None
        jit_kw = {}
        if spmd_mesh is not None:
            smeta = self._build_shard_meta(
                prog, spmd_mesh, set(feed_arrays), state_rw_names,
                state_ro_names)
            jit_kw['in_shardings'] = (smeta['feed_sh'], smeta['rw_sh'],
                                      smeta['ro_sh'], smeta['key_sh'])
        fn = jax.jit(step_fn,
                     donate_argnums=(0, 1) if feed_donate else (1,),
                     **jit_kw)
        plan = (fn, step_fn, state_rw_names, state_ro_names, smeta)
        if use_cache:
            self._cache[key] = plan
            self._plan_reports[key] = self.last_graph_opt_report
        return plan

    def run_steps(self, program=None, feed=None, fetch_list=None,
                  scope=None, repeat=None, return_numpy=True):
        """Run K training steps as ONE compiled XLA computation — a
        lax.scan over the step function with the persistable state as
        donated carry.  Populates ``last_step_report`` (measured phase
        walls × cost-model FLOPs/bytes) and, when the flight recorder
        is armed, exports the timeline ring to PADDLE_TPU_TRACE_DIR.

        TPU-native executor extension (no reference counterpart): over a
        network-attached accelerator each run() costs a host dispatch
        round trip; scanning K steps on-device amortizes it to one.  The
        per-step PRNG chain folds (seed, global_step) exactly like run(),
        so K calls of run() and one run_steps(K) produce identical
        numerics, dropout streams included.

        :param feed: list of K feed dicts (stacked on the device), or a
            single feed dict with ``repeat=K`` to reuse one device-staged
            batch for every step (benchmark mode — no re-staging).
        :param fetch_list: fetched per step; returns [K, ...]-stacked
            arrays, one per fetch.
        """
        try:
            return self._run_steps_impl(program, feed, fetch_list,
                                        scope, repeat, return_numpy)
        except BaseException:
            _tlm.maybe_dump_on_error()
            raise

    def _run_steps_impl(self, program, feed, fetch_list, scope, repeat,
                        return_numpy):
        t_call = time.perf_counter()
        if program is None:
            program = default_main_program()
        if scope is None:
            scope = global_scope()
        fetch_names = tuple(
            f.name if isinstance(f, Variable) else str(f)
            for f in (fetch_list or []))
        block = program.global_block()

        if isinstance(feed, dict):
            if not repeat:
                raise ValueError("run_steps with a single feed dict "
                                 "needs repeat=K")
            feeds, k = [feed], int(repeat)
        else:
            feeds, k = list(feed), len(feed)
            if repeat:
                raise ValueError("repeat= only combines with a single "
                                 "feed dict")
            if k == 0:
                return []
        stacked = len(feeds) > 1
        names0 = set(feeds[0])
        for i, f in enumerate(feeds[1:], start=1):
            if set(f) != names0:
                missing = sorted(names0 - set(f))
                extra = sorted(set(f) - names0)
                raise ValueError(
                    "run_steps feeds must use one key set across steps "
                    "(one compiled scan); step %d %s" % (i, '; '.join(
                        filter(None,
                               ["is missing %s" % missing if missing
                                else '',
                                "adds %s" % extra if extra else '']))))

        # tuned winners, like run(): before mesh and plan key resolve
        _maybe_apply_tuned(program, self.place)

        mesh, dev = self._mesh_and_dev(program)
        spmd = self._spmd_mesh(program) if mesh is None else None
        feed0 = _convert_feed(block, feeds[0])
        if spmd is None:
            feed0 = self._stage_feed(feed0, mesh, dev)

        fn_plan = self._get_plan(program, block, scope, feed0,
                                 fetch_names, True, mesh=mesh,
                                 spmd_mesh=spmd)
        _fn, raw_fn, rw_names, ro_names, smeta = fn_plan
        if smeta is not None:
            feed0 = {n: _shard_put(v, smeta['feed_sh'][n])
                     for n, v in feed0.items()}

        from ..flags import FLAGS
        prefetch = bool(FLAGS.device_prefetch) and stacked
        # per-call step-time breakdown (benchmarks/common.py reads it):
        # feed_s = host feed staging on the critical path (device
        # idle), feed_overlap_s = staging done while a previous chunk
        # was executing, update_s = scope write-back.  _finalize_step_
        # report joins these with the cost model under 'phases'.
        report = {'k': k, 'device_prefetch': prefetch,
                  'chunks': 1, 'chunk_steps': k,
                  'feed_s': 0.0, 'feed_overlap_s': 0.0,
                  'update_s': 0.0, 'feed_bytes': 0}
        self.last_step_report = report
        em = _em() if _obs.enabled() else None
        tl = _tlm.ring_if_armed()
        if tl is not None:
            tl.set_step(self._step)

        if prefetch:
            return self._run_steps_prefetch(
                program, block, scope, feeds, k, feed0, fetch_names,
                rw_names, ro_names, raw_fn, mesh, dev, em, report,
                return_numpy, t_call, smeta=smeta)

        multi, multi_fresh = self._multi_plan(
            program, scope, feed0, fetch_names, rw_names, ro_names,
            mesh if smeta is None else smeta['mesh'], raw_fn, k,
            stacked, smeta=smeta)

        xs = None
        if stacked:
            tf = time.perf_counter()
            xs = self._stack_chunk(feeds, 0, k, block,
                                   self._xs_placement(smeta, dev))
            report['feed_s'] = time.perf_counter() - tf
            report['feed_bytes'] = _nbytes(xs)
            if tl is not None:
                tl.record('executor.feed_stack', 'feed', t0=tf,
                          dur=report['feed_s'],
                          args={'bytes': report['feed_bytes'],
                                'steps': k})

        if smeta is not None:
            state_rw = self._stage_state_spmd(scope, rw_names,
                                              smeta['rw_sh'],
                                              smeta.get('pads'))
            state_ro = self._stage_state_spmd(scope, ro_names,
                                              smeta['ro_sh'],
                                              smeta.get('pads'))
            key0 = jax.device_put(
                jax.random.PRNGKey(self._base_seed(program)),
                smeta['key_sh'])
        else:
            state_rw = self._stage_state(
                {n: scope.get(n) for n in rw_names}, mesh, dev)
            state_ro = self._stage_state(
                {n: scope.get(n) for n in ro_names}, mesh, dev)
            key0 = jax.device_put(
                jax.random.PRNGKey(self._base_seed(program)), dev)
        t0 = jnp.asarray(self._step, jnp.int32)

        if em is not None:
            em.steps.inc(k)
            em.feed_bytes.inc(_nbytes(feed0) + (_nbytes(xs) if xs else 0))
            em.donated_state_bytes.inc(_nbytes(state_rw))
            if xs:
                # the whole [K, ...] stack is staged in one put before
                # the dispatch — the critical-path event the
                # device-prefetch pipeline exists to hide
                em.feed_blocking_puts.inc()
                em.donated_feed_bytes.inc(_nbytes(xs))

        with _obs.span('executor.run_steps'):
            ys, rw_f, last_extra = self._dispatch_multi(
                multi, multi_fresh, em, feed0, xs, state_rw, state_ro,
                key0, t0)
            self._step += k
            tu = time.perf_counter()
            for n, v in rw_f.items():
                scope.set(n, v)
            for n, v in last_extra.items():
                scope.set(n, v)
            report['update_s'] = time.perf_counter() - tu
            if tl is not None:
                tl.record('executor.scope_update', 'update', t0=tu,
                          dur=report['update_s'])
            if em is not None and return_numpy:
                self._note_amp_skips(rw_f, scope)
            if return_numpy:
                ts = time.perf_counter()
                outs = [np.asarray(y) for y in ys]
                if tl is not None:
                    tl.record('executor.fetch_sync', 'compute', t0=ts,
                              dur=time.perf_counter() - ts,
                              args={'steps': k})
            else:
                outs = list(ys)
            self._finalize_step_report(
                report, t_call,
                synced=return_numpy and bool(fetch_names))
            return outs

    def _multi_plan(self, program, scope, feed0, fetch_names, rw_names,
                    ro_names, mesh, raw_fn, k, stacked, smeta=None):
        """Get-or-build the jitted K-step scan plan for one scan length.

        The composite pass-configuration key (_pass_plan_key — the same
        single code path the run() key uses) keys the multi plan too:
        the scan closes over raw_fn, which traces the (un)rewritten
        program — a flag flip must not be served a scan over the old
        one.  The stacked feed argument (xs)
        is donated along with the state: run_steps always builds the
        stack itself from host copies, so the buffer is executor-owned
        and dead once the scan consumed it — XLA gets the whole stack
        back for intermediates instead of holding K dead batches.
        Under an SPMD mesh (``smeta``) the scan jits with the plan's
        NamedShardings — per-step feeds batch-sharded (scan dim 0
        replicated), state per the param plan — and the same xs+state
        donation flows through the pjit boundary."""
        mkey = ('multi', program._uid, program.version, k, stacked,
                fetch_names,
                tuple((n, feed0[n].shape, str(feed0[n].dtype))
                      for n in sorted(feed0)), scope._uid,
                rw_names, ro_names, mesh, _pass_plan_key(program))
        multi = self._cache.get(mkey)
        fresh = multi is None
        if fresh:
            if _obs.enabled():
                _em().plan_cache_misses.inc()
            jit_kw = {}
            if smeta is not None:
                jit_kw['in_shardings'] = (
                    smeta['feed_sh'],
                    self._xs_shardings(smeta, set(feed0))
                    if stacked else None,
                    smeta['rw_sh'], smeta['ro_sh'],
                    smeta['key_sh'], smeta['key_sh'])
            multi = jax.jit(make_multi_step_fn(raw_fn, stacked, k),
                            donate_argnums=(1, 2) if stacked else (2,),
                            **jit_kw)
            self._cache[mkey] = multi
        elif _obs.enabled():
            _em().plan_cache_hits.inc()
        return multi, fresh

    def _dispatch_multi(self, multi, fresh, em, feed0, xs, state_rw,
                        state_ro, key0, t0):
        """Invoke a multi-step plan, timing the first (compiling)
        invocation of a fresh plan under the executor.compile span.
        The donation-warning filter arms only on that compiling call —
        steady-state dispatches must not touch the process-global
        warnings state."""
        tl = _tlm.ring_if_armed()
        td = time.perf_counter() if tl is not None else None
        with _quiet_unused_donation(
                xs if (xs is not None and fresh) else None):
            if em is not None and fresh:
                with _obs.span('executor.compile'):
                    tc = time.perf_counter()
                    out = multi(feed0, xs, state_rw, state_ro, key0, t0)
                    em.compile_seconds.observe(time.perf_counter() - tc)
                em.compiles.inc()
            else:
                out = multi(feed0, xs, state_rw, state_ro, key0, t0)
        if tl is not None:
            # compile is synchronous inside the fresh call; cached
            # dispatches return before the device finishes (jax async) —
            # the event times the host-side dispatch, the device work
            # shows under executor.fetch_sync / the jax profiler trace
            tl.record('executor.compile' if fresh
                      else 'executor.dispatch',
                      'compile' if fresh else 'compute', t0=td,
                      dur=time.perf_counter() - td,
                      args={'donated_state_bytes': _nbytes(state_rw)})
        return out

    def _xs_placement(self, smeta, dev):
        """Placement argument for staging stacked feed columns: the
        per-column NamedShardings under an SPMD mesh (each chunk lands
        pre-sharded over the batch axis), the single device/sharding
        otherwise — consumed by runtime/prefetch.stage_columns."""
        if smeta is None:
            return dev
        return self._xs_shardings(
            smeta, set(smeta['feed_sh']))

    def _stack_chunk(self, feeds, lo, hi, block, placement):
        """Stack feeds[lo:hi] into device-staged [hi-lo, ...] columns
        (the one-shot path; the chunked path pre-converts and validates
        every feed before its first dispatch instead)."""
        from ..runtime.prefetch import stage_columns
        cols = {}
        want = None
        for i, f in enumerate(feeds[lo:hi]):
            fa = _convert_feed(block, f)
            if want is None:
                want = set(fa)
            elif set(fa) != want:
                # must fail here, not as an opaque scan-length
                # mismatch after state staging
                raise _feed_column_error(lo + i, set(fa), want)
            for n, v in fa.items():
                cols.setdefault(n, []).append(np.asarray(v))
        return stage_columns(
            {n: _stack_feed_col(n, vs) for n, vs in cols.items()},
            placement)

    def _run_steps_prefetch(self, program, block, scope, feeds, k,
                            feed0, fetch_names, rw_names, ro_names,
                            raw_fn, mesh, dev, em, report,
                            return_numpy, t_call, smeta=None):
        """Device-resident run_steps (PADDLE_TPU_DEVICE_PREFETCH): the
        K-step feed stack is staged in chunks through a double-buffered
        pipeline — the host stacks and device_puts chunk c+1 while the
        device scans chunk c — so steady-state steps never wait on a
        host transfer, and only ~2 chunks of feed are resident instead
        of the whole [K, ...] stack.  Bitwise-identical to the one-shot
        path: the scan body folds the PRNG key with the ABSOLUTE step
        index (key0, t), so chunk boundaries don't exist numerically,
        and the donated state chains from each chunk's output into the
        next chunk's input without a host round trip."""
        from ..flags import FLAGS
        from ..runtime.prefetch import device_prefetch
        cs = int(FLAGS.device_prefetch_chunk) or max(1, -(-k // 4))
        cs = max(1, min(cs, k))
        bounds = [(lo, min(lo + cs, k)) for lo in range(0, k, cs)]
        report['chunks'] = len(bounds)
        report['chunk_steps'] = cs
        started = [False]  # has any chunk been dispatched yet?

        # Convert + validate EVERY feed before the first dispatch: the
        # one-shot path fails atomically on a shape mismatch, and the
        # chunked path must too — chunk 0 donates the scope's state
        # buffers, so raising mid-stream would leave the scope holding
        # deleted arrays with half the steps applied.  Conversion is
        # host-side and copy-free for already-conforming ndarray feeds
        # (np.asarray is a view), but dtype coercion (int64→int32 &
        # co) copies — it happens on the critical path, so it counts
        # toward feed_s, not silently toward compute.  The per-chunk
        # np.stack + device_put — the bulk copy and transfer — still
        # runs overlapped in the thunks.
        tv = time.perf_counter()
        col_shapes = {}
        col_dtypes = {}
        conv = []
        for f in feeds:
            fa = _convert_feed(block, f)
            if conv and set(fa) != set(conv[0]):
                # e.g. one step fed (data, lengths) where another fed a
                # plain array: the LEN_SUFFIX companion appears in only
                # one of them
                raise _feed_column_error(len(conv), set(fa), set(conv[0]))
            for n in sorted(fa):
                v = np.asarray(fa[n])
                fa[n] = v
                want = col_shapes.setdefault(n, v.shape)
                if v.shape != want:
                    raise _feed_shape_error(n, {want, v.shape})
                # join the column dtype across ALL steps: the one-shot
                # path's single np.stack over K steps promotes every
                # step to the column's result_type, so each chunk must
                # stack to that same dtype — both for bitwise parity
                # and so every chunk shares ONE jit signature (a dtype
                # drift would otherwise force a fresh trace mid-stream,
                # after the scope state was donated)
                have = col_dtypes.get(n)
                col_dtypes[n] = (v.dtype if have is None
                                 else np.result_type(have, v.dtype))
            conv.append(fa)
        report['feed_s'] += time.perf_counter() - tv

        from ..runtime.prefetch import stage_columns
        xs_placement = self._xs_placement(smeta, dev)

        def make_thunk(lo, hi):
            def thunk():
                ts = time.perf_counter()
                xs = stage_columns(
                    {n: np.stack([conv[i][n] for i in range(lo, hi)])
                        .astype(col_dtypes[n], copy=False)
                     for n in col_shapes},
                    xs_placement)
                dt = time.perf_counter() - ts
                nb = _nbytes(xs)
                if started[0]:
                    report['feed_overlap_s'] += dt
                    if em is not None:
                        em.feed_prefetched_puts.inc()
                        em.feed_prefetched_bytes.inc(nb)
                else:
                    # pipeline prime: the only staging the device ever
                    # waits for
                    report['feed_s'] += dt
                    if em is not None:
                        em.feed_blocking_puts.inc()
                if em is not None:
                    em.feed_bytes.inc(nb)
                    em.donated_feed_bytes.inc(nb)
                report['feed_bytes'] += nb
                return lo, hi, xs
            return thunk

        if smeta is not None:
            state_rw = self._stage_state_spmd(scope, rw_names,
                                              smeta['rw_sh'],
                                              smeta.get('pads'))
            state_ro = self._stage_state_spmd(scope, ro_names,
                                              smeta['ro_sh'],
                                              smeta.get('pads'))
            key0 = jax.device_put(
                jax.random.PRNGKey(self._base_seed(program)),
                smeta['key_sh'])
        else:
            state_rw = self._stage_state(
                {n: scope.get(n) for n in rw_names}, mesh, dev)
            state_ro = self._stage_state(
                {n: scope.get(n) for n in ro_names}, mesh, dev)
            key0 = jax.device_put(
                jax.random.PRNGKey(self._base_seed(program)), dev)
        base = self._step
        if em is not None:
            # steps_total counts per COMPLETED chunk below, not k
            # up-front: a mid-stream failure lands the boundary state
            # and advances self._step by `done`, and the metric must
            # agree with that resumable step count
            em.feed_bytes.inc(_nbytes(feed0))
            em.donated_state_bytes.inc(_nbytes(state_rw))
        ys_parts = []
        last_extra = {}
        done = 0  # steps landed by completed chunks
        with _obs.span('executor.run_steps'):
            try:
                for lo, hi, xs in device_prefetch(
                        make_thunk(lo, hi) for lo, hi in bounds):
                    tl0 = _tlm.ring_if_armed()
                    if tl0 is not None:
                        tl0.set_step(base + lo)
                    multi, fresh = self._multi_plan(
                        program, scope, feed0, fetch_names, rw_names,
                        ro_names,
                        mesh if smeta is None else smeta['mesh'],
                        raw_fn, hi - lo, True, smeta=smeta)
                    ys, state_rw, last_extra = self._dispatch_multi(
                        multi, fresh, em, feed0, xs, state_rw, state_ro,
                        key0, jnp.asarray(base + lo, jnp.int32))
                    started[0] = True
                    if em is not None:
                        em.steps.inc(hi - done)
                    done = hi
                    ys_parts.append(ys)
                    if tl0 is not None:
                        # measured device memory, one sample per chunk
                        # (None on backends without memory_stats)
                        ms = _tlm.device_memory_stats(
                            self._memory_device())
                        if ms and ms.get('bytes_in_use') is not None:
                            tl0.counter_sample(
                                'paddle_tpu.device_bytes_in_use',
                                ms['bytes_in_use'])
            except BaseException as e:
                # BaseException: a Ctrl-C during the seconds-wide
                # multi-chunk host loop must land the boundary state
                # too, or the scope keeps referencing donated buffers
                if not started[0]:
                    raise
                # A completed chunk donated the scope's original state
                # buffers, so "unwind to before the call" no longer
                # exists.  On a mid-stream compile/staging failure
                # (feed errors never get here — every feed validated
                # above) the last completed chunk's OUTPUT state is
                # alive: land it and advance the step counter so the
                # scope reads as exactly "first `done` steps applied"
                # (a consistent, resumable boundary) instead of
                # holding references to deleted arrays.  But if the
                # failing chunk's EXECUTION already consumed that
                # carry before raising (e.g. a debug-nans abort fires
                # after donation), there is nothing consistent to land
                # — surface the original error unwrapped rather than
                # publish deleted arrays under a resumability claim.
                if any(getattr(v, 'is_deleted', lambda: False)()
                       for v in state_rw.values()):
                    raise
                for n, v in state_rw.items():
                    scope.set(n, v)
                for n, v in last_extra.items():
                    scope.set(n, v)
                self._step += done
                if not isinstance(e, Exception):
                    raise  # KeyboardInterrupt & co propagate as-is
                raise RuntimeError(
                    "run_steps(device_prefetch) failed mid-stream "
                    "after %d of %d steps; the scope holds the state "
                    "of the %d completed steps" % (done, k, done)) \
                    from e
            self._step += k
            tu = time.perf_counter()
            for n, v in state_rw.items():
                scope.set(n, v)
            for n, v in last_extra.items():
                scope.set(n, v)
            report['update_s'] = time.perf_counter() - tu
            tl = _tlm.ring_if_armed()
            if tl is not None:
                tl.record('executor.scope_update', 'update', t0=tu,
                          dur=report['update_s'])
            if em is not None and return_numpy:
                self._note_amp_skips(state_rw, scope)
            ts = time.perf_counter()
            outs = []
            for i in range(len(fetch_names)):
                parts = [p[i] for p in ys_parts]
                if return_numpy:
                    outs.append(np.concatenate(
                        [np.asarray(x) for x in parts]))
                else:
                    outs.append(parts[0] if len(parts) == 1
                                else jnp.concatenate(parts))
            if tl is not None and return_numpy and fetch_names:
                tl.record('executor.fetch_sync', 'compute', t0=ts,
                          dur=time.perf_counter() - ts,
                          args={'steps': k})
            self._finalize_step_report(
                report, t_call,
                synced=return_numpy and bool(fetch_names))
            return outs

    def _finalize_step_report(self, report, t_call, synced=False):
        """Join the measured run_steps phase walls with the static
        cost-model report (transpiler/cost_model.py, cached per plan in
        last_graph_opt_report['cost']) into ``last_step_report``:

        - ``wall_s`` = whole-call wall; ``compute_s`` = the residual
          after feed_s + update_s, i.e. device scan + fetch sync — the
          three phases sum to ~wall by construction.
        - ``phases`` = {feed, compute, update}, each with its wall and
          the modeled bytes/FLOPs that phase moves per step; compute
          carries per-role FLOPs and arithmetic intensity, plus
          achieved FLOP/s and — when PADDLE_TPU_PEAK_TFLOPS is set —
          MFU, but ONLY when ``synced`` (the fetch conversion forced
          the device scan to completion inside the measured window).
          A return_numpy=False call returns before the device
          finishes, so its residual measures host dispatch only —
          publishing a rate from it would overstate MFU by the
          device-time/dispatch-time ratio.  Callers that sync
          externally (benchmarks/common.py _step_breakdown) derive
          MFU from their own synced wall and the modeled
          flops_per_step instead.

        Also flushes the timeline ring to PADDLE_TPU_TRACE_DIR when the
        flight recorder is armed (one atomic trace_<pid>.json per
        run_steps call)."""
        import os as _os
        wall = time.perf_counter() - t_call
        k = max(int(report.get('k', 1)), 1)
        compute = max(wall - report['feed_s'] - report['update_s'], 0.0)
        report['wall_s'] = wall
        report['compute_s'] = compute
        report['synced'] = bool(synced)
        cost = (self.last_graph_opt_report or {}).get('cost')
        feed_phase = {'wall_s': report['feed_s'],
                      'overlap_s': report['feed_overlap_s'],
                      'bytes': report.get('feed_bytes', 0)}
        compute_phase = {'wall_s': compute}
        update_phase = {'wall_s': report['update_s']}
        if cost is not None and cost.get('total') is not None:
            total = cost['total']
            compute_phase.update({
                'flops': total['flops'] * k,
                'bytes': total['bytes'] * k,
                'flops_per_step': total['flops'],
                'bytes_per_step': total['bytes'],
                'intensity': total['intensity'],
                'per_role_flops': {r: v['flops']
                                   for r, v in cost['per_role'].items()},
            })
            if synced and compute > 0.0 and total['flops']:
                compute_phase['flops_per_s'] = total['flops'] * k / \
                    compute
                peak = _os.environ.get('PADDLE_TPU_PEAK_TFLOPS')
                if peak:
                    compute_phase['mfu'] = (
                        compute_phase['flops_per_s'] /
                        (float(peak) * 1e12))
            if cost.get('feed_bytes') is not None:
                feed_phase['modeled_bytes_per_step'] = cost['feed_bytes']
            update_phase['state_bytes'] = cost.get('state_bytes', 0)
        report['phases'] = {'feed': feed_phase,
                            'compute': compute_phase,
                            'update': update_phase}
        # comm attribution (SPMD plans): the modeled ICI bytes the
        # k steps' collectives moved, priced by the cost model from
        # the sharding pass's table — attributed like feed/compute/
        # update, with a wall estimate when PADDLE_TPU_ICI_GBPS is set
        noted = self._note_collectives(
            _tlm.ring_if_armed(), k,
            compute_s=compute if (synced and compute > 0.0) else None)
        if noted is not None:
            report['phases']['collective'] = {
                'modeled_ici_bytes': noted['ici_bytes'],
                'modeled_ici_bytes_per_step': noted['ici_bytes'] // k,
                'collectives': noted['collectives'],
                'by_kind': dict(noted.get('by_kind') or {}),
                'est_wall_s': noted['est_wall_s'],
            }
            for fld in ('overlap_fraction', 'overlap_basis',
                        'exposed_bytes_per_step',
                        'overlapped_bytes_per_step',
                        'exposed_est_wall_s', 'pp'):
                if fld in noted:
                    report['phases']['collective'][fld] = noted[fld]
        report['cost'] = cost
        measured = _tlm.device_memory_stats(self._memory_device())
        report['memory'] = self._memory_report(cost, measured)
        tl = _tlm.ring_if_armed()
        if tl is not None:
            self._emit_memory_counters(
                tl, (cost or {}).get('memory'),
                t_call + report['feed_s'], compute, measured=measured)
        _tlm.maybe_flush()
        return report

    def _memory_device(self):
        """The device whose memory_stats() this executor's measured
        numbers describe — the executor's PLACE, not local_devices()[0]
        (on a multi-device host they differ, and the modeled-vs-
        measured comparison must read one device)."""
        try:
            return self.place.jax_device()
        except Exception:
            return None

    def _memory_report(self, cost, measured):
        """The memory block of ``last_step_report``: the modeled peak
        (liveness walk, transpiler/memory_model.py) joined with the
        MEASURED device stats when the backend provides them —
        ``measured`` is honestly None on CPU backends, never a made-up
        zero — plus a headroom ratio against PADDLE_TPU_PEAK_HBM_BYTES
        when set, so model-vs-measured divergence is a first-class
        printed quantity."""
        from ..flags import FLAGS
        mem = (cost or {}).get('memory') if isinstance(cost, dict) \
            else None
        entry = {
            'modeled_peak_bytes': (mem or {}).get('peak_bytes'),
            'modeled_persistable_bytes':
                (mem or {}).get('persistable_bytes'),
            'watermark_op': ((mem or {}).get('watermark') or [None])[0],
            'remat_level': (mem or {}).get('remat_level'),
            'measured': measured,
        }
        if measured is not None:
            entry['measured_peak_bytes'] = measured.get(
                'peak_bytes_in_use')
        budget = int(FLAGS.peak_hbm_bytes or 0)
        if budget > 0:
            head = {'budget_bytes': budget}
            if entry['modeled_peak_bytes']:
                head['modeled_ratio'] = (
                    entry['modeled_peak_bytes'] / budget)
            if measured is not None and \
                    measured.get('peak_bytes_in_use'):
                head['measured_ratio'] = (
                    measured['peak_bytes_in_use'] / budget)
            entry['headroom'] = head
        return entry

    @staticmethod
    def _emit_memory_counters(tl, mem, t0, span, measured=None):
        """Render the modeled live-bytes sawtooth as a Chrome counter
        track (``ph:"C"``): samples step along op_seq, mapped linearly
        onto the measured compute window so the track lines up with the
        dispatch it models.  Downsampled to a bounded point count with
        the peak sample always kept — a 1000-op program must not eat
        the event ring.  ``measured`` is the device_memory_stats()
        dict the caller already captured (one query serves both the
        report and the counter track), sampled alongside."""
        timeline = (mem or {}).get('timeline') or ()
        if timeline:
            pts = list(timeline)
            cap = 96
            if len(pts) > cap:
                peak_i = max(range(len(pts)),
                             key=lambda i: pts[i]['live_bytes'])
                stride = -(-len(pts) // cap)
                keep = sorted({0, peak_i, len(pts) - 1}
                              | set(range(0, len(pts), stride)))
                pts = [pts[i] for i in keep]
            span = max(span, 1e-6)
            n = max(len(pts) - 1, 1)
            for i, p in enumerate(pts):
                tl.counter_sample('paddle_tpu.modeled_live_bytes',
                                  p['live_bytes'],
                                  t0=t0 + span * (i / n))
        if measured and measured.get('bytes_in_use') is not None:
            tl.counter_sample('paddle_tpu.device_bytes_in_use',
                              measured['bytes_in_use'])

    def _compile_common(self, program, feed, fetch_list, scope):
        if program is None:
            program = default_main_program()
        if scope is None:
            scope = global_scope()
        feed = feed or {}
        fetch_names = [
            f.name if isinstance(f, Variable) else str(f)
            for f in (fetch_list or [])
        ]
        block = program.global_block()
        feed_arrays = {}
        for name, value in feed.items():
            var = block.vars.get(name)
            feed_arrays.update(_to_feed_arrays(name, value, var))
        # compile()/compile_raw() hand their fn to AOT/export/serving
        # consumers (and run_sharded re-jits with its OWN shard plan):
        # the flag mesh is pinned off so the plan — and its cost/memory
        # report — describes the single-logical-device executable these
        # callers actually get
        fn, raw, rw_names, ro_names, _smeta = self._get_plan(
            program, block, scope, feed_arrays, tuple(fetch_names),
            True, mesh_off=True)
        state_rw = {n: scope.get(n) for n in rw_names}
        state_ro = {n: scope.get(n) for n in ro_names}
        rng_key = self._rng_key(program)
        return fn, raw, (feed_arrays, state_rw, state_ro, rng_key)

    def compile(self, program=None, feed=None, fetch_list=None, scope=None):
        """Build (but do not run) the jitted step function for a program.

        Returns (fn, example_args) where ``fn(feed, state_rw, state_ro,
        rng_key) -> (fetches, new_state)`` is the whole-block XLA
        computation — the hook used by __graft_entry__ and jax.export.
        """
        fn, _raw, args = self._compile_common(program, feed, fetch_list,
                                              scope)
        return fn, args

    def compile_raw(self, program=None, feed=None, fetch_list=None,
                    scope=None):
        """Like compile(), but returns the UN-jitted python step function —
        the hook for re-jitting with explicit shardings (parallel/api.py)
        or custom transforms."""
        _fn, raw, args = self._compile_common(program, feed, fetch_list,
                                              scope)
        return raw, args

    def reset_cache(self):
        """Drop every cached plan.  The next plan build re-reads
        PADDLE_TPU_GRAPH_OPT_LEVEL, PADDLE_TPU_SPARSE_APPLY,
        PADDLE_TPU_AMP, and PADDLE_TPU_VERIFY_IR (all folded into the composite
        pass-configuration component of every plan key, so flips
        invalidate naturally — this just frees the old plans).
        PADDLE_TPU_DEVICE_PREFETCH is re-read on every run_steps call
        and its chunking keys the scan plans by length, so it needs no
        special handling here either."""
        self.close()

    def close(self):
        self._cache.clear()
        self._plan_reports.clear()
        self.last_graph_opt_report = None
        self.last_step_report = None
        self._mesh_op_cache.clear()
        if hasattr(self, '_sharded_cache'):
            self._sharded_cache.clear()

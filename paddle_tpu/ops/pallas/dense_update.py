"""Fused dense optimizer applies as Pallas TPU kernels.

Reference parity: the DENSE branches of paddle/operators/{sgd,momentum,
adam}_op — elementwise updates over whole parameters.  Written on the
premise that XLA's lowering of the same expressions (ops/optim_ops.py)
round-trips `m_new` / `v_new` through HBM between fusions and that the
optimizer apply is where a ResNet/VGG step loses its time.  The first
device trace since (v5e, PR 21) says otherwise: with the XLA expressions
the ResNet-50 b64 step is 27.9 ms, with these kernels 79.4 — the kernel
calls themselves are 0.24 ms, the reshapes and copies that turn every
tiled parameter into the [1, N] view and back are 51 ms.  The kernels
stay, off by default, for the issue that gives them a layout-preserving
view and a cell to win (ROADMAP S2, D4).

These kernels fuse each rule into ONE grid walk over the flattened
parameter: every block DMAs a [1, T] tile of param + each moment out of
HBM exactly once, applies the update on the VPU, and stores the tile
back through ``input_output_aliases`` — the donated state is updated in
place with no intermediate materialization:

  dense_apply_sgd       param                     (+ optional fused L2
                                                   weight decay)
  dense_apply_momentum  param + velocity, ONE pass (plain and Nesterov)
  dense_apply_adam      param + m1 + m2, ONE pass  (vs 3+ XLA fusions
                                                   with HBM round-trips)

Tiling: the parameter is viewed as [1, N] (any rank, any N — Pallas
masks the ragged last block, so tile-unaligned shapes stay exact) and
walked in [1, T] lane-aligned tiles; `pick_flat_tile` chooses the
largest T whose per-block working set fits the VMEM budget, the same
budget-driven chooser pattern as lstm_cell.pick_batch_tile.

Bitwise parity contract (tier-1 tests/test_pallas_dense_update.py): the
kernel bodies restate the ops/optim_ops.py dense expressions term for
term, so XLA makes the same fma-contraction choices in both lowerings —
the PR-4 subtlety recurs here: a factor pre-rounded outside the kernel
(or an expression reassociated inside it) would change the contraction
rounding and break bitwise parity.  Scalars (lr, mu, lr_t) ride in as
(1, 1) SMEM-class operands; betas/eps/mu are trace-time constants baked
into the kernel exactly as they are baked into the XLA branch.

On non-TPU backends the kernels run with interpret=True — CPU CI
executes the same code path.  The mode switch lives in
`dense_apply_mode()`: PADDLE_TPU_DENSE_APPLY=pallas|xla forces a path;
ops/optim_ops.py routes on it per trace and the resolved mode is part of
the executor's plan cache key, so a flip retraces instead of silently
serving the old lowering.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['dense_apply_sgd', 'dense_apply_momentum', 'dense_apply_adam',
           'dense_apply_mode', 'pick_flat_tile', 'flat_tile_budget']

# per-block VMEM the flat walk may claim: tables are double-buffered by
# Mosaic (in + aliased out), values single; leave margin for temporaries
_VMEM_BUDGET = 4 * 1024 * 1024

# lane-aligned tile ladder, largest first (f32 lane width 128)
_TILES = (65536, 32768, 16384, 8192, 4096, 2048, 1024, 512, 256, 128)


def dense_apply_mode():
    """Resolved dense-apply path: 'xla' unless PADDLE_TPU_DENSE_APPLY
    pins 'pallas'.

    The kernels compile and match on a v5e, and lose there: the [1, N]
    view costs a relayout of every parameter on the way in and out —
    ResNet-50 b64, device trace, 79.4 ms per step against 27.9 with the
    XLA expressions (PERF.md, chip bring-up, PR 21) — so no platform
    selects them on its own any more.  Pinned to 'pallas' under
    PADDLE_TPU_MESH, jax refuses the step at lowering ("Mosaic kernels
    cannot be automatically partitioned").  Read at trace time and part
    of the executor's plan cache key, so a flip retraces instead of
    silently serving the old path."""
    from ...flags import FLAGS
    return 'pallas' if FLAGS.dense_apply == 'pallas' else 'xla'


def flat_tile_budget():
    """Resolved per-block VMEM budget for :func:`pick_flat_tile`:
    PADDLE_TPU_FLAT_TILE_BUDGET when >0 (the autotuner's hook — a
    registered tunable in tuning/registry.py), the baked-in 4 MiB
    otherwise.  Read at trace time and a component of the composite
    plan-cache key (pass_manager.plan_key), so an override retraces
    instead of serving a plan built at the old tile size."""
    from ...flags import FLAGS
    b = int(FLAGS.flat_tile_budget or 0)
    return b if b > 0 else _VMEM_BUDGET


def pick_flat_tile(n, n_tables, n_vals, budget=None):
    """Largest lane-aligned tile T such that one grid step's working
    set — each table twice (block in + aliased block out) + each value
    block, all f32 — fits `budget` bytes of VMEM.  Also never wider
    than the padded element count (a tiny param takes one ragged
    block).  The floor is one 128-lane tile: the budget can shrink the
    tile, never veto the kernel (same contract as
    lstm_cell.pick_batch_tile returning its smallest divisor)."""
    if budget is None:
        budget = flat_tile_budget()
    bufs = 2 * n_tables + n_vals
    padded = -(-max(int(n), 1) // 128) * 128
    for t in _TILES:
        if t <= padded and bufs * t * 4 <= budget:
            return t
    return 128


def _flat_kernel(*refs, nt, nv, ns, rule):
    """One grid step = one [1, T] tile of every table/value.  refs
    layout: nt table blocks, nv value blocks, ns (1, 1) scalar blocks,
    then the nt aliased out blocks.  Blocks are disjoint (no resident-
    block accumulation like the row-sparse kernels need) — the ragged
    last block is masked by Pallas, so tile-unaligned params are
    exact."""
    tabs = refs[:nt]
    vals = refs[nt:nt + nv]
    scalars = tuple(r[0, 0] for r in refs[nt + nv:nt + nv + ns])
    outs = refs[nt + nv + ns:]
    for o, new in zip(outs, rule(tuple(t[...] for t in tabs),
                                 tuple(v[...] for v in vals),
                                 scalars)):
        o[...] = new


def _flat_call(tables, vals, scalars, rule, interpret):
    """Launch the flat tile walk over same-shaped f32 tables/values of
    any rank: each is viewed [1, N], the grid covers ceil(N / T) tiles,
    and the tables come back input_output_aliased (in place under
    donation) in their original shapes."""
    shape = tables[0].shape
    n = 1
    for d in shape:
        n *= int(d)
    if n == 0:
        return tuple(tables) if len(tables) > 1 else tables[0]
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    nt, nv, ns = len(tables), len(vals), len(scalars)
    tile = pick_flat_tile(n, nt, nv)
    flat = [jnp.reshape(t, (1, n)) for t in tables]
    vflat = [jnp.reshape(v, (1, n)) for v in vals]
    sflat = [jnp.reshape(s, (1, 1)).astype(jnp.float32) for s in scalars]
    spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    outs = pl.pallas_call(
        functools.partial(_flat_kernel, nt=nt, nv=nv, ns=ns, rule=rule),
        grid=(-(-n // tile),),
        in_specs=([spec] * (nt + nv) +
                  [pl.BlockSpec((1, 1), lambda i: (0, 0))] * ns),
        out_specs=[spec] * nt,
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.float32)
                   for _ in tables],
        # operand t aliases out t: the state updates in place under the
        # executor's donated-carry step
        input_output_aliases={t: t for t in range(nt)},
        # tiles are disjoint; 'arbitrary' (sequential) is always valid
        # and the walk is bandwidth-bound either way
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret,
    )(*flat, *vflat, *sflat)
    return tuple(jnp.reshape(o, shape) for o in outs) if nt > 1 \
        else jnp.reshape(outs[0], shape)


def dense_apply_sgd(param, grad, lr, weight_decay=None, interpret=None):
    """param -= lr * grad, one fused pass; with `weight_decay` the
    decoupled-into-the-grad L2 term rides the same pass:
    param -= lr * (grad + wd * param) — exactly the expression the
    append_regularization_ops scale+sum pair feeds today's sgd op, so
    fusing it keeps the update bitwise when the decay coefficient is
    folded into the op instead of woven as separate ops."""
    if weight_decay is None:
        def rule(tabs, vals, scalars):
            (p,), (g,), (lr_s,) = tabs, vals, scalars
            # ops/optim_ops.py _sgd dense branch, verbatim
            return (p - lr_s * g,)
        return _flat_call([param], [grad], [lr], rule, interpret)

    def rule(tabs, vals, scalars):
        (p,), (g,), (lr_s, wd) = tabs, vals, scalars
        return (p - lr_s * (g + wd * p),)
    return _flat_call([param], [grad], [lr, weight_decay], rule,
                      interpret)


def dense_apply_momentum(param, velocity, grad, lr, mu,
                         use_nesterov=False, interpret=None):
    """Fused momentum: velocity accumulate + param step in ONE pass
    (today's XLA path re-reads v_new from HBM for the step).  `mu` is a
    trace-time constant (op attr), `lr` a traced scalar.  Returns
    (param_new, velocity_new)."""
    if use_nesterov:
        def rule(tabs, vals, scalars):
            (p, v), (g,), (lr_s,) = tabs, vals, scalars
            # ops/optim_ops.py _momentum, verbatim (nesterov arm)
            v_new = mu * v + g
            p_new = p - (g + mu * v_new) * lr_s
            return (p_new, v_new)
    else:
        def rule(tabs, vals, scalars):
            (p, v), (g,), (lr_s,) = tabs, vals, scalars
            v_new = mu * v + g
            p_new = p - lr_s * v_new
            return (p_new, v_new)
    return _flat_call([param, velocity], [grad], [lr], rule, interpret)


def dense_apply_adam(param, moment1, moment2, grad, lr_t, beta1, beta2,
                     epsilon, interpret=None):
    """Fused dense Adam: param + both moments in ONE grid walk — one
    read and one aliased write per state table, vs the XLA op soup's
    multiple fusions with `m_new`/`v_new` HBM round-trips.  `lr_t` is
    the bias-corrected rate the caller computed from the pow
    accumulators (a traced scalar); betas/eps are trace-time constants.
    Returns (p, m1, m2)."""
    def rule(tabs, vals, scalars):
        (p, m, v), (g,), (lrt,) = tabs, vals, scalars
        # ops/optim_ops.py _adam dense tail, verbatim — same
        # expressions, same fma-contraction choices (the PR-4 lesson:
        # reassociating any term here breaks bitwise parity)
        m_new = beta1 * m + (1 - beta1) * g
        v_new = beta2 * v + (1 - beta2) * jnp.square(g)
        p_new = p - lrt * m_new / (jnp.sqrt(v_new) + epsilon)
        return (p_new, m_new, v_new)
    return _flat_call([param, moment1, moment2], [grad], [lr_t], rule,
                      interpret)

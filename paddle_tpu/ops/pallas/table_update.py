"""Row-sparse table update as a Pallas TPU kernel family.

Reference parity: the sparse branches of paddle/operators/{sgd,adagrad,
adam}_op — whose whole point is touching only the gradient's rows of a
vocab-height table.  The XLA:TPU lowering of the scatter-adds those
branches compile to defeats that: every `table.at[rows].add(upd)` runs a
full pass over the table operand (~1 ns/table-row + ~28 ns/touched-row
per scattered table — PERF.md "CTR at Criteo scale"), so the optimizer
apply at 26 slots x 1M rows moves ~0.9 GB of table per step while the
gradients are row-sparse end-to-end.

These kernels make the apply O(touched rows x row width), independent of
table height: the grid walks the touched rows; each program's BlockSpec
index map (computed from the scalar-prefetched row ids) DMAs the aligned
8-row block around one touched row of each state table out of HBM (the
TPU lowering takes no narrower window: a block's second-minor dim is a
multiple of 8 or the whole array's), applies the optimizer rule to that
row on the VPU, and stores the block back through
`input_output_aliases` — the table is donated, never copied, and blocks
without a touched row are never read.

Three fused rules ship, matching the sparse branches in ops/optim_ops.py
expression-for-expression (bitwise parity is tested, not hoped for):

  sparse_apply_sgd      param                      (linear; duplicates
                                                    accumulate in slot
                                                    order, like scatter)
  sparse_apply_adagrad  param + moment, ONE pass   (halves the 2-scatter
                                                    cost of today's path)
  sparse_apply_adam     param + moment1 + moment2  (lazy adam: moments
                                                    decay only on
                                                    touched rows)

Row-id contract (the whole family): ids are sorted ascending before the
kernel sees them.  Sorting makes duplicate rows, and rows of one block,
CONSECUTIVE, which is what lets a revisited block ride Mosaic's
resident-block rule — when the index map output doesn't change between
grid steps, the block stays in VMEM with no refetch and no intermediate
store, so sequential accumulation into the out block is race-free.  Ids follow the oracle's
index semantics exactly: negatives in [-height, 0) wrap Python-style
(like XLA scatter/gather), and anything else outside [0, height) is a
sentinel — it sorts to the tail (clamped into range for the index map
only), the kernel skips its update, and the XLA oracle drops it too
(out-of-bounds scatter updates are dropped) — so ragged touched-row
counts can be padded to a bucket-friendly length with `height` and stay
bitwise-exact.  merge_rows_sentinel (core/selected_rows.py) produces
exactly this layout.

On non-TPU backends the kernels run with interpret=True — CPU CI
executes the same code path (how the tier-1 parity tests work), and
tests/test_tpu_lowering.py lowers them for the TPU, which interpret mode
does not.  The mode switch lives in `sparse_apply_mode()`:
PADDLE_TPU_SPARSE_APPLY=pallas|xla forces a path; ops/optim_ops.py
routes on it per trace.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.selected_rows import merge_rows_sentinel

__all__ = ['sparse_apply_sgd', 'sparse_apply_adagrad', 'sparse_apply_adam',
           'sparse_apply_mode']


def sparse_apply_mode():
    """Resolved sparse-apply path: 'xla' unless PADDLE_TPU_SPARSE_APPLY
    pins 'pallas'.

    The kernels compile and match bit for bit on a v5e, and lose there
    at the one shape measured: 32768 touched rows of a 1,000,003 x 16
    table take 8.6 / 14.8 / 19.2 ms per sgd / adagrad / adam call
    against 4.4 / 7.3 / 13.6 ms for the scatter expressions — one grid
    step per touched row is the cost (PERF.md, chip bring-up, PR 21) —
    so no platform selects them on its own any more.  Pinned to
    'pallas' under PADDLE_TPU_MESH, only the embedding engine's
    per-shard apply (inside a shard_map) can host the kernel; anywhere
    else jax refuses the step at lowering ("Mosaic kernels cannot be
    automatically partitioned").  Read at trace time and part of the
    executor's plan cache key, so a flip retraces instead of silently
    serving the old path."""
    from ...flags import FLAGS
    return 'pallas' if FLAGS.sparse_apply == 'pallas' else 'xla'


# rows per block: the f32 sublane count.  The TPU lowering refuses a
# block whose second-minor dim is neither a multiple of 8 nor the whole
# array's, so a one-row (1, D) window does not lower; the walk fetches
# the aligned 8-row block around each touched row instead and updates
# the one row inside it
_SUBLANES = 8


def _rowwise_kernel(rows_ref, *refs, nt, nv, ns, height, tb, vb, rule):
    """One grid step = one touched row.  refs layout: nt table blocks
    [tb, D], nv value blocks [vb, D], ns scalar blocks, then nt aliased
    out blocks.

    Block identity is the CLAMPED row's block (the index map clamps
    sentinels into range), so `fresh` — "this grid step targets a
    different table block than the previous one" — must compare clamped
    block ids: a sentinel step right after a real update in the last
    block shares that block and must not be treated as a first visit."""
    i = pl.program_id(0)
    row = rows_ref[i]
    h1 = height - 1
    bi = jnp.minimum(row, h1) // tb
    prev_bi = jnp.minimum(rows_ref[jnp.maximum(i - 1, 0)], h1) // tb
    fresh = jnp.logical_or(i == 0, bi != prev_bi)
    valid = jnp.logical_and(row >= 0, row < height)
    tabs = refs[:nt]
    vals = refs[nt:nt + nv]
    scalars = tuple(r[0, 0] for r in refs[nt + nv:nt + nv + ns])
    outs = refs[nt + nv + ns:]

    # first visit of a block: every block a grid step maps is stored, so
    # the out block starts as the fetched content (untouched rows, and a
    # clamped sentinel's whole block, go back unchanged)
    @pl.when(fresh)
    def _copy_in():
        for o, t in zip(outs, tabs):
            o[...] = t[...]

    # the block stays resident while consecutive steps map it (no
    # refetch, no store in between), so the row is always read from the
    # OUT block: a duplicate of the previous row sees its accumulated
    # value — scatter-add's per-row slot order — and another row of the
    # same block sees the copy made on the first visit
    @pl.when(valid)
    def _update():
        r = pl.ds(row - bi * tb, 1)
        v = pl.ds(i % vb, 1)
        for o, new in zip(outs, rule(tuple(o[r, :] for o in outs),
                                     tuple(x[v, :] for x in vals),
                                     scalars)):
            o[r, :] = new


def _rowwise_call(rows, tables, vals, scalars, rule, interpret):
    """Launch the row-walking grid: rows [K] int32 (sorted, sentinels at
    the tail), tables/vals lists of [H, D] / [K, D] f32, scalars a list
    of () f32.  Returns the updated tables (input_output_aliased, so
    under donation the update is in place)."""
    height, width = tables[0].shape
    k = int(rows.shape[0])
    nt, nv, ns = len(tables), len(vals), len(scalars)
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    # a dim shorter than one sublane tile is taken whole (the lowering
    # accepts a block dim equal to the array's)
    tb = min(_SUBLANES, height)
    vb = min(_SUBLANES, k)

    def _tab_map(i, rows_ref):
        return (jnp.minimum(rows_ref[i], height - 1) // tb, 0)

    tab_spec = pl.BlockSpec((tb, width), _tab_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k,),
        in_specs=(
            [tab_spec] * nt +
            [pl.BlockSpec((vb, width), lambda i, r: (i // vb, 0))] * nv +
            [pl.BlockSpec((1, 1), lambda i, r: (0, 0))] * ns),
        out_specs=[tab_spec] * nt,
    )
    kernel = functools.partial(
        _rowwise_kernel, nt=nt, nv=nv, ns=ns, height=height, tb=tb,
        vb=vb, rule=rule)
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tables],
        # operand i (0 = the scalar-prefetched rows) aliases out t: the
        # tables are updated in place under donation
        input_output_aliases={1 + t: t for t in range(nt)},
        # the grid is sequential by construction (resident-block
        # accumulation and sentinel skips depend on visit order)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret,
    )(rows, *tables, *vals, *scalars)
    return tuple(outs) if nt > 1 else outs[0]


def _prep(rows, values, height):
    """int32 [K] ids + f32 values, with ids normalized to the oracle's
    index semantics: XLA scatter/gather wraps Python-style negatives
    (verified: `p.at[[-1]].add(u)` updates the last row; ids below
    -height are dropped), so ids in [-height, 0) wrap by +height and
    anything still outside [0, height) becomes the skip-sentinel
    `height` — which the oracle drops too."""
    rows = rows.astype(jnp.int32).reshape(-1)
    rows = jnp.where(rows < 0, rows + height, rows)
    rows = jnp.where((rows < 0) | (rows >= height), height, rows)
    return rows, values.astype(jnp.float32)


def sparse_apply_sgd(param, rows, values, lr, interpret=None):
    """param[rows] -= lr * values, O(touched rows).

    Bitwise-matches `param.at[rows].add(-lr * values)`: the update
    vector is computed identically outside the kernel, rows are stably
    sorted so duplicates stay in slot order, and duplicate visits
    accumulate sequentially in the resident block — the same per-row
    association XLA's scatter-add applies.  Ids wrap/drop exactly like
    the oracle's (see _prep); the canonical sentinel sorts to the
    tail."""
    height = param.shape[0]
    rows, values = _prep(rows, values, height)
    if rows.shape[0] == 0:
        return param
    u = -lr * values  # outside the kernel: bitwise-identical to the
    #                   XLA path's update vector
    order = jnp.argsort(rows, stable=True)

    def rule(tabs, vals, _scalars):
        (p,), (u_blk,) = tabs, vals
        return (p + u_blk,)

    return _rowwise_call(rows[order], [param], [u[order]], [], rule,
                         interpret=interpret)


def sparse_apply_adagrad(param, moment, rows, values, lr, epsilon,
                         interpret=None):
    """Fused sparse Adagrad: moment accumulate + param step on the
    touched rows in ONE kernel pass (today's XLA path pays two full
    table scatters).  Duplicates are pre-merged (merge_rows_sentinel),
    so the nonlinear rule sees each row once; expressions mirror
    ops/optim_ops.py's sparse branch term for term.  Returns
    (param_new, moment_new)."""
    height = param.shape[0]
    rows, values = _prep(rows, values, height)
    if rows.shape[0] == 0:
        return param, moment
    mrows, g, _valid = merge_rows_sentinel(rows, values, height)
    # the XLA branch rounds "moment + g^2" TWICE, differently: the step's
    # mom_row rides a gather+add that XLA:CPU contracts to fma(g, g,
    # mom), while the moment OUTPUT scatter-adds a separately-rounded
    # g^2.  Bitwise parity means reproducing both: square(g) computed
    # in-kernel contracts the same way for the step; the pre-rounded
    # `sq` operand gives the moment output its plain add.
    sq = jnp.square(g)
    neg_lr = jnp.reshape(-lr, (1, 1)).astype(jnp.float32)

    def rule(tabs, vals, scalars):
        (p, mom), (g_blk, sq_blk), (nlr,) = tabs, vals, scalars
        mom_row = mom + jnp.square(g_blk)
        p_new = p + nlr * g_blk / (jnp.sqrt(mom_row) + epsilon)
        return (p_new, mom + sq_blk)

    return _rowwise_call(mrows, [param, moment], [g, sq], [neg_lr], rule,
                         interpret=interpret)


def sparse_apply_adam(param, moment1, moment2, rows, values, lr_t,
                      beta1, beta2, epsilon, interpret=None):
    """Fused lazy sparse Adam: param + both moments in ONE kernel pass.
    `lr_t` is the bias-corrected rate (lr * sqrt(1-b2^t)/(1-b1^t)) the
    caller computed from the pow accumulators — it rides into the
    kernel as a (1, 1) SMEM-class scalar operand.  Moments decay and
    the param moves only on touched rows; sentinel slots are skipped,
    so padding never decays anything.  Returns (p, m1, m2)."""
    height = param.shape[0]
    rows, values = _prep(rows, values, height)
    if rows.shape[0] == 0:
        return param, moment1, moment2
    mrows, g, _valid = merge_rows_sentinel(rows, values, height)
    neg_lrt = jnp.reshape(-lr_t, (1, 1)).astype(jnp.float32)

    def rule(tabs, vals, scalars):
        (p, m, v), (g_blk,), (nlrt,) = tabs, vals, scalars
        # expression-for-expression the XLA branch's jaxpr, so XLA makes
        # the SAME fma-contraction choices in both lowerings (see the
        # adagrad note: pre-rounding a factor outside the kernel can
        # change the rounding the contraction would have produced)
        m_row = beta1 * m + (1 - beta1) * g_blk
        v_row = beta2 * v + (1 - beta2) * jnp.square(g_blk)
        # m + (m_row - m), not m_row: the oracle scatter-ADDS the delta,
        # and bitwise parity means reproducing its rounding
        m_new = m + (m_row - m)
        v_new = v + (v_row - v)
        step = nlrt * m_row / (jnp.sqrt(v_row) + epsilon)
        return (p + step, m_new, v_new)

    return _rowwise_call(mrows, [param, moment1, moment2], [g],
                         [neg_lrt], rule, interpret=interpret)

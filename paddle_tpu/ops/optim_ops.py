"""Optimizer update ops.

Reference parity: paddle/operators/{sgd,momentum,adam,adamax,adagrad,
decayed_adagrad,adadelta,rmsprop,ftrl,proximal_gd,proximal_adagrad}_op.*.
Each is a functional update: reads param/grad/moments, returns new values;
the executor's donated persistable state makes them in-place on device.

Sparse grads arrive as a core/selected_rows.SelectedRows (or a raw
(rows, values) pair): sgd/adagrad/adam apply them ROW-WISE — scatter-adds
into the donated buffers, the vocab-height dense grad never materializes
(parity: sgd_op.cc / adagrad_op.cc sparse branches; adam applies lazily
on the touched rows).  Other optimizers densify via scatter-add.

The ROW-WISE apply itself has two interchangeable lowerings, selected
per trace by ops/pallas/table_update.sparse_apply_mode():

  'xla'    — the `.at[rows].add` scatter path below, verbatim (the
             default).  XLA:TPU lowers every scatter as a pass over the
             table operand.
  'pallas' — ops/pallas/table_update.py: a grid over the touched rows
             updates the donated table in place, with Adagrad's
             param+moment (and Adam's param+both-moments) fused into
             ONE kernel pass.  Bitwise-identical to the XLA path
             (tier-1 tests/test_pallas_table_update.py, and on a v5e:
             chip_smoke.py), and slower than it there at the CTR bench
             shape — one grid step per touched row.

PADDLE_TPU_SPARSE_APPLY=pallas pins the kernel path; the resolved mode
is part of the executor's plan cache key, so a flip retraces.
"""
import jax.numpy as jnp

from ..core.registry import register_op
from ..core.selected_rows import SelectedRows, merge_duplicate_rows
from .common import first


def _pallas_rowwise(p, values):
    """True when the Pallas row-walking apply should serve this sparse
    update: mode resolves to pallas and the operand is a rank-2 table
    with matching row width (anything else falls back to the scatter
    path — e.g. rank>2 params the kernels don't block for)."""
    if getattr(p, 'ndim', 0) != 2 or getattr(values, 'ndim', 0) != 2:
        return False
    if p.shape[1] != values.shape[1]:
        return False
    from .pallas.table_update import sparse_apply_mode
    return sparse_apply_mode() == 'pallas'


def _embed_ways(attrs, p, values):
    """Shard count when this sparse apply targets a row-sharded
    embedding table (attrs stamped by the embed_shard pass) AND the
    Pallas row-walk serves it — the engine routes each shard's
    SelectedRows slice onto the kernel over LOCAL rows only.  Under
    PADDLE_TPU_SPARSE_APPLY=xla the global scatter stays (rows < true
    height never touch the sentinel pad rows, so it is equally
    correct, just not shard-local)."""
    ways = int(attrs.get('embed_ways') or 0)
    if ways > 1 and _pallas_rowwise(p, values):
        return ways
    return 0


def _p32(x):
    return x.astype(jnp.float32)


def _as_sparse(grad):
    """Normalize a sparse grad to (rows, values) or None if dense."""
    if isinstance(grad, SelectedRows):
        return grad.rows, grad.values
    if isinstance(grad, tuple):
        rows, values = grad
        return rows.astype(jnp.int32).reshape(-1), _p32(values)
    return None


def _sparse_to_update(param, grad):
    """Densify a sparse grad by scatter-add (fallback for optimizers
    without a row-wise sparse rule)."""
    if isinstance(grad, SelectedRows):
        return grad.to_dense().astype(jnp.float32)
    if isinstance(grad, tuple):
        rows, values = grad
        dense = jnp.zeros(param.shape, jnp.float32)
        return dense.at[rows.astype(jnp.int32).reshape(-1)].add(
            _p32(values))
    return _p32(grad)


@register_op('sgd')
def _sgd(ctx, ins, attrs):
    p = first(ins, 'Param')
    grad = first(ins, 'Grad')
    lr = _p32(first(ins, 'LearningRate')).reshape(())
    sp = _as_sparse(grad)
    if sp is not None:
        # row-wise apply: duplicates accumulate (linear update)
        rows, values = sp
        ways = _embed_ways(attrs, p, values)
        if ways:
            from ..distributed.embedding_engine import sharded_apply_sgd
            p_new = sharded_apply_sgd(
                _p32(p), rows, _p32(values), lr, ways,
                height=int(attrs['embed_height']),
                tile=int(attrs.get('embed_tile', 8)))
            return {'ParamOut': [p_new.astype(p.dtype)]}
        if _pallas_rowwise(p, values):
            from .pallas.table_update import sparse_apply_sgd
            p_new = sparse_apply_sgd(_p32(p), rows, _p32(values), lr)
            return {'ParamOut': [p_new.astype(p.dtype)]}
        p_new = _p32(p).at[rows].add(-lr * _p32(values))
        return {'ParamOut': [p_new.astype(p.dtype)]}
    g = _p32(grad)
    # optional fused L2 weight decay (the scale+sum pair
    # append_regularization_ops would otherwise weave as separate ops)
    wd = attrs.get('weight_decay', 0.0)
    if wd:
        return {'ParamOut': [
            (_p32(p) - lr * (g + jnp.float32(wd) * _p32(p))).astype(
                p.dtype)]}
    return {'ParamOut': [(_p32(p) - lr * g).astype(p.dtype)]}


@register_op('momentum')
def _momentum(ctx, ins, attrs):
    p = first(ins, 'Param')
    g = _sparse_to_update(p, first(ins, 'Grad'))
    v = _p32(first(ins, 'Velocity'))
    lr = _p32(first(ins, 'LearningRate')).reshape(())
    mu = attrs.get('mu', 0.9)
    v_new = mu * v + g
    if attrs.get('use_nesterov', False):
        p_new = _p32(p) - (g + mu * v_new) * lr
    else:
        p_new = _p32(p) - lr * v_new
    return {'ParamOut': [p_new.astype(p.dtype)], 'VelocityOut': [v_new]}


@register_op('adam')
def _adam(ctx, ins, attrs):
    p = first(ins, 'Param')
    grad = first(ins, 'Grad')
    m = _p32(first(ins, 'Moment1'))
    v = _p32(first(ins, 'Moment2'))
    lr = _p32(first(ins, 'LearningRate')).reshape(())
    b1p = _p32(first(ins, 'Beta1Pow')).reshape(())
    b2p = _p32(first(ins, 'Beta2Pow')).reshape(())
    b1 = attrs.get('beta1', 0.9)
    b2 = attrs.get('beta2', 0.999)
    eps = attrs.get('epsilon', 1e-8)
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    sp = _as_sparse(grad)
    if sp is not None:
        # lazy sparse adam: moments decay and the param moves only on
        # touched rows; duplicate rows merge first (nonlinear update)
        rows, values = sp
        ways = _embed_ways(attrs, p, values)
        if ways:
            from ..distributed.embedding_engine import \
                sharded_apply_adam
            p_new, m_new, v_new = sharded_apply_adam(
                _p32(p), m, v, rows, _p32(values), lr_t, b1, b2, eps,
                ways, height=int(attrs['embed_height']),
                tile=int(attrs.get('embed_tile', 8)))
            return {'ParamOut': [p_new.astype(p.dtype)],
                    'Moment1Out': [m_new], 'Moment2Out': [v_new]}
        if _pallas_rowwise(p, values):
            from .pallas.table_update import sparse_apply_adam
            p_new, m_new, v_new = sparse_apply_adam(
                _p32(p), m, v, rows, _p32(values), lr_t, b1, b2, eps)
            return {'ParamOut': [p_new.astype(p.dtype)],
                    'Moment1Out': [m_new], 'Moment2Out': [v_new]}
        rows, g, valid = merge_duplicate_rows(rows, _p32(values))
        vmask = valid[:, None]
        m_row = b1 * m[rows] + (1 - b1) * g
        v_row = b2 * v[rows] + (1 - b2) * jnp.square(g)
        m_new = m.at[rows].add(jnp.where(vmask, m_row - m[rows], 0.0))
        v_new = v.at[rows].add(jnp.where(vmask, v_row - v[rows], 0.0))
        step = -lr_t * m_row / (jnp.sqrt(v_row) + eps)
        p_new = _p32(p).at[rows].add(jnp.where(vmask, step, 0.0))
        return {'ParamOut': [p_new.astype(p.dtype)], 'Moment1Out': [m_new],
                'Moment2Out': [v_new]}
    g = _p32(grad)
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * jnp.square(g)
    p_new = _p32(p) - lr_t * m_new / (jnp.sqrt(v_new) + eps)
    return {'ParamOut': [p_new.astype(p.dtype)], 'Moment1Out': [m_new],
            'Moment2Out': [v_new]}


@register_op('adamax')
def _adamax(ctx, ins, attrs):
    p = first(ins, 'Param')
    g = _sparse_to_update(p, first(ins, 'Grad'))
    m = _p32(first(ins, 'Moment'))
    u = _p32(first(ins, 'InfNorm'))
    lr = _p32(first(ins, 'LearningRate')).reshape(())
    b1p = _p32(first(ins, 'Beta1Pow')).reshape(())
    b1 = attrs.get('beta1', 0.9)
    b2 = attrs.get('beta2', 0.999)
    eps = attrs.get('epsilon', 1e-8)
    m_new = b1 * m + (1 - b1) * g
    u_new = jnp.maximum(b2 * u, jnp.abs(g))
    p_new = _p32(p) - (lr / (1 - b1p)) * m_new / (u_new + eps)
    return {'ParamOut': [p_new.astype(p.dtype)], 'MomentOut': [m_new],
            'InfNormOut': [u_new]}


@register_op('adagrad')
def _adagrad(ctx, ins, attrs):
    p = first(ins, 'Param')
    grad = first(ins, 'Grad')
    mom = _p32(first(ins, 'Moment'))
    lr = _p32(first(ins, 'LearningRate')).reshape(())
    eps = attrs.get('epsilon', 1e-6)
    sp = _as_sparse(grad)
    if sp is not None:
        # reference adagrad_op.cc sparse branch: merge duplicate rows,
        # then accumulate + step on the touched rows only
        rows, values = sp
        ways = _embed_ways(attrs, p, values)
        if ways:
            from ..distributed.embedding_engine import \
                sharded_apply_adagrad
            p_new, mom_new = sharded_apply_adagrad(
                _p32(p), mom, rows, _p32(values), lr, eps, ways,
                height=int(attrs['embed_height']),
                tile=int(attrs.get('embed_tile', 8)))
            return {'ParamOut': [p_new.astype(p.dtype)],
                    'MomentOut': [mom_new]}
        if _pallas_rowwise(p, values):
            from .pallas.table_update import sparse_apply_adagrad
            p_new, mom_new = sparse_apply_adagrad(
                _p32(p), mom, rows, _p32(values), lr, eps)
            return {'ParamOut': [p_new.astype(p.dtype)],
                    'MomentOut': [mom_new]}
        rows, g, valid = merge_duplicate_rows(rows, _p32(values))
        vmask = valid[:, None]
        mom_row = mom[rows] + jnp.square(g)
        mom_new = mom.at[rows].add(jnp.where(vmask, jnp.square(g), 0.0))
        step = -lr * g / (jnp.sqrt(mom_row) + eps)
        p_new = _p32(p).at[rows].add(jnp.where(vmask, step, 0.0))
        return {'ParamOut': [p_new.astype(p.dtype)], 'MomentOut': [mom_new]}
    g = _p32(grad)
    mom_new = mom + jnp.square(g)
    p_new = _p32(p) - lr * g / (jnp.sqrt(mom_new) + eps)
    return {'ParamOut': [p_new.astype(p.dtype)], 'MomentOut': [mom_new]}


@register_op('decayed_adagrad')
def _decayed_adagrad(ctx, ins, attrs):
    p = first(ins, 'Param')
    g = _sparse_to_update(p, first(ins, 'Grad'))
    mom = _p32(first(ins, 'Moment'))
    lr = _p32(first(ins, 'LearningRate')).reshape(())
    decay = attrs.get('decay', 0.95)
    eps = attrs.get('epsilon', 1e-6)
    mom_new = decay * mom + (1 - decay) * jnp.square(g)
    p_new = _p32(p) - lr * g / (jnp.sqrt(mom_new) + eps)
    return {'ParamOut': [p_new.astype(p.dtype)], 'MomentOut': [mom_new]}


@register_op('adadelta')
def _adadelta(ctx, ins, attrs):
    p = first(ins, 'Param')
    g = _sparse_to_update(p, first(ins, 'Grad'))
    avg_sq_grad = _p32(first(ins, 'AvgSquaredGrad'))
    avg_sq_upd = _p32(first(ins, 'AvgSquaredUpdate'))
    rho = attrs.get('rho', 0.95)
    eps = attrs.get('epsilon', 1e-6)
    asg_new = rho * avg_sq_grad + (1 - rho) * jnp.square(g)
    update = -jnp.sqrt((avg_sq_upd + eps) / (asg_new + eps)) * g
    asu_new = rho * avg_sq_upd + (1 - rho) * jnp.square(update)
    return {'ParamOut': [(_p32(p) + update).astype(p.dtype)],
            'AvgSquaredGradOut': [asg_new],
            'AvgSquaredUpdateOut': [asu_new]}


@register_op('rmsprop')
def _rmsprop(ctx, ins, attrs):
    p = first(ins, 'Param')
    g = _sparse_to_update(p, first(ins, 'Grad'))
    ms = _p32(first(ins, 'MeanSquare'))
    mom = _p32(first(ins, 'Moment'))
    lr = _p32(first(ins, 'LearningRate')).reshape(())
    decay = attrs.get('decay', 0.9)
    mu = attrs.get('momentum', 0.0)
    eps = attrs.get('epsilon', 1e-10)
    ms_new = decay * ms + (1 - decay) * jnp.square(g)
    mom_new = mu * mom + lr * g / jnp.sqrt(ms_new + eps)
    return {'ParamOut': [(_p32(p) - mom_new).astype(p.dtype)],
            'MeanSquareOut': [ms_new], 'MomentOut': [mom_new]}


@register_op('ftrl')
def _ftrl(ctx, ins, attrs):
    p = first(ins, 'Param')
    g = _sparse_to_update(p, first(ins, 'Grad'))
    sq = _p32(first(ins, 'SquaredAccumulator'))
    lin = _p32(first(ins, 'LinearAccumulator'))
    lr = _p32(first(ins, 'LearningRate')).reshape(())
    l1 = attrs.get('l1', 0.0)
    l2 = attrs.get('l2', 0.0)
    lr_power = attrs.get('lr_power', -0.5)
    new_sq = sq + jnp.square(g)
    sigma = (jnp.power(new_sq, -lr_power) - jnp.power(sq, -lr_power)) / lr
    new_lin = lin + g - sigma * _p32(p)
    x = jnp.clip(new_lin, -l1, l1) - new_lin
    y = jnp.power(new_sq, -lr_power) / lr + 2 * l2
    p_new = x / y
    return {'ParamOut': [p_new.astype(p.dtype)],
            'SquaredAccumOut': [new_sq], 'LinearAccumOut': [new_lin]}


@register_op('proximal_gd')
def _proximal_gd(ctx, ins, attrs):
    p = first(ins, 'Param')
    g = _sparse_to_update(p, first(ins, 'Grad'))
    lr = _p32(first(ins, 'LearningRate')).reshape(())
    l1 = attrs.get('l1', 0.0)
    l2 = attrs.get('l2', 0.0)
    prox = _p32(p) - lr * g
    p_new = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0) / \
        (1.0 + lr * l2)
    return {'ParamOut': [p_new.astype(p.dtype)]}


@register_op('proximal_adagrad')
def _proximal_adagrad(ctx, ins, attrs):
    p = first(ins, 'Param')
    g = _sparse_to_update(p, first(ins, 'Grad'))
    mom = _p32(first(ins, 'Moment'))
    lr = _p32(first(ins, 'LearningRate')).reshape(())
    l1 = attrs.get('l1', 0.0)
    l2 = attrs.get('l2', 0.0)
    mom_new = mom + jnp.square(g)
    lr_t = lr / jnp.sqrt(mom_new)
    prox = _p32(p) - lr_t * g
    p_new = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr_t * l1, 0.0) / \
        (1.0 + lr_t * l2)
    return {'ParamOut': [p_new.astype(p.dtype)], 'MomentOut': [mom_new]}

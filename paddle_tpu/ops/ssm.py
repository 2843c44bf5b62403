"""The three ops of a selective state-space (Mamba-1) mixer that are not
matmuls or norms: the causal depthwise convolution with carried inputs,
the selective scan over a sequence, and the one-token state update.

No reference parity: the reference predates them.  The equations are
Gu & Dao's (arXiv:2312.00752) as ``transformers``' ``modeling_jamba.py``
runs them.  For a sequence ``u[0..T)`` of ``Dc`` channels with carried
state ``(c, s)``, ``c`` [K - 1, Dc] the inputs before ``u[0]`` and ``s``
[N, Dc] the state:

- ``causal_conv1d``: ``v[t] = act(b + sum_j w[j] * u[t - (K-1) + j])``,
  ``u`` before ``t = 0`` read from ``c``; the new ``c`` is the last
  K - 1 of ``c ++ u[:n_valid]``.
- ``selective_scan``: ``s = exp(dt[t] * A) * s + (dt[t] * v[t]) * B[t]``,
  ``y[t] = sum_n s[n] * C[t][n] + D * v[t]``, with ``dt[t] = 0`` for
  ``t >= n_valid``: a padding row multiplies the state by one and adds
  nothing, so the state stays where token ``n_valid - 1`` left it.
- ``selective_state_update``: the same at ``T = 1`` for R rows at once,
  row r on state row r; a row that is not ``live`` takes ``dt = 0``
  (and, in ``causal_conv1d``, is not shifted into ``c``).

LAYOUT.  Channels are the minor (lane) dimension everywhere: ``s`` and
``A`` are [N, Dc] and the convolution's weight [K, Dc], where the
published tensors are [Dc, N] and [Dc, 1, K].  A [.., Dc, 16] float32
buffer is held by the TPU in tiles of 128 lanes, eight times its bytes.

All three are float32 whatever their inputs (a recurrence over
thousands of steps through ``exp``): AMP black.  On a TPU, for shapes
the kernel takes, ``selective_scan`` runs ops/pallas/selective_scan.py
(the state stays in VMEM for the whole sequence); elsewhere the
``lax.scan`` over tokens below.  The math functions are shared with the
decode engine's Jamba block (inference/blocks.py).
"""
import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import first

_F32 = jnp.float32


def _act(y, activation):
    if activation in (None, 'none'):
        return y
    if activation != 'silu':
        raise ValueError("causal_conv1d: activation %r" % (activation,))
    return y * jax.nn.sigmoid(y)


def causal_conv1d_math(u, w, b, c, n_valid=None, activation='silu'):
    """A sequence: ``u`` [T, Dc], ``w`` [K, Dc], ``b`` [Dc], ``c``
    [K - 1, Dc], ``n_valid`` a scalar (None: T) -> (v [T, Dc], new c).
    One token a row: ``u`` [R, Dc] and ``c`` [R, K - 1, Dc] (``n_valid``
    is then ``live`` [R] bool or None) -> (v [R, Dc], new c)."""
    u, w, b, c = (a.astype(_F32) for a in (u, w, b, c))
    k = w.shape[0]
    if c.ndim == 3:             # one token a row
        v = b + w[k - 1] * u + sum(w[j] * c[:, j] for j in range(k - 1))
        new = jnp.concatenate([c[:, 1:], u[:, None]], axis=1)
        if n_valid is not None:
            new = jnp.where(n_valid[:, None, None], new, c)
        return _act(v, activation), new
    t = u.shape[0]
    ext = jnp.concatenate([c, u], axis=0)   # row r is position r - (K-1)
    v = b + sum(w[j] * ext[j:j + t] for j in range(k))
    n = t if n_valid is None else n_valid
    return _act(v, activation), jax.lax.dynamic_slice_in_dim(
        ext, jnp.asarray(n, jnp.int32), k - 1, axis=0)


def _token(s, v, dt, a, b, c, d):
    """One step of the recurrence for any leading dimensions: ``s``
    [.., N, Dc]; ``v``, ``dt`` [.., Dc]; ``b``, ``c`` [.., N]."""
    s = jnp.exp(dt[..., None, :] * a) * s \
        + (dt * v)[..., None, :] * b[..., :, None]
    return s, jnp.sum(s * c[..., :, None], axis=-2) + d * v


def selective_scan_math(v, dt, a, b, c, d, s0, n_valid=None):
    """``v``, ``dt`` [T, Dc] (``dt`` after its softplus), ``a`` [N, Dc]
    (negative), ``b``, ``c`` [T, N], ``d`` [Dc], ``s0`` [N, Dc],
    ``n_valid`` a scalar (None: T) -> (y [T, Dc], s [N, Dc]).  A
    ``lax.scan`` over the tokens: the plain form, and the CPU's."""
    v, dt, a, b, c, d, s0 = (x.astype(_F32)
                             for x in (v, dt, a, b, c, d, s0))
    if n_valid is not None:
        dt = jnp.where(jnp.arange(v.shape[0])[:, None] < n_valid, dt, 0.0)

    def step(s, x):
        return _token(s, x[0], x[1], a, x[2], x[3], d)

    s, y = jax.lax.scan(step, s0, (v, dt, b, c))
    return y, s


def selective_state_update_math(s, v, dt, a, b, c, d, live=None):
    """One token a row: ``s`` [R, N, Dc], ``v``, ``dt`` [R, Dc], ``b``,
    ``c`` [R, N], ``live`` [R] bool -> (y [R, Dc], s [R, N, Dc])."""
    s, v, dt, a, b, c, d = (x.astype(_F32)
                            for x in (s, v, dt, a, b, c, d))
    if live is not None:
        dt = jnp.where(live[:, None], dt, 0.0)
    s, y = _token(s, v, dt, a, b, c, d)
    return y, s


def selective_scan_path(backend, tokens, channels, n_state):
    """What ``selective_scan`` runs for these shapes: ``'pallas_scan'``
    (ops/pallas/selective_scan.py) on a TPU where the kernel takes
    them, else ``'xla_scan'`` (``selective_scan_math``).  Backend and
    shapes decide, nothing else; the decode engine records the answer
    with its programs' ``decode.compile`` spans."""
    if backend == 'tpu':
        from .pallas.selective_scan import supported
        if supported(tokens, channels, n_state):
            return 'pallas_scan'
    return 'xla_scan'


def selective_scan(v, dt, a, b, c, d, s0, n_valid, backend=None):
    """``selective_scan_math``'s signature and result, by
    ``selective_scan_path``."""
    backend = backend or jax.default_backend()
    scan = selective_scan_math
    if selective_scan_path(backend, v.shape[0], v.shape[1],
                           a.shape[0]) == 'pallas_scan':
        from .pallas.selective_scan import selective_scan as scan
    return scan(v, dt, a, b, c, d, s0,
                jnp.asarray(v.shape[0] if n_valid is None else n_valid,
                            jnp.int32).reshape(()))


# -- what the equations cost ------------------------------------------------

def scan_ops(tokens, channels, n_state):
    """Vector operations of the recurrence over ``tokens``: a token a
    channel a state lane is one exp, three products and two sums (the
    state's decay, its input, the read-out), and ``D * v`` beside."""
    return tokens * channels * (6 * n_state + 2)


def scan_bytes(tokens, channels, n_state, rows=1, itemsize=4):
    """Bytes the recurrence has to move for ``rows`` sequences of
    ``tokens`` each: the state in and out once a sequence, ``v``, ``dt``
    and ``y`` once a token a channel, ``B`` and ``C`` once a token."""
    return rows * itemsize * (2 * n_state * channels
                              + tokens * (3 * channels + 2 * n_state))


# -- registry ops -----------------------------------------------------------

@register_op('causal_conv1d')
def _causal_conv1d(ctx, ins, attrs):
    """Causal depthwise convolution with carried inputs: X [T, Dc] (one
    sequence; State [K - 1, Dc]; NValid a scalar, optional) or X [R, Dc]
    (one token a row; State [R, K - 1, Dc]; Live [R], optional), W
    [K, Dc], Bias [Dc]; ``activation`` 'silu' (the default) or 'none'.
    Out as X, StateOut as State: the last K - 1 valid inputs."""
    state = first(ins, 'State')
    mask = first(ins, 'Live' if state.ndim == 3 else 'NValid')
    if mask is not None:
        mask = mask.astype(bool) if state.ndim == 3 \
            else jnp.asarray(mask, jnp.int32).reshape(())
    v, new = causal_conv1d_math(
        first(ins, 'X'), first(ins, 'W'), first(ins, 'Bias'), state, mask,
        attrs.get('activation', 'silu'))
    return {'Out': [v], 'StateOut': [new]}


@register_op('selective_scan')
def _selective_scan(ctx, ins, attrs):
    """The selective scan of one sequence: X, Dt [T, Dc], A [N, Dc], B,
    C [T, N], D [Dc], State [N, Dc], NValid a scalar (optional: T).
    Out [T, Dc], StateOut [N, Dc]; rows from NValid on leave the state
    alone.  On a TPU a Pallas kernel, the state in VMEM for the whole
    sequence, else a scan over the tokens."""
    y, s = selective_scan(
        first(ins, 'X'), first(ins, 'Dt'), first(ins, 'A'),
        first(ins, 'B'), first(ins, 'C'), first(ins, 'D'),
        first(ins, 'State'), first(ins, 'NValid'),
        backend=getattr(ctx, 'backend', None))
    return {'Out': [y], 'StateOut': [s]}


@register_op('selective_state_update')
def _selective_state_update(ctx, ins, attrs):
    """``selective_scan`` at one token a row, row r on state row r: X,
    Dt [R, Dc], A [N, Dc], B, C [R, N], D [Dc], State [R, N, Dc], Live
    [R] (optional).  Out [R, Dc], StateOut [R, N, Dc]; a row that is
    not live leaves its state alone."""
    live = first(ins, 'Live')
    y, s = selective_state_update_math(
        first(ins, 'State'), first(ins, 'X'), first(ins, 'Dt'),
        first(ins, 'A'), first(ins, 'B'), first(ins, 'C'),
        first(ins, 'D'), None if live is None else live.astype(bool))
    return {'Out': [y], 'StateOut': [s]}

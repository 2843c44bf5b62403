"""The selective scan of one sequence as a Pallas TPU kernel
(ops/ssm.py ``selective_scan_math`` is the plain form).

``s = exp(dt[t] * A) * s + (dt[t] * v[t]) * B[t]``, ``y[t] = sum_n s[n]
* C[t][n] + D * v[t]`` is a chain over the tokens, a few vector
operations a token a state element and no matmul.  As a ``lax.scan`` it
is one tiny program a token (512 x 26 of them a chunk); as an
associative scan it materialises [T, Dc, N] float32, 168 MB a layer for
a chunk of 512.  Here the whole state [N, Dc] (16 x 5120 float32: 320
KB) stays in VMEM from the sequence's first token to its last and is
read from and written to HBM once.

Grid ``(T / tb, Dc / bc)``, channels innermost: a step takes ``tb``
tokens of ``bc`` channels (lanes), walks them in groups of eight (one
[8, bc] tile of ``v`` and ``dt`` in, one of ``y`` out), and carries
``s[:, block]`` in registers through the group's eight tokens.  ``B``
and ``C`` come in spread over 128 lanes ([T, N, 128]; a block of them
serves every channel block of its tokens and is fetched once), so a
token's [N, 128] tile is laid beside itself ``bc / 128`` times and no
lane is moved.  ``n_valid`` is scalar-prefetched: rows from it on take
``dt = 0`` (the state stays where token ``n_valid - 1`` left it), and a
block of tokens wholly past it writes zeros and does no work.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['selective_scan', 'supported']

_TOKENS = 128       # tokens a grid step
_LANES = 128
_GROUP = 8          # tokens a tile of v, dt and y


def _channel_block(channels):
    return next((b for b in (512, 256, 128) if channels % b == 0), None)


def supported(tokens, channels, n_state):
    """Whether the kernel takes these shapes: tokens in whole tiles of
    eight (and whole blocks of ``_TOKENS`` past that), channels in whole
    128-lane registers, a state of whole float32 sublane tiles."""
    return tokens % _GROUP == 0 and \
        (tokens <= _TOKENS or tokens % _TOKENS == 0) and \
        _channel_block(channels) is not None and n_state % 8 == 0


def _kernel(nv_ref, v_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s0_ref,
            y_ref, s_ref, s_scr, *, tb, bc):
    j, i = pl.program_id(0), pl.program_id(1)
    lanes = pl.ds(pl.multiple_of(i * bc, bc), bc)

    @pl.when(j == 0)
    def _load():
        s_scr[:, lanes] = s0_ref[:, lanes]

    n_valid, start = nv_ref[0], j * tb

    @pl.when(start < n_valid)
    def _run():
        a, d = a_ref[...], d_ref[...]           # [N, bc], [1, bc]
        row = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, 1), 0)

        def group(g, s):
            t0 = pl.multiple_of(g * _GROUP, _GROUP)
            v = v_ref[pl.ds(t0, _GROUP), :]     # [8, bc]
            dt = jnp.where(start + t0 + row < n_valid,
                           dt_ref[pl.ds(t0, _GROUP), :], 0.0)
            ys = []
            for r in range(_GROUP):
                vr, dr = v[r:r + 1], dt[r:r + 1]            # [1, bc]
                b = jnp.tile(b_ref[t0 + r], (1, bc // _LANES))   # [N, bc]
                c = jnp.tile(c_ref[t0 + r], (1, bc // _LANES))
                s = jnp.exp(dr * a) * s + (dr * vr) * b
                ys.append(jnp.sum(s * c, axis=0, keepdims=True) + d * vr)
            y_ref[pl.ds(t0, _GROUP), :] = jnp.concatenate(ys, axis=0)
            return s

        s_scr[:, lanes] = jax.lax.fori_loop(0, tb // _GROUP, group,
                                            s_scr[:, lanes])

    @pl.when(start >= n_valid)
    def _skip():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(j == pl.num_programs(0) - 1)
    def _store():
        s_ref[:, lanes] = s_scr[:, lanes]


@functools.partial(jax.jit, static_argnames=('interpret',))
def selective_scan(v, dt, a, b, c, d, s0, n_valid, interpret=False):
    """``selective_scan_math``'s signature and result: ``v``, ``dt``
    [T, Dc] float32, ``a`` [N, Dc], ``b``, ``c`` [T, N], ``d`` [Dc],
    ``s0`` [N, Dc], ``n_valid`` an int32 scalar -> (y [T, Dc], s
    [N, Dc]).  The caller tests ``supported`` first."""
    f32 = jnp.float32
    t, dc = v.shape
    n = a.shape[0]
    tb, bc = min(t, _TOKENS), _channel_block(dc)
    wide = lambda x: jnp.broadcast_to(      # noqa: E731
        x.astype(f32)[:, :, None], (t, n, _LANES))
    tokens = pl.BlockSpec((tb, bc), lambda j, i, nv: (j, i))
    spread = pl.BlockSpec((tb, n, _LANES), lambda j, i, nv: (j, 0, 0))
    whole = pl.BlockSpec((n, dc), lambda j, i, nv: (0, 0))
    y, s = pl.pallas_call(
        functools.partial(_kernel, tb=tb, bc=bc),
        out_shape=(jax.ShapeDtypeStruct((t, dc), f32),
                   jax.ShapeDtypeStruct((n, dc), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t // tb, dc // bc),
            in_specs=[tokens, tokens,
                      pl.BlockSpec((n, bc), lambda j, i, nv: (0, i)),
                      spread, spread,
                      pl.BlockSpec((1, bc), lambda j, i, nv: (0, i)),
                      whole],
            out_specs=(tokens, whole),
            scratch_shapes=[pltpu.VMEM((n, dc), f32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary')),
        name='selective_scan',
        interpret=interpret,
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), v.astype(f32),
      dt.astype(f32), a.astype(f32), wide(b), wide(c),
      d.astype(f32).reshape(1, dc), s0.astype(f32))
    return y, s

"""Flash-attention per-phase perf harness (PERF.md roofline data).

Times the three Pallas kernels (fwd, dkv, dq) in isolation and the full
fwd+bwd train step, at a chosen tile config, with chained iterations so
one host sync times the whole run.

Reports BOTH FLOP accountings:
  * executed TFLOPS — MACs the kernels actually run (causal alive-tile
    fraction, dkv 4 matmuls / dq 3 matmuls incl. the s/dp recomputes)
  * bench TFLOPS   — the bench_attention.py convention
    (4*B*H*T^2*D * 0.5 causal * [1 fwd | 2.5 bwd]).

Usage: python benchmarks/exp_flash.py [--phase fwd|dkv|dq|full]
         [--bq 1024] [--bk 1024] [--B 16] [--T 8192] [--steps 10]
"""
import argparse
import json
import time

import numpy as np

import common  # noqa: F401


def alive_fraction(t, bq, bk, causal):
    """Fraction of (q, k) tiles the causal dead-tile skip actually runs."""
    if not causal:
        return 1.0
    nq, nk = -(-t // bq), -(-t // bk)
    alive = sum(1 for qi in range(nq) for ki in range(nk)
                if (qi * bq + bq - 1) >= ki * bk)
    return alive / (nq * nk)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--phase', default='all',
                    choices=['fwd', 'dkv', 'dq', 'bwd', 'full', 'all'])
    ap.add_argument('--bq', type=int, default=None)
    ap.add_argument('--bk', type=int, default=None)
    ap.add_argument('--B', type=int, default=16)
    ap.add_argument('--T', type=int, default=8192)
    ap.add_argument('--H', type=int, default=8)
    ap.add_argument('--D', type=int, default=64)
    # 100-step chains: short chains fold the per-launch cost into
    # every step
    ap.add_argument('--steps', type=int, default=100)
    ap.add_argument('--causal', type=int, default=1)
    args = ap.parse_args()

    import importlib

    import jax
    import jax.numpy as jnp
    fa = importlib.import_module('paddle_tpu.ops.pallas.flash_attention')

    tpu = common.on_tpu()
    B, T, H, D = args.B, args.T, args.H, args.D
    causal = bool(args.causal)
    scale = D ** -0.5
    auto = 1024 if D <= 64 else 512
    bq = args.bq or auto
    bk = args.bk or auto
    interp = not tpu

    rng = np.random.default_rng(0)
    dt = jnp.bfloat16 if tpu else jnp.float32
    BH = B * H
    q = jnp.asarray(rng.normal(size=(BH, T, D)), dt)
    k = jnp.asarray(rng.normal(size=(BH, T, D)), dt)
    v = jnp.asarray(rng.normal(size=(BH, T, D)), dt)

    o, lse = jax.jit(lambda q, k, v: fa._fa_forward_sliced(
        q, k, v, causal, scale, bq, bk, interp))(q, k, v)
    do = jnp.asarray(rng.normal(size=(BH, T, D)), dt)

    frac = alive_fraction(T, bq, bk, causal)
    base = 2 * BH * T * T * D * frac  # MACs*2 of ONE [T,T,D] matmul pass

    def timeit(stepfn, *state):
        """stepfn: state -> state.  K steps ride ONE lax.scan inside one
        jit, and one scalar pull syncs the chain."""
        @jax.jit
        def chain(*state):
            def body(c, _):
                return stepfn(*c), None
            out, _ = jax.lax.scan(body, state, None, length=args.steps)
            return out
        cur = chain(*state)
        np.asarray(jax.tree_util.tree_leaves(cur)[0][0, 0])  # compile+sync
        best = []
        for _ in range(3):
            t0 = time.perf_counter()
            cur = chain(*state)
            np.asarray(jax.tree_util.tree_leaves(cur)[0][0, 0])
            best.append((time.perf_counter() - t0) / args.steps)
        return float(np.median(best))

    results = {}
    phases = ([args.phase] if args.phase != 'all'
              else ['fwd', 'dkv', 'dq', 'full'])

    for ph in phases:
        if ph == 'fwd':
            def fwd_step(q, k, v):
                o, _ = fa._fa_forward_sliced(q, k, v, causal, scale,
                                             bq, bk, interp)
                return (q - 1e-6 * o).astype(q.dtype), k, v
            dt_s = timeit(fwd_step, q, k, v)
            executed = 2 * base  # qk + pv
            bench = 4 * BH * T * T * D * (0.5 if causal else 1.0)
        elif ph in ('dkv', 'dq', 'bwd'):
            def bwd_step(q, k, v, o, lse, do, _ph=ph):
                res = (q, k, v, jnp.int32(0), jnp.int32(0), o, lse)
                gq, gk, gv = fa._fa_backward_pallas(
                    causal, scale, ((bq, bk), (bq, bk)), res, do, None,
                    interp,
                    phases=(('dkv', 'dq') if _ph == 'bwd' else (_ph,)),
                    allow_fused=(_ph == 'bwd'))
                if _ph == 'dq':
                    q = (q - 1e-6 * gq).astype(q.dtype)
                elif _ph == 'dkv':
                    k = (k - 1e-6 * gk).astype(k.dtype)
                    v = (v - 1e-6 * gv).astype(v.dtype)
                else:
                    q = (q - 1e-6 * gq).astype(q.dtype)
                    k = (k - 1e-6 * gk).astype(k.dtype)
                    v = (v - 1e-6 * gv).astype(v.dtype)
                return q, k, v, o, lse, do
            dt_s = timeit(bwd_step, q, k, v, o, lse, do)
            # dkv kernel: s, dp, dv, dk matmuls; dq kernel: s, dp, dq;
            # fused bwd: s, dp, dv, dk, dq
            executed = {'dkv': 4, 'dq': 3, 'bwd': 5}[ph] * base
            bench = None
        else:  # full train step, the bench_attention.py shape
            def loss(q, k, v):
                # None tiles -> the kernel's per-phase defaults
                return jnp.sum(fa.flash_attention(
                    q, k, v, causal=causal, block_q=args.bq,
                    block_k=args.bk,
                    interpret=interp).astype(jnp.float32))

            def step(q, k, v):
                # all three grads feed the next state: consuming only dq
                # lets XLA dead-code-eliminate the whole dkv kernel
                dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
                return ((q - 1e-3 * dq).astype(q.dtype),
                        (k - 1e-3 * dk).astype(k.dtype),
                        (v - 1e-3 * dv).astype(v.dtype))
            qB = q.reshape(B, H, T, D).transpose(0, 2, 1, 3)
            kB = k.reshape(B, H, T, D).transpose(0, 2, 1, 3)
            vB = v.reshape(B, H, T, D).transpose(0, 2, 1, 3)
            dt_s = timeit(step, qB, kB, vB)
            if args.bq or args.bk:
                # pinned tiles: fwd 2 matmuls + fused bwd 5, one frac
                executed = 7 * base
            else:
                # per-phase default tiles -> per-phase alive fractions
                f_fwd = alive_fraction(T, 2048, 1024, causal)
                f_bwd = alive_fraction(T, 1024, 2048, causal)
                executed = 2 * BH * T * T * D * (2 * f_fwd + 5 * f_bwd)
            bench = 4 * BH * T * T * D * (0.5 if causal else 1.0) * 3.5
        results[ph] = {
            'ms': round(dt_s * 1e3, 3),
            'executed_tflops': round(executed / dt_s / 1e12, 2),
        }
        if bench is not None:
            results[ph]['bench_tflops'] = round(bench / dt_s / 1e12, 2)

    print(json.dumps({
        'config': {'B': B, 'T': T, 'H': H, 'D': D, 'bq': bq, 'bk': bk,
                   # 'full' with unpinned tiles runs the kernel's
                   # per-phase defaults, not the bq/bk shown here
                   'tiles_pinned': bool(args.bq or args.bk),
                   'causal': causal, 'alive_frac': round(frac, 4),
                   'dtype': str(dt.__name__ if hasattr(dt, '__name__')
                                else dt)},
        'phases': results,
    }))


if __name__ == '__main__':
    main()

"""Per-conv FLOPs roofline for the ResNet-50 b64 train step (PERF.md
round-5 item #4).

Times every distinct conv shape of ResNet-50 at 224² NHWC bf16 in
isolation — forward, input-grad (dgrad) and weight-grad (wgrad) each as
their own jitted chain (grad-of-sum DCEs the other kernels, so each
number is one conv kind) — and reports achieved TFLOPS against the
~192 TFLOPS measured device peak.  K-step lax.scan chains amortize the
launch cost.

Usage: python benchmarks/exp_conv.py [--steps 30] [--batch 64]
"""
import argparse
import json
import time

import numpy as np

import common
from common import on_tpu

# (name, HW_in, Cin, Cout, k, stride, count) — ResNet-50 @ 224,
# counts include the projection 1x1s
SHAPES = [
    ('stem7x7', 224, 3, 64, 7, 2, 1),
    # MLPerf-style space-to-depth(2) stem: [224,224,3] -> [112,112,12],
    # the 7x7/2 (zero-padded to 8x8) becomes 4x4/1 at 12 channels —
    # same math, 4x the MXU channel occupancy, 1.3x the nominal FLOPs
    ('stem_s2d2', 112, 12, 64, 4, 1, 0),
    ('s1_1x1a', 56, 64, 64, 1, 1, 3),      # first uses Cin=64; blocks
    ('s1_1x1a256', 56, 256, 64, 1, 1, 2),  # 2-3 read the 256-wide trunk
    ('s1_3x3', 56, 64, 64, 3, 1, 3),
    # channel-pad probe for the worst real-path shape: same spatial
    # geometry with Cin=128 (2x the MACs) — if it is not ~2x slower,
    # the C=64 contraction is underfeeding the MXU
    ('s1_3x3_c128', 56, 128, 64, 3, 1, 0),
    ('s1_1x1b', 56, 64, 256, 1, 1, 3),
    ('s1_proj', 56, 64, 256, 1, 1, 1),
    ('s2_1x1a', 56, 256, 128, 1, 2, 1),    # stride-2 entry
    ('s2_1x1a512', 28, 512, 128, 1, 1, 3),
    ('s2_3x3', 28, 128, 128, 3, 1, 4),
    ('s2_1x1b', 28, 128, 512, 1, 1, 4),
    ('s2_proj', 56, 256, 512, 1, 2, 1),
    ('s3_1x1a', 28, 512, 256, 1, 2, 1),
    ('s3_1x1a1024', 14, 1024, 256, 1, 1, 5),
    ('s3_3x3', 14, 256, 256, 3, 1, 6),
    ('s3_1x1b', 14, 256, 1024, 1, 1, 6),
    ('s3_proj', 28, 512, 1024, 1, 2, 1),
    ('s4_1x1a', 14, 1024, 512, 1, 2, 1),
    ('s4_1x1a2048', 7, 2048, 512, 1, 1, 2),
    ('s4_3x3', 7, 512, 512, 3, 1, 3),
    ('s4_1x1b', 7, 512, 2048, 1, 1, 3),
    ('s4_proj', 14, 1024, 2048, 1, 2, 1),
]

PEAK_TFLOPS = 192.0  # measured square-matmul device peak (PERF.md)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=30)
    ap.add_argument('--batch', type=int, default=64)
    ap.add_argument('--only', default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    tpu = on_tpu()
    B = args.batch if tpu else 2
    steps = args.steps if tpu else 2
    dt = jnp.bfloat16 if tpu else jnp.float32
    dn = lax.conv_dimension_numbers((1, 1, 1, 1), (1, 1, 1, 1),
                                    ('NHWC', 'HWIO', 'NHWC'))

    def timeit(stepfn, *state):
        """Two-chain-length fit: wall(K) = K*t_dev + L where L is the
        per-launch cost — the slope between K and 8K cancels L exactly
        (at sub-ms conv times a single-K estimate is mostly L)."""
        k1, k2 = steps, 8 * steps

        def make(k):
            @jax.jit
            def chain(*state):
                def body(c, _):
                    return stepfn(*c), None
                out, _ = jax.lax.scan(body, state, None, length=k)
                return out
            return chain

        def sync(cur):
            # gather ONE scalar on-device before pulling: np.asarray on
            # the whole carry would copy 100+ MB to the host
            leaf = jax.tree_util.tree_leaves(cur)[0]
            np.asarray(leaf[(0,) * leaf.ndim])

        walls = []
        for k in (k1, k2):
            chain = make(k)
            cur = chain(*state)
            sync(cur)
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                cur = chain(*state)
                sync(cur)
                ts.append(time.perf_counter() - t0)
            walls.append(float(np.median(ts)))
        return max((walls[1] - walls[0]) / (k2 - k1), 1e-9)

    rng = np.random.default_rng(0)
    rows = []
    for (name, hw, cin, cout, k, stride, count) in SHAPES:
        if args.only and args.only != name:
            continue
        if not tpu and hw > 56:
            continue
        x = jnp.asarray(rng.normal(size=(B, hw, hw, cin)) * 0.1, dt)
        w = jnp.asarray(rng.normal(size=(k, k, cin, cout)) * 0.1, dt)
        pad = 'SAME'
        hwo = -(-hw // stride)
        flops = 2 * B * hwo * hwo * cout * cin * k * k

        def conv(x, w):
            # bf16 in/out: XLA:TPU convs accumulate fp32 internally;
            # keeping io dtypes uniform lets the vjp's transposed convs
            # trace without cotangent-dtype mismatches
            return lax.conv_general_dilated(
                x, w, (stride, stride), pad, dimension_numbers=dn)

        y0 = jnp.zeros((B, hwo, hwo, cout), dt)

        def fwd_step(x, w):
            y = conv(x, w)
            # scalar feedback serializes the chain without reshaping y
            return (x * (1 + 1e-6 * jnp.mean(y).astype(dt))), w

        # dgrad/wgrad chain the COTANGENT through the previous grad: a
        # constant cotangent makes the transposed conv loop-invariant
        # and XLA hoists it out of the scan (measured: slope -> 0)
        def dgrad_step(ct, x, w):
            _, vjp = jax.vjp(lambda x: conv(x, w), x)
            dx, = vjp(ct)
            return (ct * (1 + 1e-6 * jnp.mean(dx).astype(dt))), x, w

        def wgrad_step(ct, x, w):
            _, vjp = jax.vjp(lambda w: conv(x, w), w)
            dw, = vjp(ct)
            return (ct * (1 + 1e-6 * jnp.mean(dw).astype(dt))), x, w

        r = {'name': name, 'hw': hw, 'cin': cin, 'cout': cout, 'k': k,
             'stride': stride, 'count': count,
             'gflop': round(flops / 1e9, 2)}
        for kind, fn, st in (('fwd', fwd_step, (x, w)),
                             ('dgrad', dgrad_step, (y0 + 1, x, w)),
                             ('wgrad', wgrad_step, (y0 + 1, x, w))):
            dt_s = timeit(fn, *st)
            r[kind + '_ms'] = round(dt_s * 1e3, 3)
            r[kind + '_tflops'] = round(flops / dt_s / 1e12, 1)
            r[kind + '_pct_peak'] = round(
                100 * flops / dt_s / 1e12 / PEAK_TFLOPS, 1)
        rows.append(r)
        print(json.dumps(r))

    tot = {'metric': 'resnet50_conv_roofline_summary', 'batch': B}
    for kind in ('fwd', 'dgrad', 'wgrad'):
        tot[kind + '_total_ms'] = round(
            sum(r[kind + '_ms'] * r['count'] for r in rows), 2)
    tot['weighted_tflops'] = round(
        sum(r['gflop'] * r['count'] * 3 for r in rows) / 1e3 /
        (tot['fwd_total_ms'] + tot['dgrad_total_ms'] +
         tot['wgrad_total_ms']), 1)
    print(json.dumps(tot))


if __name__ == '__main__':
    main()

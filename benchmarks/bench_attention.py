"""Long-context attention benchmark: Pallas flash-attention kernel
(ops/pallas/flash_attention.py) at long sequence lengths on one chip.

The reference's attention (fluid nets.scaled_dot_product_attention over
matmul/softmax ops) materializes the [T, T] score matrix — at T=8192 that
is 2 GB/head-batch in fp32 and does three HBM passes; the flash kernel
keeps the online-softmax state in VMEM (one pass).  Multi-chip sequence
parallelism over this kernel is parallel/ring_attention.py (tested on the
virtual mesh; see test_parallel.py).

Prints ONE JSON line: causal attention fwd+bwd tokens/s at the longest
sequence that fits, plus achieved TFLOPS.
"""
import json
import time

import numpy as np

import common  # noqa: F401  (sys.path bootstrap)


def main():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention

    tpu = common.on_tpu()
    if tpu:
        # B=16 fills the chip.  r5: honest fwd+bwd (the r1-r4 ~57
        # TFLOPS lines had the dkv kernel DCE'd away — see the step()
        # comment), K=50 scan chains; PERF.md has the per-phase
        # roofline
        B, T, H, D = 16, 8192, 8, 64
        steps = 50
    else:
        B, T, H, D = 1, 512, 2, 32
        steps = 2

    rng = np.random.default_rng(0)
    # f32-vs-bf16 side by side (PERF.md AMP table): bf16 is what the
    # PADDLE_TPU_AMP=bf16 pass feeds this white-listed kernel, f32 is
    # the full-precision baseline it replaces
    for dt, amp_label in ((jnp.float32, 'off'), (jnp.bfloat16, 'bf16')):
        _run_one(rng, flash_attention, B, T, H, D, steps, dt,
                 amp_label, tpu)


def _run_one(rng, flash_attention, B, T, H, D, steps, dt, amp_label,
             tpu):
    import jax
    import jax.numpy as jnp

    q = jnp.asarray(rng.normal(size=(B, T, H, D)), dt)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)), dt)
    v = jnp.asarray(rng.normal(size=(B, T, H, D)), dt)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    # K steps as ONE lax.scan chain, (q, k, v) <- sgd(step): the chain
    # serializes on-device and ONE scalar pull syncs it.
    # ALL THREE grads must feed the chain: consuming only dq lets XLA
    # dead-code-eliminate the dkv backward kernel outright (the r1-r4
    # lines did exactly that — they timed fwd+dq, not fwd+bwd).
    def step(q, k, v):
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return ((q - 1e-3 * dq).astype(q.dtype),
                (k - 1e-3 * dk).astype(k.dtype),
                (v - 1e-3 * dv).astype(v.dtype))

    @jax.jit
    def chain(q, k, v):
        def body(c, _):
            return step(*c), None
        out, _ = jax.lax.scan(body, (q, k, v), None, length=steps)
        return out

    qq, kk, vv = chain(q, k, v)
    np.asarray(qq[0, 0, 0])  # compile + sync

    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        qq, kk, vv = chain(q, k, v)
        np.asarray(qq[0, 0, 0])  # sync the whole chain
        samples.append((time.perf_counter() - t0) / steps)
    dt_s = float(np.median(samples))

    tokens_s = B * T / dt_s
    # causal fwd 2*B*H*T^2*D MACs * 0.5, bwd ~2.5x fwd (flash recompute)
    flops = 4 * B * H * T * T * D * 0.5 * 3.5
    print(json.dumps({
        "metric": "flash_attention_causal_train_tokens_per_sec",
        "value": round(tokens_s, 2),
        "achieved_tflops": round(flops / dt_s / 1e12, 2),
        "dtype": str(np.dtype(dt)) if dt != jnp.bfloat16 else "bfloat16",
        "amp": amp_label,
        "note": "B=%d T=%d H=%d D=%d fwd+bwd%s" % (
            B, T, H, D, '' if tpu else ' cpu-smoke'),
    }))


if __name__ == '__main__':
    main()

"""Comparison (C) of the OLMoE configuration, on the chip only: ONE
expert layer at the published widths (64 experts of 2048 x 1024, 8 a
token, 512 tokens, bf16 weights) with the routing GIVEN — the
reference's own indices and weights are fed to the system's expert
computation, so no rank-8/rank-9 flip can occur and what is left is the
arithmetic of the grouped matmuls alone.  Skips without a TPU; the
builder runs it through the chip tool:

    python3 -m pytest chipbench/tests/test_olmoe_chip.py -s
"""
import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

# max|got - want| / max|want| of the layer's output.  The system rounds
# the normed input and the weighted gated product to bf16 (2^-9 relative
# each) before its matmuls and accumulates in f32; the reference
# multiplies the same bf16 weights by f32 activations at ``highest``.
# Measured 2.88e-3 on the chip (my chip run, PR 26); the bar is three
# times that.  Experts computed below the stated precision fail it: the
# test also runs the system's computation on inputs and weights cut to 4
# mantissa bits (a scaled float8) and asserts that it does.  A wrong
# expert for one token moves that row by its own scale (error 0.1-1).
EXPERTS_TOL = 9e-3


@pytest.fixture(scope='module')
def tpu():
    import jax
    if jax.devices()[0].platform != 'tpu':
        pytest.skip('comparison (C) runs at the published widths on a TPU')
    return jax.devices()[0]


def test_expert_layer_with_given_routing(tpu):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.moe import moe_experts, moe_route
    with open(os.path.join(HERE, '..', 'configs', 'olmoe-1b-7b.json')) as f:
        c = json.load(f)
    d, f_, e, k = (c['hidden_size'], c['intermediate_size'],
                   c['num_experts'], c['num_experts_per_tok'])
    std = c['assumed']['expert_init_std']
    keys = jax.random.split(jax.random.PRNGKey(3000000026), 5)
    bf16 = jnp.bfloat16
    x = jax.random.normal(keys[0], (512, d), jnp.float32)
    router = jax.random.normal(keys[1], (d, e), jnp.float32) * 0.02
    gate = (jax.random.normal(keys[2], (e, d, f_)) * std).astype(bf16)
    up = (jax.random.normal(keys[3], (e, d, f_)) * std).astype(bf16)
    down = (jax.random.normal(keys[4], (e, f_, d)) * std).astype(bf16)

    @jax.jit
    def reference(x, gate, up, down):
        with jax.default_matmul_precision('highest'):
            w, idx = moe_route(x, router, k)
            r = jnp.zeros((x.shape[0], e)).at[
                jnp.arange(x.shape[0])[:, None], idx].set(w)
            f32 = jnp.float32
            g = jnp.einsum('td,edf->etf', x, gate.astype(f32))
            u = jnp.einsum('td,edf->etf', x, up.astype(f32))
            o = jnp.einsum('etf,efd->etd', jax.nn.silu(g) * u,
                           down.astype(f32))
            return jnp.einsum('etd,te->td', o, r), w, idx

    want, w, idx = reference(x, gate, up, down)
    got = jax.jit(moe_experts)(x, w, idx, gate, up, down)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    def cut(a, bits=4):
        """``a`` rounded to ``bits`` mantissa bits, exponent kept."""
        m, ex = jnp.frexp(a.astype(jnp.float32))
        return jnp.ldexp(jnp.round(m * 2 ** bits) / 2 ** bits, ex)

    low = jax.jit(moe_experts)(cut(x), w, idx, cut(gate).astype(bf16),
                               cut(up).astype(bf16), cut(down).astype(bf16))
    err_low = float(jnp.max(jnp.abs(low - want)) / jnp.max(jnp.abs(want)))
    print('EXPERTS_GIVEN_ROUTING', json.dumps(
        {'rel_err': err, 'rel_err_4_mantissa_bits': err_low,
         'tol': EXPERTS_TOL,
         'experts_touched': int(len(np.unique(np.asarray(idx))))}))
    assert err <= EXPERTS_TOL
    assert err_low > EXPERTS_TOL

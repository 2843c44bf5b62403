"""Plain reference for an ``olmoe_serve`` configuration: the OLMoE
decoder (Muennighoff et al., arXiv:2409.02060; the block of
allenai/OLMoE-1B-7B-0125-Instruct's config.json, ``model_type`` olmoe:
pre-RMSNorm, QK-norm over the whole projected row before the split into
heads, rotary positions with the half-split "rotate_half" pairing, full
multi-head attention, 64 SiLU-gated experts of which each token takes
its 8 largest by a float32 softmax router, weights not renormalised, no
shared expert, no biases, untied head) as one full-context forward pass
in float32 ``jax.numpy`` at ``highest`` matmul precision.  No cache, no
pages, no batching, no dispatch, no program code: every expert is
computed densely for every token and masked by top-k membership.

Departures from the published model: none in the layer equations.  The
weights are read by the program's fixed ``olmoe_*`` names
(models/olmoe.py): ``olmoe_embed`` [V, D]; per layer ``in_norm_w``,
``q_w``/``k_w``/``v_w`` [D, D] (input-major, so ``h @ W``; the
checkpoint stores the transpose), ``q_norm_w``/``k_norm_w`` [D],
``o_w`` [D, D], ``post_norm_w``, ``router_w`` [D, E], ``gate_w``/
``up_w`` [E, D, F], ``down_w`` [E, F, D] (the experts stacked); then
``olmoe_norm_f_w`` and ``olmoe_head_w`` [D, V].  Weights held in
bfloat16 are widened to float32 and used as the values they are.

What the weights' shapes do not give is fixed here: 8 experts a token
(``top_k``), theta 10000, eps 1e-5, no renormalisation.

TOLERANCE.  The error is max|got - want| over max|want| of the logits
of one request.  The system holds weights and cache in bfloat16 and
multiplies bf16 x bf16 into f32 (activations rounded to 8 mantissa
bits, 2^-9 an operand, before every matmul); this reference multiplies
the same bf16 weights at ``highest`` with float32 activations.
Measured on the chip at the published widths, 8 layers, with the
configuration's seeded weights (my chip runs, PR 26; 25 seeds, weights
and prompts from the seed, prompts of 48 and 200 tokens, 6 positions
each, 50 readings): 0.0032-0.0120, median 0.0050 (OPT: 0.0051-0.0074).
The same equations with both inputs of every matmul cut to 4 mantissa
bits (a scaled float8, the nearest precision below the stated one; 24
readings): 0.081-0.190.  LOGITS_TOL 2.8e-2 is three times the largest
error of the 12 validation seeds (0.0093), 2.3 times the largest of all
50 readings, and a third of the smallest 4-bit reading: weights, cache
or matmul inputs held below the stated precision are not correct.

How much two correct computations differ depends on the seeded weights,
and the scales were chosen for that (configuration file, ``assumed``;
PERF.md section 6, PR 26).  With every matrix at N(0, 0.02) the same
comparison read 0.10-0.25: (1) *flips*: the 64 router weights are then
nearly equal, the 8th and 9th lie within the perturbation of the
router's input for 7% of (token, layer) pairs, the system takes another
8th expert than the reference, legitimately, and each flip moves the
row by a few percent of its scale; (2) the residual stream, starting
from an embedding of 0.02, is made of branch outputs alone, so a
perturbation grows from layer to layer.  A decided router (0.12: the
8th weight is 0.005, a flip costs nothing) and a unit embedding (each
branch adds 0.2-0.45 to a stream of 1) remove both.

A wrong page, a position off by one, a missing rotation, the other
rotation pairing, QK-norm per head, seven experts and (where the 8
weights do not sum to ~1) renormalised weights move the logits by
0.1-1.6 on the CPU at toy widths (tests/test_olmoe_decode.py): all
above this bar.  The mathematics is proven at 2e-5 on the CPU
(comparison (A), same file), the expert arithmetic alone at 9e-3 on
the chip with the routing given (comparison (C),
chipbench/tests/test_olmoe_chip.py).
"""
import jax
import jax.numpy as jnp

LOGITS_TOL = 2.8e-2
TOP_K = 8
THETA = 10000.0
EPS = 1e-5


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * w


def _rope(u, pos):
    """u [T, H, Dh] rotated by pos [T]; pairing (j, j + Dh/2)."""
    dh = u.shape[-1]
    inv_freq = THETA ** (-jnp.arange(dh // 2, dtype=jnp.float32)
                         * 2.0 / dh)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-u[..., dh // 2:], u[..., :dh // 2]], axis=-1)
    return u * jnp.cos(ang) + rot * jnp.sin(ang)


def branches(p, tokens, n_layers, n_heads, top_k=TOP_K):
    """The forward pass with what it went through: (logits [T, V],
    per layer the RMS of the attention branch's and of the expert
    branch's contribution to the residual, per layer the router's
    probabilities [T, E])."""
    f32 = lambda name: p[name].astype(jnp.float32)
    with jax.default_matmul_precision('highest'):
        t = tokens.shape[0]
        x = f32('olmoe_embed')[tokens]
        d = x.shape[-1]
        dh = d // n_heads
        pos = jnp.arange(t)
        causal = jnp.tril(jnp.ones((t, t), bool))
        rms_of = lambda a: jnp.sqrt(jnp.mean(a * a))
        attn_rms, moe_rms, probs = [], [], []
        for i in range(n_layers):
            q = 'olmoe_l%d_' % i
            h = _rms(x, f32(q + 'in_norm_w'))
            qh = _rms(h @ f32(q + 'q_w'), f32(q + 'q_norm_w'))
            kh = _rms(h @ f32(q + 'k_w'), f32(q + 'k_norm_w'))
            vh = h @ f32(q + 'v_w')
            qh = _rope(qh.reshape(t, n_heads, dh), pos)
            kh = _rope(kh.reshape(t, n_heads, dh), pos)
            vh = vh.reshape(t, n_heads, dh)
            s = jnp.einsum('qhd,khd->hqk', qh, kh) / jnp.sqrt(float(dh))
            s = jnp.where(causal[None], s, -jnp.inf)
            a = jnp.einsum('hqk,khd->qhd', jax.nn.softmax(s, axis=-1),
                           vh).reshape(t, d) @ f32(q + 'o_w')
            x = x + a
            h = _rms(x, f32(q + 'post_norm_w'))
            r = jax.nn.softmax(h @ f32(q + 'router_w'), axis=-1)
            _, idx = jax.lax.top_k(r, top_k)
            chosen = jnp.zeros(r.shape, bool).at[
                jnp.arange(t)[:, None], idx].set(True)
            g = jnp.einsum('td,edf->etf', h, f32(q + 'gate_w'))
            u = jnp.einsum('td,edf->etf', h, f32(q + 'up_w'))
            o = jnp.einsum('etf,efd->etd', jax.nn.silu(g) * u,
                           f32(q + 'down_w'))
            y = jnp.einsum('etd,te->td', o, jnp.where(chosen, r, 0.0))
            x = x + y
            attn_rms.append(rms_of(a))
            moe_rms.append(rms_of(y))
            probs.append(r)
        x = _rms(x, f32('olmoe_norm_f_w'))
        return (x @ f32('olmoe_head_w'), jnp.stack(attn_rms),
                jnp.stack(moe_rms), jnp.stack(probs))


def logits(p, tokens, n_layers, n_heads, top_k=TOP_K):
    """[T, V] next-token scores for one sequence of int tokens [T]."""
    return branches(p, tokens, n_layers, n_heads, top_k)[0]

"""Expert parallelism over an 'ep' mesh axis: the collective routine
only.

This module is the ``ep`` all-to-all dispatch, with a top-1 argmax gate
and a fixed capacity that DROPS over-capacity tokens; no ``Program`` and
no engine reaches it.  The mixture-of-experts layer the registry and the
decode engine run is ``ops/moe.py`` ``moe_ffn`` (float32 softmax router,
top-k, no capacity, no dropped token, on one chip); sharding that op's
experts over ``ep`` with this module's all-to-all, and giving this gate
the top-k it lacks (``ops.moe.moe_route``), is ROADMAP R1's second half.

The reference predates mixture-of-experts, but the mesh design
(SURVEY §6.5) names 'ep' among the first-class axes: each mesh member
owns one (or E/ep) experts, tokens route to their expert with an
`all_to_all` over ICI, the expert FFN runs local, and a second
`all_to_all` routes results home — the standard TPU MoE dispatch
(GShard/Switch layout), expressed with the same collective backend as
dp/tp/sp.

Static shapes: every member sends exactly `capacity` tokens to every
expert (over-capacity tokens drop, under-capacity slots pad) — the
TPU-friendly fixed-capacity formulation.
"""
import jax
import jax.numpy as jnp
from jax import lax

__all__ = ['dispatch', 'combine', 'expert_ffn', 'moe_layer']


def _capacity_gather(x, gates, n_expert, capacity):
    """Select up to `capacity` token indices per expert (top-gate order
    not needed for correctness here: first-come order, parity with
    capacity-dropping MoE).  Returns idx [E, C] and valid [E, C]."""
    t = x.shape[0]
    # rank of each token within its expert's arrivals
    expert = jnp.argmax(gates, axis=-1)  # [T]
    onehot = jax.nn.one_hot(expert, n_expert, dtype=jnp.int32)  # [T, E]
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot  # 1-based
    pos = jnp.sum(pos_in_expert, axis=-1) - 1  # [T], 0-based
    keep = pos < capacity
    # scatter token ids into [E, C] slots
    slot = jnp.where(keep, expert * capacity + pos, n_expert * capacity)
    idx = jnp.full((n_expert * capacity + 1,), t, jnp.int32)
    idx = idx.at[slot].set(jnp.arange(t, dtype=jnp.int32))
    idx = idx[:-1].reshape(n_expert, capacity)
    valid = idx < t
    idx = jnp.minimum(idx, t - 1)
    return idx, valid, expert, keep, pos


def dispatch(x, gates, axis_name, capacity):
    """Route tokens to their expert's mesh member.

    x [T, D] local tokens, gates [T, E] routing scores with E == mesh
    size of `axis_name`.  Returns (expert_in [E*C_local... actually
    [E, C, D] received tokens for THIS member's expert], routing state
    for combine()).
    """
    n_expert = lax.psum(1, axis_name)
    idx, valid, expert, keep, pos = _capacity_gather(x, gates, n_expert,
                                                     capacity)
    send = x[idx] * valid[..., None].astype(x.dtype)  # [E, C, D]
    # all_to_all: member m sends send[e] to member e; receives [E, C, D]
    # where axis 0 now indexes the SOURCE member
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)
    return recv, (idx, valid, expert, keep, pos)


def combine(y, state, axis_name):
    """Inverse of dispatch: return expert outputs to their home tokens.
    y [E_src, C, D] processed tokens (source-indexed); returns [T, D]
    with dropped tokens zero."""
    idx, valid, expert, keep, pos = state
    back = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)  # [E, C, D] expert-indexed again
    d = y.shape[-1]
    flat = back.reshape(-1, d)  # [E*C, D]
    slot = expert * idx.shape[1] + pos  # token's slot if kept
    gathered = flat[jnp.minimum(slot, flat.shape[0] - 1)]
    return jnp.where(keep[:, None], gathered, 0.0).astype(y.dtype)


def expert_ffn(x, w1, b1, w2, b2):
    """The local expert: position-wise FFN on [*, D] tokens."""
    h = jax.nn.relu(jnp.einsum('...d,dh->...h', x, w1) + b1)
    return jnp.einsum('...h,hd->...d', h, w2) + b2


def moe_layer(x, gates, w1, b1, w2, b2, axis_name, capacity):
    """Full fixed-capacity MoE layer inside shard_map over `axis_name`:
    dispatch -> local expert FFN -> combine.  Each member holds ONE
    expert's weights (w1 [D, H] local)."""
    recv, state = dispatch(x, gates, axis_name, capacity)
    y = expert_ffn(recv, w1, b1, w2, b2)
    return combine(y, state, axis_name)

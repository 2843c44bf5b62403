"""The bytes a routed-expert decoder's decode step needs, from its
shapes and from how many experts the step's tokens touched.  Kept with
the benchmark (beside flops.py, which counts a dense decoder) so that no
PR that claims a gain can change them."""


def _nbytes(a):
    return int(a.size) * a.dtype.itemsize


def experts_step_bytes(params, layers, touched, rows, top_k):
    """Least bytes the expert layers of one decode step move: the three
    matrices of every touched expert once (``touched`` experts a layer,
    the mean over layers), the router's weights, and the step's
    activations in the expert layers — per layer the ``rows`` normed
    inputs (float32) read by the router and the experts, the gate/up
    results written and read once (float32, ``rows * top_k`` of them),
    and the ``rows`` outputs (float32)."""
    gate = params['olmoe_l0_gate_w']
    e, d, f = (int(x) for x in gate.shape)
    one_expert = 3 * d * f * gate.dtype.itemsize
    router = _nbytes(params['olmoe_l0_router_w'])
    acts = 4 * (2 * rows * d + 2 * 2 * rows * top_k * f)
    return layers * (touched * one_expert + router + acts)


def moe_decode_step_bytes(params, layers, touched, slots, top_k,
                          cached_tokens, kv_bytes_per_token):
    """Bytes the whole decode step must move: the expert layers as
    above, every other weight once except the embedding table (one row
    a slot), and the cached keys and values of the running requests
    (``cached_tokens`` positions in all)."""
    experts = sum(_nbytes(v) for n, v in params.items()
                  if n.endswith(('gate_w', 'up_w', 'down_w', 'router_w')))
    embed = params['olmoe_embed']
    rest = sum(_nbytes(v) for v in params.values()) - experts \
        - _nbytes(embed) + slots * int(embed.shape[1]) * embed.dtype.itemsize
    return experts_step_bytes(params, layers, touched, slots, top_k) \
        + rest + cached_tokens * kv_bytes_per_token

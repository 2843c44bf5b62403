"""The operations and bytes that a latent-attention decoder with a share
of routed experts needs, from its shapes and from what a step read and
routed.  Kept with the benchmark (beside flops.py and flops_moe.py) so
that no PR that claims a gain can change them.  FLOPs = 2 x
multiply-accumulates.

A position's cached row is ``kv_lora_rank + qk_rope_head_dim`` values
(512 + 64 at the published widths); the lanes of zeros the device's
tiling adds to it are not counted as needed: a pool held without them
would read fewer bytes, so they lower the share."""


def latent_row(config):
    """Values a position caches a layer: the latent and the rotary key."""
    return config['kv_lora_rank'] + config['qk_rope_head_dim']


def mla_decode_flops(config, live_positions, layers):
    """FLOPs of the absorbed decode attention over ``live_positions``
    cached positions (summed over the running slots), in every layer:
    per position and head one score over the whole row and one
    probability-weighted sum of the latent: 2 x H x (row + rank)
    (278,528 at the published widths)."""
    per_position = 2 * config['num_attention_heads'] \
        * (latent_row(config) + config['kv_lora_rank'])
    return layers * live_positions * per_position


def mla_decode_bytes(config, live_positions, layers, itemsize):
    """Bytes of the live latent rows, read once a layer."""
    return layers * live_positions * latent_row(config) * itemsize


def _nbytes(a):
    return int(a.size) * a.dtype.itemsize


def held_experts_step_bytes(params, layer, routing_layers, touched, rows,
                            held_assignments):
    """Least bytes the expert layers of one step move: per routing layer
    the three matrices of every held expert that got a token
    (``touched`` of them, the mean over layers), the shared expert's
    gate and up (its down projection runs in a fusion the trace names
    like the attention output's and the dense layer's, so neither its
    bytes nor its time can be counted), the router and its bias, and
    the activations: the ``rows``
    normed inputs (float32) read by the router, the held experts and the
    shared expert, the gate/up results of the shared expert (``rows``)
    and of the held assignments (``held_assignments`` a layer) written
    and read once (float32), and the ``rows`` outputs (float32).
    ``layer`` names one expert layer (its weights' shapes stand for
    all)."""
    n = 'dots_l%d_' % layer
    gate = params[n + 'gate_w']
    _e, d, f = (int(x) for x in gate.shape)
    one_expert = 3 * d * f * gate.dtype.itemsize
    shared = sum(_nbytes(params[n + 'shared_%s_w' % s])
                 for s in ('gate', 'up'))
    fs = int(params[n + 'shared_gate_w'].shape[1])
    router = _nbytes(params[n + 'router_w']) \
        + _nbytes(params[n + 'router_bias'])
    acts = 4 * (2 * rows * d + 2 * 2 * (rows * fs + held_assignments * f))
    return routing_layers * (touched * one_expert + shared + router + acts)


def held_experts_step_flops(params, layer, routing_layers, rows,
                            held_assignments):
    """Least FLOPs of the same operations: per routing layer the router
    over ``rows``, the three products of an expert for each of the
    ``held_assignments`` (a layer), and the shared expert's gate and up
    for every row (its down projection is not counted, as above)."""
    n = 'dots_l%d_' % layer
    _e, d, f = (int(x) for x in params[n + 'gate_w'].shape)
    fs = int(params[n + 'shared_gate_w'].shape[1])
    width = int(params[n + 'router_w'].shape[1])
    return routing_layers * 2 * (rows * d * width
                                 + held_assignments * 3 * d * f
                                 + rows * 2 * d * fs)

"""The bytes and operations of a decode step of a decoder that runs its
stack of layers several times a token over ONE set of weights, each
recurrence with cache slots of its own.  Kept with the benchmark (beside
flops.py, flops_moe.py, flops_mla.py and flops_gqa.py) so that no PR
that claims a gain can change them; reckoned from the configuration's
shapes and the spans' counts, whatever implements the step.  FLOPs = 2 x
multiply-accumulates.

``loop_positions`` is the spans' ``kv_loop_live_positions``: the running
slots' cached positions and the new one, summed, times the recurrences:
the positions the decode rows' attention reads a layer of weights."""


def layer_weight_bytes(config, itemsize):
    """One layer's weights: q, k, v, o, the SwiGLU's three matrices at
    ``itemsize`` and four float32 norm weights."""
    d, dh = config['hidden_size'], config['head_dim']
    q, kv = config['num_attention_heads'] * dh, \
        config['num_key_value_heads'] * dh
    matrices = 2 * d * q + 2 * d * kv + 3 * d * config['intermediate_size']
    return matrices * itemsize + 4 * d * 4


def head_bytes(config, itemsize):
    return config['hidden_size'] * config['vocab_size'] * itemsize


def kv_slot_bytes(config, itemsize):
    """Bytes of a position's K and V in one cache slot."""
    return 2 * config['num_key_value_heads'] * config['head_dim'] * itemsize


def loop_decode_bytes(config, loop_positions, kv_itemsize):
    """Least bytes the decode rows' attention reads: every live position
    of every recurrence's slot, K and V, once a layer."""
    return loop_positions * config['num_hidden_layers'] \
        * kv_slot_bytes(config, kv_itemsize)


def loop_decode_flops(config, loop_positions):
    """FLOPs of the same: per position and head one score and one
    probability-weighted sum over ``head_dim``."""
    return 4 * config['head_dim'] * config['num_attention_heads'] \
        * config['num_hidden_layers'] * loop_positions


def loop_step_bytes(config, loop_positions, itemsize, kv_itemsize):
    """Least bytes one decode step reads: the layers' weights once a
    RECURRENCE (nothing on the chip holds 1.2 GB between two), the head
    once, and the live K/V of every slot.  The embedding's one row a
    slot and the activations are left out: the count is a floor."""
    return config['total_ut_steps'] * config['num_hidden_layers'] \
        * layer_weight_bytes(config, itemsize) \
        + head_bytes(config, itemsize) \
        + loop_decode_bytes(config, loop_positions, kv_itemsize)

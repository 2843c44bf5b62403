"""The bytes and operations of decode attention over K/V heads that
fewer than the query heads share, where some layers read every cached
position and others a window of the newest.  Kept with the benchmark
(beside flops.py, flops_moe.py and flops_mla.py) so that no PR that
claims a gain can change them.  FLOPs = 2 x multiply-accumulates.

A position caches one K and one V row of ``num_key_value_heads x
head_dim`` values a layer, whatever the layer's query heads."""


def kinds(config):
    """{kind: (layers of the kind among those run, its query heads)}."""
    n = config['num_hidden_layers']
    out = {}
    for kind, heads in zip(config['layer_types'][:n],
                           config['num_attention_heads_per_layer'][:n]):
        layers, _h = out.get(kind, (0, heads))
        out[kind] = (layers + 1, heads)
    return out


def kv_row_bytes(config, itemsize):
    """Bytes of a position's K and V in one layer."""
    return 2 * config['num_key_value_heads'] * config['head_dim'] * itemsize


def gqa_decode_bytes(config, full_positions, window_positions, itemsize):
    """Least bytes the decode rows' attention reads: the live positions
    of the layers that keep everything (``full_positions``: each running
    slot's context and its new position, summed) and of the layers that
    read a window (``window_positions``: the same, capped at the
    window), K and V, once a layer of the kind."""
    k = kinds(config)
    row = kv_row_bytes(config, itemsize)
    return row * (k['full_attention'][0] * full_positions
                  + k['sliding_attention'][0] * window_positions)


def gqa_decode_flops(config, full_positions, window_positions):
    """FLOPs of the same: per position and query head one score and one
    probability-weighted sum over ``head_dim``."""
    k, dh = kinds(config), config['head_dim']
    return 4 * dh * (
        k['full_attention'][0] * k['full_attention'][1] * full_positions
        + k['sliding_attention'][0] * k['sliding_attention'][1]
        * window_positions)

"""The bytes a selective state-space (Mamba-1) layer's recurrence has to
move, for the shares of a roofline of its two forms: the SCAN of a
prompt chunk's tokens from a stream's state, and the one-token UPDATE of
the decode rows' states.  Kept with the benchmark (beside flops.py,
flops_moe.py, flops_mla.py, flops_gqa.py and flops_loop.py) so that no PR
that claims a gain can change them; reckoned from the configuration's
shapes and the spans' counts, whatever implements the recurrence.

What the EQUATIONS need (``s = exp(dt A) s + (dt v) B``, ``y = s . C + D
v``, the configuration's ``state_dtype`` for the state and float32, the
activations' dtype, for the rest): the state [d_state, d_inner] in and
out ONCE a sequence (a chunk, or a row's one token), and a token's
``v``, ``dt`` and ``y`` [d_inner] and ``B``, ``C`` [d_state] once.
``A`` and ``D`` are weights, read once a layer a call whatever the
tokens, and are left out: the count is a floor.  The recurrence has no
matrix product and ~6 vector operations a token a channel a state lane,
so the limit that counts is this one or the vector unit's; the share is
of the BYTES' time at the HBM peak and cannot pass 100%.

``scan_tokens`` is the spans' ``ssm_scan_tokens`` (a chunk's valid
tokens), ``live_slots`` their ``ssm_live_slots`` (the running decode
rows); both times the state layers."""
import numpy as np


def state_layers(config):
    n, period, offset = (config['num_hidden_layers'],
                         config['attn_layer_period'],
                         config['attn_layer_offset'])
    return sum(1 for i in range(n) if i % period != offset)


def d_inner(config):
    return config['mamba_expand'] * config['hidden_size']


def state_bytes(config):
    """A stream's SSM state in one layer."""
    return config['mamba_d_state'] * d_inner(config) \
        * np.dtype(config['state_dtype']).itemsize


def token_bytes(config):
    """What one token moves in one layer: v, dt, y and B, C, float32."""
    return 4 * (3 * d_inner(config) + 2 * config['mamba_d_state'])


def scan_bytes(config, chunks, scan_tokens):
    """Least bytes the scans of ``chunks`` chunks holding ``scan_tokens``
    valid tokens in all move, every state layer's."""
    return state_layers(config) * (2 * chunks * state_bytes(config)
                                   + scan_tokens * token_bytes(config))


def step_bytes(config, live_slots):
    """Least bytes the one-token updates of ``live_slots`` rows move."""
    return state_layers(config) * live_slots \
        * (2 * state_bytes(config) + token_bytes(config))


def stream_state_bytes(config):
    """What a stream holds in the state layers: the SSM state and the
    convolution's last ``d_conv - 1`` inputs, every state layer's."""
    itemsize = np.dtype(config['state_dtype']).itemsize
    return state_layers(config) * d_inner(config) * itemsize \
        * (config['mamba_d_state'] + config['mamba_d_conv'] - 1)


def kv_position_bytes(config):
    """What a cached position holds in the attention layers."""
    n = config['num_hidden_layers'] - state_layers(config)
    dh = config['hidden_size'] // config['num_attention_heads']
    return n * 2 * config['num_key_value_heads'] * dh \
        * np.dtype(config['kv_dtype']).itemsize

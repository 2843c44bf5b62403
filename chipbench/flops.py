"""The operations and bytes an algorithm needs, from its shapes.  Kept
with the benchmark so that no PR that claims a gain can change them.
FLOPs = 2 x multiply-accumulates."""


def decode_step_bytes(params, slots, cached_tokens, kv_bytes_per_token):
    """Bytes one decode step must move: every weight once, except the
    embedding table (one row a slot), plus the cached keys and values of
    the running requests (``cached_tokens`` positions in all)."""
    total = sum(int(v.size) * v.dtype.itemsize for v in params.values())
    embed = params['tr_embed']
    row = int(embed.shape[1]) * embed.dtype.itemsize
    return total - int(embed.size) * embed.dtype.itemsize + slots * row \
        + cached_tokens * kv_bytes_per_token


def prefill_flops(config, tokens):
    """FLOPs of one full-context forward over ``tokens`` positions of a
    pre-LN decoder: the four attention projections, the two FFN
    matrices, causal attention (half of the T x T scores and of their
    product with V), and the head for the last position only."""
    d, f, layers = config['hidden_size'], config['ffn_dim'], \
        config['num_hidden_layers']
    per_token = 2 * (4 * d * d + 2 * d * f)
    attention = 2 * 2 * tokens * tokens * d / 2.0
    return layers * (per_token * tokens + attention) \
        + 2 * d * config['vocab_size']


def conv_flops(batch, out_hw, k, c_in, c_out):
    """Forward FLOPs of one convolution: 2 x outputs x kernel volume."""
    return 2.0 * batch * out_hw * out_hw * k * k * c_in * c_out


def resnet_convs(config, batch):
    """Every convolution of a bottleneck ResNet as
    (out_hw, kernel, c_in, c_out, in_hw), stem first."""
    hw = config['image_size'] // 2
    convs = [(hw, 7, config['image_channels'], config['stem_width'],
              config['image_size'])]
    hw //= 2                                    # the max pool
    c_in, exp = config['stem_width'], config['bottleneck_expansion']
    for stage, (w, blocks) in enumerate(zip(config['stage_widths'],
                                            config['stage_blocks'])):
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            out = hw // stride
            if c_in != w * exp or stride != 1:
                convs.append((out, 1, c_in, w * exp, hw))
            convs.append((out, 1, c_in, w, hw))
            convs.append((out, 3, w, w, out))
            convs.append((out, 1, w, w * exp, out))
            c_in, hw = w * exp, out
    return convs


def resnet_conv_roofline_s(config, batch, peaks, act_bytes=2, w_bytes=4):
    """Least seconds the convolutions of one training step can take:
    for each convolution and each of its three passes (forward, input
    gradient, weight gradient; the stem has no input gradient) the larger
    of FLOPs / peak and bytes / bandwidth, where a pass reads its two
    operands and writes its result once."""
    total = 0.0
    for i, (out_hw, k, c_in, c_out, in_hw) in enumerate(
            resnet_convs(config, batch)):
        fl = conv_flops(batch, out_hw, k, c_in, c_out)
        x = batch * in_hw * in_hw * c_in * act_bytes
        y = batch * out_hw * out_hw * c_out * act_bytes
        w = k * k * c_in * c_out * w_bytes
        passes = 2 if i == 0 else 3
        total += passes * max(fl / peaks['bf16_flops_per_s'],
                              (x + y + w) / peaks['hbm_bytes_per_s'])
    return total

"""Plain reference for a ``resnet_train`` configuration: ResNet v1 with
bottleneck blocks (He et al., arXiv:1512.03385, Table 1 and Figure 5
right), forward pass and mean cross-entropy in float32 ``jax.numpy`` at
``highest`` matmul precision.  No AMP, no fusion, no program code.

Departures from the paper, each because the system under test has it:
the stride of a down-sampling block sits on its first 1x1 convolution
(the reference's benchmark/paddle/image/resnet.py does so); every
convolution carries a bias (zero at the start); batch norm uses the
batch's own statistics, biased variance, eps 1e-5.

It reads the weights by the names the program gives them, in the order
the architecture creates them: ``conv2d_<i>.w_0`` (OIHW) and ``.b_0``,
``batch_norm_<i>.w_0`` / ``.b_0``, ``fc_0.w_0`` / ``.b_0``; within a
block the projection shortcut comes first.

TOLERANCE.  The system runs its convolutions on bf16 operands (8
mantissa bits) and sums in f32; over 53 layers with batch norm
re-scaling each, the logits differ from f32 by a few parts in a hundred
and the mean loss over a batch by far less, since the errors of single
images average out.  Measured: 2.4e-4 to 1.8e-3 on the chip at batch 256, by
seed (my chip runs, PR 23); 5e-6 in f32 on the CPU at toy width (and 1e-2 there in
bf16, where a batch of 8 at 1x1 resolution averages nothing out, which
is why the rehearsal runs f32).  LOSS_RTOL 1e-2 is five times the
chip's widest reading; a wrong stride, a missing shortcut or running statistics
in place of batch statistics moves the loss by 10% or more, and bf16
weights as well as activations by about 1%.
"""
import jax
import jax.numpy as jnp

LOSS_RTOL = 1e-2
EPS = 1e-5


def _conv_bn(x, w, i, stride, pad, relu):
    k = jnp.transpose(w['conv2d_%d.w_0' % i], (2, 3, 1, 0))  # OIHW->HWIO
    y = jax.lax.conv_general_dilated(
        x, k, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
    y = y + w['conv2d_%d.b_0' % i]
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    y = (y - mean) / jnp.sqrt(var + EPS) * w['batch_norm_%d.w_0' % i] \
        + w['batch_norm_%d.b_0' % i]
    return jnp.maximum(y, 0.0) if relu else y


def logits(w, img, config):
    """[B, classes] pre-softmax scores for NHWC float32 images."""
    x = _conv_bn(img, w, 0, 2, 3, True)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])
    i, ch_in = 1, config['stem_width']
    exp = config['bottleneck_expansion']
    for stage, (width, blocks) in enumerate(zip(config['stage_widths'],
                                                config['stage_blocks'])):
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            short = x
            if ch_in != width * exp or stride != 1:
                short = _conv_bn(x, w, i, stride, 0, False)
                i += 1
            y = _conv_bn(x, w, i, stride, 0, True)
            y = _conv_bn(y, w, i + 1, 1, 1, True)
            y = _conv_bn(y, w, i + 2, 1, 0, False)
            i += 3
            x = jnp.maximum(short + y, 0.0)
            ch_in = width * exp
    x = jnp.mean(x, axis=(1, 2))
    return x @ w['fc_0.w_0'] + w['fc_0.b_0']


def loss(w, img, label, config):
    """Mean cross-entropy of softmax(logits) against int labels [B, 1]."""
    with jax.default_matmul_precision('highest'):
        z = logits({k: jnp.asarray(v, jnp.float32) for k, v in w.items()},
                   jnp.asarray(img, jnp.float32), config)
        logp = jax.nn.log_softmax(z, axis=-1)
        picked = jnp.take_along_axis(logp, label.reshape(-1, 1), axis=1)
        return -jnp.mean(picked)


def loss_and_fc_grads(w, img, label, config):
    """(loss, d loss / d fc weights, d loss / d fc bias)."""
    head = {k: w[k] for k in ('fc_0.w_0', 'fc_0.b_0')}

    def f(h):
        return loss(dict(w, **h), img, label, config)
    value, g = jax.value_and_grad(f)(head)
    return value, g['fc_0.w_0'], g['fc_0.b_0']

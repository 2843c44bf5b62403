"""Recurrent ops.

Reference parity: paddle/operators/{lstm_op,lstm_unit_op,gru_op,
gru_unit_op}.* — the reference reorders sequences by length and runs
batched GEMMs per time step over the packed LoD layout.  TPU-native design:
padded [B, T, D] + lengths, one lax.scan over time whose body is a single
MXU matmul; finished rows freeze their state via masks (no reordering, no
dynamic shapes).
"""
import os

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import first

_ACC = dict(preferred_element_type=jnp.float32)


def _gate_act(name):
    return {
        'sigmoid': jax.nn.sigmoid,
        'tanh': jnp.tanh,
        'relu': jax.nn.relu,
        'identity': lambda x: x,
    }[name]


def _maybe_reverse(xf, lengths, is_reverse):
    """Reverse each row's valid prefix (padded tail stays in place).
    Returns (x, rev_idx) with rev_idx None when not reversing — the same
    gather applied to the outputs undoes it."""
    if not is_reverse:
        return xf, None
    b, t = xf.shape[0], xf.shape[1]
    ln = (jnp.full((b,), t, jnp.int32) if lengths is None
          else lengths.astype(jnp.int32).reshape(-1))
    idx = jnp.arange(t)
    rev_idx = jnp.where(idx[None, :] < ln[:, None],
                        ln[:, None] - 1 - idx[None, :], idx[None, :])
    return jnp.take_along_axis(xf, rev_idx[..., None], axis=1), rev_idx


def _unreverse_and_mask(seqs, rev_idx, lengths, t):
    """Shared RNN output epilogue: undo _maybe_reverse's gather and zero
    positions >= length.  seqs: [B, T, H] arrays; returns the list."""
    mask = None
    if lengths is not None:
        mask = (jnp.arange(t)[None, :] <
                lengths.astype(jnp.int32).reshape(-1)[:, None])[..., None]
    out = []
    for v in seqs:
        if rev_idx is not None:
            v = jnp.take_along_axis(v, rev_idx[..., None], axis=1)
        if mask is not None:
            v = jnp.where(mask, v, 0.0)
        out.append(v)
    return out


def _device_vmem_bytes():
    """Scoped VMEM a Pallas kernel may claim by default on the attached
    TPU, from device_kind: 16 MiB for the v2–v5 families, 32 MiB
    starting with v6 (Trillium).  On a TPU backend an unparseable
    device_kind is an error — the tile chooser must not size a kernel
    for a part it cannot identify.  Off-TPU the kernels only run
    interpreted, where the budget merely picks a tile: the v5e figure
    serves."""
    if jax.default_backend() != 'tpu':
        return 16 * 1024 * 1024
    import re
    kind = jax.devices()[0].device_kind
    m = re.search(r'v(\d+)', kind.lower())
    if not m:
        raise RuntimeError(
            "cannot size the RNN kernels' VMEM budget: unrecognised TPU "
            "device_kind %r (set PADDLE_TPU_RNN_VMEM_BUDGET_MB)" % kind)
    return (32 if int(m.group(1)) >= 6 else 16) * 1024 * 1024


def _rnn_vmem_budget():
    """VMEM bytes the BPTT kernel may claim: 75% of the device's VMEM
    (the rest is margin for Mosaic's own temporaries), derived from the
    attached device generation rather than hardcoded.
    PADDLE_TPU_RNN_VMEM_BUDGET_MB overrides for parts where the margin
    is wrong in either direction."""
    mb = os.environ.get('PADDLE_TPU_RNN_VMEM_BUDGET_MB')
    if mb:
        try:
            return int(float(mb) * 1024 * 1024)
        except ValueError:
            pass
    return int(_device_vmem_bytes() * 0.75)


def _pallas_rnn_fits_vmem(batch, hidden, gate_width):
    """The BPTT kernel keeps the weight block AND an equally-sized f32
    dW accumulator resident in VMEM for the whole grid, plus per-step
    [bt, gate_width] tiles.  The batch dimension TILES (grid =
    (batch_tiles, time)), so a config fits whenever ANY divisor of the
    batch keeps the working set under budget — only a hidden size whose
    resident weight+accumulator alone exceed VMEM falls back to the
    lax.scan path."""
    from .pallas.lstm_cell import pick_batch_tile
    return pick_batch_tile(batch, hidden, gate_width,
                           _rnn_vmem_budget()) is not None


@register_op('lstm')
def _lstm(ctx, ins, attrs):
    """Dynamic LSTM over a padded batch (operators/lstm_op.cc).  Input is
    the pre-projected gates [B, T, 4H] (the reference's `dynamic_lstm`
    layer computes x@W outside the op); Weight [H, 4H] is the recurrent
    projection; gate order i, f, c, o (reference order: i c f o differs —
    we follow the fluid docstring order input/forget/cell/output applied
    consistently with the layer)."""
    x = first(ins, 'Input')  # [B, T, 4H]
    w = first(ins, 'Weight').astype(jnp.float32)  # [H, 4H]
    bias = first(ins, 'Bias')  # [1, 4H] or [1, 7H] with peepholes
    lengths = first(ins, 'XLen')
    h0 = first(ins, 'H0')
    c0 = first(ins, 'C0')
    b, t, fourh = x.shape
    h = fourh // 4
    use_peepholes = attrs.get('use_peepholes', True) and bias is not None \
        and bias.shape[-1] == 7 * h

    xf = x.astype(jnp.float32)
    if bias is not None:
        xf = xf + bias.astype(jnp.float32)[..., :4 * h].reshape(1, 1, -1)

    backend = getattr(ctx, 'backend', None) or jax.default_backend()
    if attrs.get('use_pallas') and h0 is None and c0 is None and \
            attrs.get('gate_activation', 'sigmoid') == 'sigmoid' and \
            attrs.get('cell_activation', 'tanh') == 'tanh' and \
            attrs.get('candidate_activation', 'tanh') == 'tanh' and \
            _pallas_rnn_fits_vmem(b, h, fourh) and \
            (backend == 'tpu' or attrs.get('pallas_interpret', False)):
        # fused Pallas time loop (ops/pallas/lstm_cell.py): carry lives
        # in VMEM across grid steps; backward is the reverse-time BPTT
        # kernel.  TPU-only (interpret mode would unroll all T steps);
        # falls back to the lax.scan path for custom-activation or
        # chained-h0/c0 configs (peepholes ride the kernel via
        # pw = Bias[4H:7H]).  Ragged batches run the kernel UNMASKED:
        # lengths are prefixes, so padded steps can't reach any valid
        # output, and the zero-mask below (whose vjp zeroes the padded
        # cotangents) makes fwd and bwd exactly match the masked scan.
        from .pallas.lstm_cell import lstm_scan
        xin, rev_idx = _maybe_reverse(xf, lengths,
                                      attrs.get('is_reverse', False))
        pw = (bias.astype(jnp.float32).reshape(-1)[4 * h:7 * h]
              .reshape(3, h) if use_peepholes else None)
        # kernel gate order (i, f, cand, o) == this op's (i, f, c, o)
        hs, cs = lstm_scan(jnp.swapaxes(xin, 0, 1), w, pw,
                           interpret=backend != 'tpu')
        hs, cs = _unreverse_and_mask(
            [jnp.swapaxes(hs, 0, 1), jnp.swapaxes(cs, 0, 1)],
            rev_idx, lengths, t)
        return {'Hidden': [hs.astype(x.dtype)],
                'Cell': [cs.astype(x.dtype)]}
    if lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)
    lengths = lengths.astype(jnp.int32).reshape(-1)
    gate_act = _gate_act(attrs.get('gate_activation', 'sigmoid'))
    cell_act = _gate_act(attrs.get('cell_activation', 'tanh'))
    cand_act = _gate_act(attrs.get('candidate_activation', 'tanh'))
    is_reverse = attrs.get('is_reverse', False)

    if use_peepholes:
        bf = bias.astype(jnp.float32).reshape(-1)
        w_ic, w_fc, w_oc = (bf[4 * h:5 * h], bf[5 * h:6 * h],
                            bf[6 * h:7 * h])
    if is_reverse:
        xf, rev_idx = _maybe_reverse(xf, lengths, True)

    h_prev = (h0.astype(jnp.float32) if h0 is not None
              else jnp.zeros((b, h), jnp.float32))
    c_prev = (c0.astype(jnp.float32) if c0 is not None
              else jnp.zeros((b, h), jnp.float32))

    def step(carry, inputs):
        h_p, c_p = carry
        g_t, t_idx = inputs  # [B, 4H]
        g = g_t + jnp.matmul(h_p, w, **_ACC)
        gi, gf, gc, go = jnp.split(g, 4, axis=1)
        if use_peepholes:
            gi = gi + c_p * w_ic
            gf = gf + c_p * w_fc
        i = gate_act(gi)
        f = gate_act(gf)
        c = f * c_p + i * cand_act(gc)
        if use_peepholes:
            go = go + c * w_oc
        o = gate_act(go)
        h_t = o * cell_act(c)
        alive = (t_idx < lengths)[:, None]
        h_t = jnp.where(alive, h_t, h_p)
        c = jnp.where(alive, c, c_p)
        return (h_t, c), (h_t, c)

    (_, _), (hs, cs) = jax.lax.scan(
        step, (h_prev, c_prev),
        (jnp.swapaxes(xf, 0, 1), jnp.arange(t)))
    hs, cs = _unreverse_and_mask(
        [jnp.swapaxes(hs, 0, 1), jnp.swapaxes(cs, 0, 1)],
        rev_idx if is_reverse else None, lengths, t)
    return {'Hidden': [hs.astype(x.dtype)], 'Cell': [cs.astype(x.dtype)]}


@register_op('lstm_unit')
def _lstm_unit(ctx, ins, attrs):
    """Single LSTM cell step (operators/lstm_unit_op): X [B, 4H] gates,
    C_prev [B, H] → (C, H).  Gate order i, f, o, j (parity with the
    reference kernel)."""
    x = first(ins, 'X').astype(jnp.float32)
    c_prev = first(ins, 'C_prev').astype(jnp.float32)
    forget_bias = attrs.get('forget_bias', 0.0)
    i, f, o, j = jnp.split(x, 4, axis=1)
    c = jax.nn.sigmoid(f + forget_bias) * c_prev + \
        jax.nn.sigmoid(i) * jnp.tanh(j)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    dt = first(ins, 'X').dtype
    return {'C': [c.astype(dt)], 'H': [h.astype(dt)]}


@register_op('gru')
def _gru(ctx, ins, attrs):
    """Dynamic GRU over a padded batch (operators/gru_op.cc).  Input [B, T,
    3H] pre-projected; Weight packs [H, 2H] (update/reset) + [H, H]
    (candidate)."""
    x = first(ins, 'Input')
    w = first(ins, 'Weight').astype(jnp.float32)  # [H, 3H]
    bias = first(ins, 'Bias')
    lengths = first(ins, 'XLen')
    h0 = first(ins, 'H0')
    b, t, threeh = x.shape
    h = threeh // 3

    xf = x.astype(jnp.float32)
    if bias is not None:
        xf = xf + bias.astype(jnp.float32).reshape(1, 1, -1)

    backend = getattr(ctx, 'backend', None) or jax.default_backend()
    if attrs.get('use_pallas') and \
            attrs.get('gate_activation', 'sigmoid') == 'sigmoid' and \
            attrs.get('activation', 'tanh') == 'tanh' and \
            _pallas_rnn_fits_vmem(b, h, threeh) and \
            (backend == 'tpu' or attrs.get('pallas_interpret', False)):
        # fused Pallas time loop (ops/pallas/lstm_cell.gru_scan); ragged
        # batches run unmasked + zero-mask outside (see the lstm branch);
        # a chained h0 (seq2seq decoder) rides the kernel's h0 input
        from .pallas.lstm_cell import gru_scan
        xin, rev_idx = _maybe_reverse(xf, lengths,
                                      attrs.get('is_reverse', False))
        h0f = h0.astype(jnp.float32) if h0 is not None else None
        hs = jnp.swapaxes(gru_scan(jnp.swapaxes(xin, 0, 1), w, h0f,
                                   interpret=backend != 'tpu'), 0, 1)
        hs, = _unreverse_and_mask([hs], rev_idx, lengths, t)
        return {'Hidden': [hs.astype(x.dtype)]}

    if lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)
    lengths = lengths.astype(jnp.int32).reshape(-1)
    gate_act = _gate_act(attrs.get('gate_activation', 'sigmoid'))
    cand_act = _gate_act(attrs.get('activation', 'tanh'))
    is_reverse = attrs.get('is_reverse', False)
    w_rz = w[:, :2 * h]
    w_c = w[:, 2 * h:]
    if is_reverse:
        xf, rev_idx = _maybe_reverse(xf, lengths, True)

    h_prev = (h0.astype(jnp.float32) if h0 is not None
              else jnp.zeros((b, h), jnp.float32))

    def step(h_p, inputs):
        g_t, t_idx = inputs
        rz = g_t[:, :2 * h] + jnp.matmul(h_p, w_rz, **_ACC)
        u = gate_act(rz[:, :h])      # update gate
        r = gate_act(rz[:, h:])      # reset gate
        c = cand_act(g_t[:, 2 * h:] + jnp.matmul(r * h_p, w_c, **_ACC))
        h_t = u * h_p + (1.0 - u) * c
        alive = (t_idx < lengths)[:, None]
        h_t = jnp.where(alive, h_t, h_p)
        return h_t, h_t

    _, hs = jax.lax.scan(step, h_prev,
                         (jnp.swapaxes(xf, 0, 1), jnp.arange(t)))
    hs, = _unreverse_and_mask([jnp.swapaxes(hs, 0, 1)],
                              rev_idx if is_reverse else None, lengths, t)
    return {'Hidden': [hs.astype(x.dtype)]}


@register_op('gru_unit')
def _gru_unit(ctx, ins, attrs):
    """Single GRU step (operators/gru_unit_op): Input [B, 3H] pre-projected
    gates, HiddenPrev [B, H], Weight [H, 3H]."""
    x = first(ins, 'Input').astype(jnp.float32)
    h_p = first(ins, 'HiddenPrev').astype(jnp.float32)
    w = first(ins, 'Weight').astype(jnp.float32)
    bias = first(ins, 'Bias')
    h = h_p.shape[1]
    if bias is not None:
        x = x + bias.astype(jnp.float32).reshape(1, -1)
    gate_act = _gate_act(
        {0: 'sigmoid', 1: 'sigmoid', 2: 'tanh', 3: 'relu'}.get(
            attrs.get('gate_activation', 0), 'sigmoid')
        if isinstance(attrs.get('gate_activation', 0), int)
        else attrs.get('gate_activation', 'sigmoid'))
    cand_act = _gate_act(
        {0: 'identity', 1: 'sigmoid', 2: 'tanh', 3: 'relu'}.get(
            attrs.get('activation', 2), 'tanh')
        if isinstance(attrs.get('activation', 2), int)
        else attrs.get('activation', 'tanh'))
    rz = x[:, :2 * h] + jnp.matmul(h_p, w[:, :2 * h], **_ACC)
    u = gate_act(rz[:, :h])
    r = gate_act(rz[:, h:])
    c = cand_act(x[:, 2 * h:] + jnp.matmul(r * h_p, w[:, 2 * h:], **_ACC))
    h_t = u * h_p + (1.0 - u) * c
    dt = first(ins, 'Input').dtype
    return {'Hidden': [h_t.astype(dt)], 'ResetHiddenPrev': [(r * h_p).astype(dt)],
            'Gate': [jnp.concatenate([u, r, c], axis=1).astype(dt)]}

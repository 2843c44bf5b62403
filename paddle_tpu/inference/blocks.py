"""Block descriptions: what ``DecodeEngine`` needs to know about a
decoder's layer to serve it.

The engine owns the loop over layers, the page pools, where a
position's cached rows are written, and the three programs (a
whole-prompt ``prefill``, a ``chunk``, a decode ``step``).  A block
description supplies the rest, once, as pure functions of the weights (a
``{name: array}`` dict):

- ``names(n_layers)``      the weights it reads, by their fixed names;
- ``sizes(params)``        ``d_model`` and ``vocab_size`` from shapes,
  and ``positions`` (a learned position table's rows) where it has one;
- ``cache_rows(sizes)``    what a position caches in every layer, as
  ``((name, width), ...)``: ``(('k', Hkv * Dh), ('v', Hkv * Dh))`` for
  attention over K/V heads, ``(('latent', W),)`` for a latent cache.
  The engine holds one page pool ``[pages, page_size, width]`` a row a
  layer and nothing else about the cache;
- ``layer_kinds(n_layers)`` (optional) the kind of each layer's cache,
  ``'full'`` (every position stays) or ``'window'`` (the ``window``
  newest do), where a block has both: the engine then holds a page
  group a kind, the window group's a ring a stream, and hands each
  layer's attend its own group's table.  A third kind, ``'state'``, is
  a layer that caches NOTHING a position and a fixed-size state a
  STREAM, which it reads and rewrites (a state-space mixer): see
  "State layers" below;
- ``embed(p, tokens, positions)`` -> x [T, D] float32 (the positions
  it is handed lie inside the engine's ``max_seq``);
- ``qkv(p, x, i, positions)`` -> (q, *rows): the queries as the block's
  own attention takes them, then one [T, width] array a cache row, in
  ``cache_rows`` order, as the cache holds a position (the engine casts
  them to the pools' dtype, writes them, and attends over what it
  wrote);
- the three ways to attend, over rows already in the pools' dtype:
  ``attend_prefill(p, i, q, rows)`` -> (ctx, kept): causal attention of
  a whole prompt over its own rows; ``kept`` is what ``pack`` writes
  into the pages later, a [T, ...] array a cache row;
  ``attend_chunk(p, i, q, pools, pt, pos0)`` -> ctx: one stream's
  prompt chunk (query j at position ``pos0 + j``) over the stream's
  pages ``pt`` [MPP] of layer i's ``pools`` (one buffer a cache row);
  ``attend_step(p, i, q, pools, pt, ctx_len)`` -> ctx: one token a slot
  over the slot's pages ``pt`` [S, MPP], ``ctx_len`` [S] positions each
  (0 for an idle slot, which attends over nothing);
- ``describe(program, sizes, backend, page_size, dtype)`` -> what the
  program's ``decode.compile`` span says of the block's part in it
  (which attention its shapes take);
- ``after_attention(p, x, ctx, i, active)`` -> (x, counts): everything
  between attention and the next layer; ``counts`` is int32 [n] (tokens
  routed to each expert among the rows where ``active``) or None for a
  layer without routed experts.  A block that holds a SHARE of the
  experts (``experts_share``) ends ``counts`` with the assignments to
  experts held elsewhere;
- ``live_positions_arg``   the name under which a ``decode.step`` span
  reports the cached positions the step's attention reads (a block
  whose kernel reads one row a position says so); None: pages only;
- ``head(p, x)`` -> logits [T, V] float32;
- (optional) ``between(p, x, t)`` -> (x, gate): a block that has it
  runs its layers ``ut_steps`` times a token over the one set of
  weights (a recurrence into cache slots of its own: the engine's
  ``_layers``).  It is called where recurrence ``t`` ends, with the
  stream x [T, D]; the x it returns is what the next recurrence starts
  from (after the last, what ``head`` gets) and ``gate`` [T] float32 is
  each row's exit gate there.  ``exit_distribution(gates [R, T])`` ->
  p [R, T]: the share of a row that leaves at each recurrence.

State layers.  A block whose ``layer_kinds`` names ``'state'`` also
supplies:

- ``state_rows(sizes)`` -> what a STREAM holds in every state layer, as
  ``((name, shape, dtype), ...)`` (a shape's minor dimension whole
  128-lane registers).  The engine holds, a row a RUN of
  consecutive state layers, one pool ``[layers of the run, max_streams
  + 1, *shape]`` indexed by the server's slot (the last row is the idle
  rows' trash), and nothing else about the state;
- ``state_runs(n_layers)`` -> ``[(first layer, layers), ...]`` and
  ``state_weights(p, r)`` -> run r's weights, ``{name: [layers, ...]}``
  stacked: the engine walks a run under ONE ``lax.scan`` (the body is
  traced once, the pools are its carry) and hands the body a layer's
  slice ``w``;
- ``state_layer(w, x, rows=None, seq=None)`` -> (x, rows' new state,
  seq's new state): the whole layer over x [T, D] whose first rows are
  ONE TOKEN each on a state of their own (``rows`` = (state [R, ...] a
  state row ..., live [R]): a decode step's, or the ones a chunk
  carries) and whose other rows are ONE SEQUENCE continuing one state
  (``seq`` = (state [...] a state row ..., n_valid): a prompt or a
  chunk of it, rows from ``n_valid`` on padding that must leave the
  state where token ``n_valid - 1`` left it).  The three programs are
  its three forms, as the attends are attention's: a whole prompt is
  ``seq`` alone from zeros, a step ``rows`` alone, a chunk both;
- ``scan_path(sizes, backend, tokens)`` -> the form a sequence of
  ``tokens`` rows takes in its state layers (the ``decode.compile``
  span's ``ssm``).

Such a block's ``qkv`` / attends / ``after_attention`` are only ever
called for its other layers.

Every function takes the weights ``p`` as traced values: the engine
hands each of its programs the one placed copy as an operand, in the
precision the configuration states, so a description never closes over
an array of its own.  What a program returns stays on the device; an
engine call copies to the host what its caller reads (next-token ids,
routing counts, a prompt's last row of logits) and leaves the decode
rows' ``[S, V]`` logits there.

``positions`` are absolute token positions [T]; a block with a learned
position table indexes it in ``embed`` and the engine's ``max_seq``
defaults to its rows, a rotary block turns q and k in ``qkv`` and the
engine's ``max_seq`` is a setting.
"""
import jax
import jax.numpy as jnp

from ..core.registry import get_op_impl
from ..ops.attention import (_dense_attention, _grouped,
                             chunk_attention_path, latent_attention_path,
                             paged_attention_path)
from ..ops.moe import (moe_counts, moe_experts, moe_route,
                       moe_route_grouped, rms_norm_math, rotary_math,
                       swiglu_math, yarn_mscale)
from ..ops.ssm import (causal_conv1d_math, selective_scan,
                       selective_scan_path, selective_state_update_math)

__all__ = ['KVBlock', 'OptBlock', 'OlmoeBlock', 'DotsVlmBlock',
           'LagunaBlock', 'OuroBlock', 'JambaBlock']


def _mm(x, w):
    """Matmul at the weights' precision: the activation takes the
    weight's dtype, the accumulator is float32."""
    return jnp.dot(x.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


class KVBlock(object):
    """The cache and the attention of attention over K/V heads: a
    position caches one K and one V row of ``n_kv_heads * head_dim``
    (``n_kv_heads`` is ``n_heads`` unless the block says otherwise:
    full multi-head attention), ``qkv`` returns q [T, H, Dh] and k, v
    [T, Hkv * Dh], and the three attends are the dense causal one,
    ``chunked_prefill_attention`` and ``paged_attention``
    (ops/attention.py), which take the K/V heads from the row they are
    handed and group the query heads over them.  ``window_of(i)`` is
    how many of the newest positions layer i reads (None: all)."""

    experts_share = False
    live_positions_arg = None

    @property
    def n_kv_heads(self):
        return self.n_heads

    def head_dim(self, sizes):
        return sizes['d_model'] // self.n_heads

    def window_of(self, i):
        return None

    def cache_rows(self, sizes):
        width = self.n_kv_heads * self.head_dim(sizes)
        return (('k', width), ('v', width))

    def describe(self, program, sizes, backend, page_size, dtype):
        # what the op's dispatch takes for these shapes (the step calls
        # it with no context: the default backend)
        return {'attention': paged_attention_path(
            backend, self.n_kv_heads, self.head_dim(sizes), page_size,
            dtype, self.n_heads // self.n_kv_heads)} \
            if program == 'step' else {}

    def attend_prefill(self, p, i, q, rows):
        t, h, dh = q.shape
        k, v = (r.reshape(t, -1, dh) for r in rows)
        kq, vq = (_grouped(r, h) for r in (k, v))
        return _dense_attention(q[None], kq[None], vq[None], True, None,
                                **self._window(i))[0], (k, v)

    def _window(self, i):
        w = self.window_of(i)
        return {} if w is None else {'window': w}

    def attend_chunk(self, p, i, q, pools, pt, pos0):
        return get_op_impl('chunked_prefill_attention').compute(
            None, {'Q': [q], 'KPool': [pools[0]], 'VPool': [pools[1]],
                   'PT': [pt], 'Pos0': [pos0]}, self._window(i))['Out'][0]

    def attend_step(self, p, i, q, pools, pt, ctx_len):
        return get_op_impl('paged_attention').compute(
            None, {'Q': [q], 'KPool': [pools[0]], 'VPool': [pools[1]],
                   'PT': [pt], 'CtxLen': [ctx_len]},
            self._window(i))['Out'][0]


class OptBlock(KVBlock):
    """The OPT layer (models/transformer.py builds the same block as a
    ``Program``; chipbench/reference/opt.py is its plain reference):
    learned positions, pre-LayerNorm, one fused q/k/v projection, full
    multi-head attention, a ReLU FFN, biases everywhere, a final
    LayerNorm and an untied head with a bias."""

    def __init__(self, n_heads, eps=1e-5):
        self.n_heads = int(n_heads)
        self.eps = float(eps)

    @staticmethod
    def names(n_layers):
        from ..models.transformer import param_names
        return param_names(n_layers)

    def sizes(self, params):
        v, d = params['tr_embed'].shape
        return {'d_model': int(d), 'vocab_size': int(v),
                'positions': int(params['tr_pos'].shape[0])}

    def norm(self, x, w, b):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        return (xf - mean) / jnp.sqrt(var + self.eps) * w + b

    def embed(self, p, tokens, positions):
        return p['tr_embed'][tokens] + p['tr_pos'][positions]

    def qkv(self, p, x, i, positions):
        n = 'tr_l%d_' % i
        h = self.norm(x, p[n + 'ln_attn_w'], p[n + 'ln_attn_b'])
        q, k, v = jnp.split(h @ p[n + 'qkv_w'] + p[n + 'qkv_b'], 3,
                            axis=-1)
        return q.reshape(x.shape[0], self.n_heads, -1), k, v

    def activation(self, h):
        return jnp.maximum(h, 0.0)

    def after_attention(self, p, x, ctx, i, active):
        n = 'tr_l%d_' % i
        x = x + ctx.reshape(x.shape) @ p[n + 'proj_w'] + p[n + 'proj_b']
        h = self.norm(x, p[n + 'ln_ffn_w'], p[n + 'ln_ffn_b'])
        h = self.activation(h @ p[n + 'ffn_up_w'] + p[n + 'ffn_up_b'])
        return x + h @ p[n + 'ffn_down_w'] + p[n + 'ffn_down_b'], None

    def head(self, p, x):
        x = self.norm(x, p['tr_ln_f_w'], p['tr_ln_f_b'])
        return x @ p['tr_head_w'] + p['tr_head_b']


class OlmoeBlock(KVBlock):
    """The OLMoE layer (models/olmoe.py builds the same block as a
    ``Program``; chipbench/reference/olmoe.py is its plain reference):
    pre-RMSNorm, QK-norm over the whole projected row before the split
    into heads, rotary positions (half-split pairing), full multi-head
    attention, then a routed-expert FFN — float32 softmax router, the
    ``top_k`` largest experts a token, weights as they are
    (``renormalize`` False, the published ``norm_topk_prob``), every
    token reaching every one of its experts.  Keys are cached AFTER
    QK-norm and rotation, values as they are."""

    def __init__(self, n_heads, top_k=8, eps=1e-5, theta=10000.0,
                 renormalize=False):
        self.n_heads = int(n_heads)
        self.top_k = int(top_k)
        self.eps = float(eps)
        self.theta = float(theta)
        self.renormalize = bool(renormalize)

    @staticmethod
    def names(n_layers):
        from ..models.olmoe import param_names
        return param_names(n_layers)

    def sizes(self, params):
        v, d = params['olmoe_embed'].shape
        return {'d_model': int(d), 'vocab_size': int(v)}

    def norm(self, x, w):
        return rms_norm_math(x, w, self.eps)

    def qk_norm(self, u, w):
        """Over the whole [T, H * Dh] row, before the heads are split."""
        return self.norm(u, w)

    def rotate(self, u, positions):
        return rotary_math(u, positions, self.theta)

    def embed(self, p, tokens, positions):
        return p['olmoe_embed'][tokens].astype(jnp.float32)

    def qkv(self, p, x, i, positions):
        n = 'olmoe_l%d_' % i
        t, d = x.shape
        heads = (t, self.n_heads, d // self.n_heads)
        h = self.norm(x, p[n + 'in_norm_w'])
        q = self.qk_norm(_mm(h, p[n + 'q_w']), p[n + 'q_norm_w'])
        k = self.qk_norm(_mm(h, p[n + 'k_w']), p[n + 'k_norm_w'])
        q = self.rotate(q.reshape(heads), positions)
        k = self.rotate(k.reshape(heads), positions).reshape(t, d)
        return q, k, _mm(h, p[n + 'v_w'])

    def after_attention(self, p, x, ctx, i, active):
        n = 'olmoe_l%d_' % i
        x = x + _mm(ctx.reshape(x.shape), p[n + 'o_w'])
        h = self.norm(x, p[n + 'post_norm_w'])
        w, idx = moe_route(h, p[n + 'router_w'], self.top_k,
                           self.renormalize)
        y = moe_experts(h, w, idx, p[n + 'gate_w'], p[n + 'up_w'],
                        p[n + 'down_w'])
        return x + y, moe_counts(idx, p[n + 'router_w'].shape[1], active)

    def head(self, p, x):
        return _mm(self.norm(x, p['olmoe_norm_f_w']), p['olmoe_head_w'])


class DotsVlmBlock(object):
    """The layer of dots.vlm1's language model, a DeepSeek-V3-shaped
    decoder (models/dots_vlm.py builds the same block as a ``Program``;
    chipbench/reference/dots_vlm.py is its plain reference): pre-RMSNorm,
    multi-head LATENT attention (low-rank queries; one compressed
    key/value latent and one rotary key a position, shared by all
    heads; YaRN rotary frequencies, interleaved pairing, softmax scale
    ``(nope + rope)^-1/2 * mscale^2``), then ``first_dense`` leading
    layers with a dense SwiGLU FFN and after them routed experts under
    the grouped sigmoid router plus a shared expert.

    The cache holds, per position and layer, ONE row: [the latent after
    its norm | the rotary key after rotation | zeros up to a whole
    number of 128-lane registers], and nothing per head.  A whole-prompt
    prefill attends in the expanded form (keys and values of every head
    rebuilt from the rows); a decode step in the absorbed form (the key
    up-projection folded into the query, the value up-projection
    applied to the attended latent), which reads the rows as they are;
    so does a chunk (on the chip 0.64-2.45 ms a layer for 256 rows over
    0-3.6k cached positions, against 5.22 ms expanded: PERF.md).  The
    two forms are the same numbers (tests/test_dots_vlm_decode.py).

    This chip may hold a SHARE of each layer's routed experts, experts
    ``first_expert ..`` as many as the stacked weights hold: the router
    keeps its published width, the held experts' part is computed, what
    the others would add is left out (ops/moe.py ``moe_experts``)."""

    experts_share = True
    live_positions_arg = 'kv_latent_live_positions'
    LANES = 128

    def __init__(self, n_heads, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, top_k=8, n_group=8, topk_group=4,
                 routed_scaling_factor=2.5, renormalize=True,
                 first_expert=0, first_dense=1, eps=1e-6, theta=10000.0,
                 yarn=None, mscale_all_dim=1.0):
        self.n_heads = int(n_heads)
        self.nope, self.rope = int(qk_nope_head_dim), int(qk_rope_head_dim)
        self.v_dim = int(v_head_dim)
        self.top_k, self.n_group = int(top_k), int(n_group)
        self.topk_group = int(topk_group)
        self.routed_scale = float(routed_scaling_factor)
        self.renormalize = bool(renormalize)
        self.first_expert, self.first_dense = int(first_expert), \
            int(first_dense)
        self.eps, self.theta = float(eps), float(theta)
        self.yarn = dict(yarn) if yarn else None
        m = yarn_mscale(self.yarn['factor'], mscale_all_dim) \
            if self.yarn else 1.0
        self.softmax_scale = (self.nope + self.rope) ** -0.5 * m * m

    def names(self, n_layers):
        from ..models.dots_vlm import param_names
        return param_names(n_layers, self.first_dense)

    def sizes(self, params):
        v, d = params['dots_embed'].shape
        return {'d_model': int(d), 'vocab_size': int(v),
                'kv_lora_rank': int(params['dots_l0_kv_norm_w'].shape[0])}

    def cache_rows(self, sizes):
        w = sizes['kv_lora_rank'] + self.rope
        return (('latent', -(-w // self.LANES) * self.LANES),)

    def describe(self, program, sizes, backend, page_size, dtype):
        paged = latent_attention_path(backend, page_size, dtype)
        (name, width), = self.cache_rows(sizes)
        return {'attention_path': {'prefill': 'dense_expanded',
                                   'pack': None, 'step': paged,
                                   'chunk': '%s+%s' % (paged, paged)
                                   }[program],
                'cache_rows': {name: width},
                'cache_bytes_per_position': width * jnp.dtype(dtype).itemsize}

    def norm(self, x, w):
        return rms_norm_math(x, w, self.eps)

    def rotate(self, u, positions):
        return rotary_math(u, positions, self.theta, self.yarn,
                           interleaved=True)

    def latent_row(self, c_raw, r_raw, w, positions):
        """A position's cached row (unpadded): the latent AFTER its
        norm, the rotary key AFTER rotation."""
        return jnp.concatenate(
            [self.norm(c_raw, w),
             self.rotate(r_raw[:, None, :], positions)[:, 0]], axis=-1)

    def route(self, h, router_w, bias):
        return moe_route_grouped(h, router_w, bias, self.top_k,
                                 self.n_group, self.topk_group,
                                 self.routed_scale, self.renormalize)

    def embed(self, p, tokens, positions):
        return p['dots_embed'][tokens].astype(jnp.float32)

    def qkv(self, p, x, i, positions):
        n = 'dots_l%d_' % i
        t = x.shape[0]
        h = self.norm(x, p[n + 'in_norm_w'])
        cq = self.norm(_mm(h, p[n + 'qa_w']), p[n + 'q_norm_w'])
        q = _mm(cq, p[n + 'qb_w']).reshape(t, self.n_heads, -1)
        q = jnp.concatenate(
            [q[..., :self.nope], self.rotate(q[..., self.nope:], positions)],
            axis=-1)
        rank = p[n + 'kv_norm_w'].shape[0]
        kva = _mm(h, p[n + 'kva_w'])
        row = self.latent_row(kva[:, :rank], kva[:, rank:],
                              p[n + 'kv_norm_w'], positions)
        return q, self._lanes(row)

    def _lanes(self, u):
        """``u`` [..., w] with zeros up to the cached row's width."""
        pad = -u.shape[-1] % self.LANES
        return jnp.pad(u, [(0, 0)] * (u.ndim - 1) + [(0, pad)])

    def _kvb(self, p, i):
        """W_kvb [rank, H, nope + v]: per head the key and the value
        up-projection of the latent."""
        w = p['dots_l%d_kvb_w' % i]
        return w.reshape(w.shape[0], self.n_heads, self.nope + self.v_dim)

    def _attend_expanded(self, p, i, q, rows, valid):
        """q [Tq, H, nope + rope] over cached ``rows`` [Tk, W] where
        ``valid`` [Tq, Tk]: every head's keys and values rebuilt."""
        f32 = jnp.float32
        w = self._kvb(p, i)
        rank = w.shape[0]
        kv = jnp.einsum('kc,chd->khd', rows[:, :rank].astype(w.dtype), w,
                        preferred_element_type=f32)
        s = jnp.einsum('qhn,khn->hqk', q[..., :self.nope],
                       kv[..., :self.nope]) \
            + jnp.einsum('qhr,kr->hqk', q[..., self.nope:],
                         rows[:, rank:rank + self.rope].astype(f32))
        s = jnp.where(valid[None], s * self.softmax_scale, -1e30)
        return jnp.einsum('hqk,khv->qhv', jax.nn.softmax(s, axis=-1),
                          kv[..., self.nope:])

    def _absorb(self, p, i, q):
        """The key up-projection folded into the query: [T, H, W]
        against the cached rows as they are."""
        w = self._kvb(p, i)
        qt = jnp.einsum('thn,chn->thc', q[..., :self.nope].astype(w.dtype),
                        w[..., :self.nope],
                        preferred_element_type=jnp.float32)
        return self._lanes(jnp.concatenate([qt, q[..., self.nope:]],
                                           axis=-1))

    def _attend_absorbed(self, op, p, i, q, pool, **ins):
        w = self._kvb(p, i)
        ctx = get_op_impl(op).compute(
            None, dict({'Q': [self._absorb(p, i, q)], 'Pool': [pool]},
                       **{k: [v] for k, v in ins.items()}),
            {'scale': self.softmax_scale, 'value_dim': w.shape[0]}
        )['Out'][0]
        return jnp.einsum('thc,chv->thv', ctx.astype(w.dtype),
                          w[..., self.nope:],
                          preferred_element_type=jnp.float32)

    def attend_prefill(self, p, i, q, rows):
        t = q.shape[0]
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        return self._attend_expanded(p, i, q, rows[0], causal), rows

    def attend_chunk(self, p, i, q, pools, pt, pos0):
        return self._attend_absorbed(
            'latent_chunked_prefill_attention', p, i, q, pools[0],
            PT=pt, Pos0=pos0)

    def attend_step(self, p, i, q, pools, pt, ctx_len):
        return self._attend_absorbed('latent_paged_attention', p, i, q,
                                     pools[0], PT=pt, CtxLen=ctx_len)

    def after_attention(self, p, x, ctx, i, active):
        n = 'dots_l%d_' % i
        x = x + _mm(ctx.reshape(x.shape[0], -1), p[n + 'o_w'])
        h = self.norm(x, p[n + 'post_norm_w'])
        ffn = p[n + 'gate_w'], p[n + 'up_w'], p[n + 'down_w']
        if i < self.first_dense:       # a leading dense layer
            return x + swiglu_math(h, *ffn), None
        w, idx = self.route(h, p[n + 'router_w'], p[n + 'router_bias'])
        y = moe_experts(h, w, idx, *ffn, first=self.first_expert,
                        shared=(p[n + 'shared_gate_w'],
                                p[n + 'shared_up_w'],
                                p[n + 'shared_down_w']))
        return x + y, moe_counts(idx, ffn[0].shape[0], active,
                                 first=self.first_expert)

    def head(self, p, x):
        return _mm(self.norm(x, p['dots_norm_f_w']), p['dots_head_w'])


class LagunaBlock(KVBlock):
    """The layer of poolside's Laguna-S-2.1 (models/laguna.py declares
    the same parameters; chipbench/reference/laguna.py is its plain
    reference): pre-RMSNorm; attention of ``heads[i]`` query heads over
    ``n_kv_heads`` K/V heads, more query heads on the layers that read a
    WINDOW of the newest positions (``kinds[i] == 'window'``) than on
    those that read everything; rotary positions by kind (``rope``: a
    dict a kind of ``theta``, the lanes of a head that turn, ``yarn``
    and the factor on cos and sin); a gate a head, the sigmoid of a
    projection of the layer's normed input, on the attended values
    before the output projection; then ``first_dense`` leading layers
    with a dense SwiGLU FFN and after them routed experts under a
    softmax router whose ``top_k`` weights are renormalised and scaled,
    beside a shared expert.  Keys are cached after rotation.

    A position caches K and V of ``n_kv_heads * head_dim`` lanes in
    every layer, whatever the layer's query heads.  The window layers'
    pages are a group of their own (``layer_kinds``): a stream holds a
    ring there, not its whole context.  This chip may hold a SHARE of
    each layer's routed experts, ``first_expert ..``, as ``DotsVlmBlock``
    does."""

    experts_share = True
    n_heads = None          # by layer: ``heads``

    def __init__(self, heads, n_kv_heads, head_dim, kinds, window, rope,
                 top_k=10, routed_scaling_factor=2.5, first_expert=0,
                 first_dense=1, eps=1e-6):
        self.heads = tuple(int(h) for h in heads)
        self._kv_heads, self._head_dim = int(n_kv_heads), int(head_dim)
        self.kinds = tuple(kinds)
        if set(self.kinds) - {'full', 'window'} or \
                len(self.kinds) != len(self.heads):
            raise ValueError("a kind a layer, 'full' or 'window': %r"
                             % (self.kinds,))
        self.window = int(window)
        self.rope = {k: dict(v) for k, v in rope.items()}
        self.top_k = int(top_k)
        self.routed_scale = float(routed_scaling_factor)
        self.first_expert, self.first_dense = int(first_expert), \
            int(first_dense)
        self.eps = float(eps)

    @property
    def n_kv_heads(self):
        return self._kv_heads

    def head_dim(self, sizes):
        return self._head_dim

    def window_of(self, i):
        return self.window if self.kinds[i] == 'window' else None

    def layer_kinds(self, n_layers):
        return self.kinds[:n_layers]

    def names(self, n_layers):
        from ..models.laguna import param_names
        return param_names(n_layers, self.first_dense)

    def sizes(self, params):
        v, d = params['laguna_embed'].shape
        return {'d_model': int(d), 'vocab_size': int(v)}

    def describe(self, program, sizes, backend, page_size, dtype):
        if program not in ('step', 'chunk'):
            return {}
        by_kind = {k: self.heads[self.kinds.index(k)]
                   for k in sorted(set(self.kinds))}
        row = 2 * self._kv_heads * self._head_dim \
            * jnp.dtype(dtype).itemsize
        step = {k: paged_attention_path(
            backend, self._kv_heads, self._head_dim, page_size, dtype,
            h // self._kv_heads) for k, h in by_kind.items()}
        out = {'attention': step, 'heads': by_kind,
               'kv_heads': self._kv_heads, 'window': self.window,
               'cache_bytes_per_position': {k: row for k in by_kind}}
        if program == 'chunk':
            out['attention'] = {k: '%s+%s' % (step[k], chunk_attention_path(
                backend, self._kv_heads, self._head_dim, page_size, dtype,
                by_kind[k], sizes['table_pages'][k]))
                for k in by_kind}
        return out

    def norm(self, x, w):
        return rms_norm_math(x, w, self.eps)

    def rotate(self, u, positions, kind):
        """u [T, H, Dh]: the first ``lanes`` of every head turn, the
        others pass; cos and sin carry the kind's ``factor``."""
        r = self.rope[kind]
        lanes = int(r.get('lanes', u.shape[-1]))
        turned = rotary_math(u[..., :lanes], positions, r['theta'],
                             r.get('yarn')) * r.get('factor', 1.0)
        return turned if lanes == u.shape[-1] else jnp.concatenate(
            [turned, u[..., lanes:].astype(jnp.float32)], axis=-1)

    def embed(self, p, tokens, positions):
        return p['laguna_embed'][tokens].astype(jnp.float32)

    def qkv(self, p, x, i, positions):
        n = 'laguna_l%d_' % i
        t, dh = x.shape[0], self._head_dim
        u = self.norm(x, p[n + 'in_norm_w'])
        q = _mm(u, p[n + 'q_w']).reshape(t, self.heads[i], dh)
        k = _mm(u, p[n + 'k_w']).reshape(t, self._kv_heads, dh)
        q = self.rotate(q, positions, self.kinds[i])
        k = self.rotate(k, positions, self.kinds[i]).reshape(t, -1)
        return q, k, _mm(u, p[n + 'v_w'])

    def after_attention(self, p, x, ctx, i, active):
        n = 'laguna_l%d_' % i
        # the gate a head, from the layer's normed input (the same
        # ``u`` as ``qkv``'s: the compiler keeps one)
        u = self.norm(x, p[n + 'in_norm_w'])
        gate = jax.nn.sigmoid(_mm(u, p[n + 'g_w']))            # [T, H]
        x = x + _mm((ctx.astype(jnp.float32)
                     * gate[:, :, None]).reshape(x.shape[0], -1),
                    p[n + 'o_w'])
        h = self.norm(x, p[n + 'post_norm_w'])
        ffn = p[n + 'gate_w'], p[n + 'up_w'], p[n + 'down_w']
        if i < self.first_dense:       # a leading dense layer
            return x + swiglu_math(h, *ffn), None
        w, idx = moe_route(h, p[n + 'router_w'], self.top_k, True,
                           self.routed_scale)
        y = moe_experts(h, w, idx, *ffn, first=self.first_expert,
                        shared=(p[n + 'shared_gate_w'],
                                p[n + 'shared_up_w'],
                                p[n + 'shared_down_w']))
        return x + y, moe_counts(idx, ffn[0].shape[0], active,
                                 first=self.first_expert)

    def head(self, p, x):
        return _mm(self.norm(x, p['laguna_norm_f_w']), p['laguna_head_w'])


class OuroBlock(KVBlock):
    """The layer of ByteDance's Ouro (models/ouro.py declares the same
    parameters; chipbench/reference/ouro.py is its plain reference), a
    stack that runs ``ut_steps`` times a token over ONE set of weights:
    RMSNorm on each branch's way in AND on its way out ("sandwich"),
    full multi-head attention with rotary positions over the whole head
    (half-split pairing; keys cached after rotation), a SwiGLU FFN, no
    biases.  ``between`` closes a recurrence with the final norm, whose
    output the next recurrence starts from, and reads the exit gate off
    it: one sigmoid a row.  ``head`` is therefore the plain product
    with the head's matrix: the last recurrence's closing norm has
    already normalised what it is handed.

    A recurrence has K/V of its own: a position is cached in ``ut_steps``
    slots a layer, none shared (the engine's cache counts them).

    The exit rule: with gates g[t], p[t] = g[t] prod_{s<t} (1 - g[s])
    and the last recurrence takes the remainder; a token would leave at
    the first t whose running sum of p reaches ``early_exit_threshold``.
    At the published 1.0 none leaves early and every recurrence runs for
    every row, which is what is served: rows of one batch leaving at
    different recurrences is not built, and a lower threshold is
    refused."""

    def __init__(self, n_heads, ut_steps=4, early_exit_threshold=1.0,
                 eps=1e-6, theta=1e6):
        if float(early_exit_threshold) < 1.0:
            raise ValueError(
                "early_exit_threshold %g: only 1.0 (the published value: "
                "every recurrence runs for every token) is served; rows "
                "of one batch leaving the loop at different recurrences, "
                "and what their skipped cache slots hold for later "
                "tokens, are not built" % early_exit_threshold)
        self.n_heads = int(n_heads)
        self.ut_steps = int(ut_steps)
        self.eps, self.theta = float(eps), float(theta)

    @staticmethod
    def names(n_layers):
        from ..models.ouro import param_names
        return param_names(n_layers)

    def sizes(self, params):
        v, d = params['ouro_embed'].shape
        return {'d_model': int(d), 'vocab_size': int(v)}

    def norm(self, x, w):
        return rms_norm_math(x, w, self.eps)

    def embed(self, p, tokens, positions):
        return p['ouro_embed'][tokens].astype(jnp.float32)

    def qkv(self, p, x, i, positions):
        n = 'ouro_l%d_' % i
        t, d = x.shape
        heads = (t, self.n_heads, d // self.n_heads)
        a = self.norm(x, p[n + 'in_norm_w'])
        q = rotary_math(_mm(a, p[n + 'q_w']).reshape(heads), positions,
                        self.theta)
        k = rotary_math(_mm(a, p[n + 'k_w']).reshape(heads), positions,
                        self.theta).reshape(t, d)
        return q, k, _mm(a, p[n + 'v_w'])

    def out_norm(self, y, w):
        """The norm on a branch's way out."""
        return self.norm(y, w)

    def after_attention(self, p, x, ctx, i, active):
        n = 'ouro_l%d_' % i
        x = x + self.out_norm(_mm(ctx.reshape(x.shape), p[n + 'o_w']),
                              p[n + 'in_norm2_w'])
        m = self.norm(x, p[n + 'post_norm_w'])
        y = swiglu_math(m, p[n + 'gate_w'], p[n + 'up_w'], p[n + 'down_w'])
        return x + self.out_norm(y, p[n + 'post_norm2_w']), None

    def between(self, p, x, t):
        x = self.norm(x, p['ouro_norm_f_w'])
        return x, jax.nn.sigmoid(x @ p['ouro_exit_w'].astype(jnp.float32)
                                 + p['ouro_exit_b'][0])

    @staticmethod
    def exit_distribution(gates):
        stay = jnp.cumprod(1.0 - gates, axis=0)
        stay = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
        return jnp.concatenate([gates[:-1] * stay[:-1], stay[-1:]])

    def head(self, p, x):
        return _mm(x, p['ouro_head_w'])


class JambaBlock(KVBlock):
    """The layers of AI21's Jamba (models/jamba.py declares the same
    parameters; chipbench/reference/jamba.py is its plain reference):
    layer i is attention where ``i % period == offset`` and a Mamba-1
    mixer everywhere else (``layer_kinds``: ``'full'`` and ``'state'``),
    each behind a pre-RMSNorm and followed by a pre-normed gated MLP
    (SiLU); a final RMSNorm; the head is the embedding, transposed.

    Attention: ``n_heads`` query heads over ``n_kv_heads`` K/V heads,
    no bias and NO positional encoding: ``qkv`` turns nothing, keys are
    cached as projected.

    The Mamba mixer, for its normed input h: ``[u, z] = h W_in``; ``v =
    silu(conv(u))``, a causal depthwise convolution over the last
    ``d_conv`` inputs; ``[dt, B, C] = v W_x``, each through an RMSNorm
    of its own (Jamba's); ``dt = softplus(dt W_dt + b_dt)``; the
    selective scan ``s = exp(dt A) s + (dt v) B``, ``y = s . C + D v``
    with ``A = -exp(a_log)``; out ``(y silu(z)) W_out``.  A stream holds
    ``s`` [d_state, d_inner] and the convolution's last ``d_conv - 1``
    inputs, float32, in every such layer (``state_rows``), and no K/V
    there.  The matrices are multiplied once for all the rows of a
    call; the carried decode rows take ``selective_state_update``, the
    sequence rows ``selective_scan`` (ops/ssm.py; on the chip the
    Pallas kernel)."""

    def __init__(self, n_heads, n_kv_heads, head_dim, period, offset,
                 eps=1e-6):
        self.n_heads = int(n_heads)
        self._kv_heads, self._head_dim = int(n_kv_heads), int(head_dim)
        self.period, self.offset = int(period), int(offset)
        self.eps = float(eps)

    @property
    def n_kv_heads(self):
        return self._kv_heads

    def head_dim(self, sizes):
        return self._head_dim

    def layer_kinds(self, n_layers):
        from ..models.jamba import layer_kinds
        return layer_kinds(n_layers, self.period, self.offset)

    def state_runs(self, n_layers):
        from ..models.jamba import state_runs
        return state_runs(self.layer_kinds(n_layers))

    def names(self, n_layers):
        from ..models.jamba import param_names
        return param_names(n_layers, self.period, self.offset)

    def sizes(self, params):
        v, d = params['jamba_embed'].shape
        _n, k, dc = params['jamba_r0_conv_w'].shape
        return {'d_model': int(d), 'vocab_size': int(v),
                'd_inner': int(dc), 'd_conv': int(k),
                'd_state': int(params['jamba_r0_a_log'].shape[1])}

    def state_rows(self, sizes):
        # the convolution's carried inputs as ONE row of (d_conv - 1) x
        # d_inner lanes: held [.., slots, d_conv - 1, d_inner] the
        # compiler keeps the pool slots-minor and re-lays the whole of
        # it out at every program's edge and once a layer (1.98 ms of a
        # 30.9 ms chunk on the chip: PERF.md section 6, PR 59)
        dc = sizes['d_inner']
        return (('ssm', (sizes['d_state'], dc), jnp.float32),
                ('conv', ((sizes['d_conv'] - 1) * dc,), jnp.float32))

    @staticmethod
    def state_weights(p, r):
        from ..models.jamba import STATE_LAYER
        return {s: p['jamba_r%d_%s' % (r, s)] for s in STATE_LAYER}

    def scan_path(self, sizes, backend, tokens):
        return selective_scan_path(backend, tokens, sizes['d_inner'],
                                   sizes['d_state'])

    def norm(self, x, w):
        return rms_norm_math(x, w, self.eps)

    def embed(self, p, tokens, positions):
        return p['jamba_embed'][tokens].astype(jnp.float32)

    def mlp(self, w, x):
        h = self.norm(x, w['post_norm_w'])
        return swiglu_math(h, w['gate_w'], w['up_w'], w['down_w'])

    # -- the attention layers ----------------------------------------------

    @staticmethod
    def _attention_weights(p, i):
        from ..models.jamba import ATTENTION_LAYER
        return {s: p['jamba_l%d_%s' % (i, s)] for s in ATTENTION_LAYER}

    def qkv(self, p, x, i, positions):
        w = self._attention_weights(p, i)
        h = self.norm(x, w['in_norm_w'])
        q = _mm(h, w['q_w']).reshape(x.shape[0], self.n_heads, -1)
        return q, _mm(h, w['k_w']), _mm(h, w['v_w'])

    def after_attention(self, p, x, ctx, i, active):
        w = self._attention_weights(p, i)
        x = x + _mm(ctx.reshape(x.shape[0], -1), w['o_w'])
        return x + self.mlp(w, x), None

    # -- the state layers -----------------------------------------------------

    def seq_valid(self, n_valid, rows):
        """How many of a sequence's ``rows`` rows advance its state."""
        return n_valid

    def carried(self, c):
        """The convolution's inputs from before a sequence's first."""
        return c

    def small_norms(self, w, dt, b, c):
        return (self.norm(dt, w['dt_norm_w']), self.norm(b, w['b_norm_w']),
                self.norm(c, w['c_norm_w']))

    def state_layer(self, w, x, rows=None, seq=None):
        y, rows, seq = self.mixer(w, self.norm(x, w['in_norm_w']), rows,
                                  seq)
        x = x + y
        return x + self.mlp(w, x), rows, seq

    def mixer(self, w, h, rows, seq):
        f32 = jnp.float32
        uz = _mm(h, w['in_w'])
        dc = uz.shape[1] // 2
        u, z = uz[:, :dc], uz[:, dc:]
        R = rows[0].shape[0] if rows else 0
        conv = w['conv_w'], w['conv_b']
        vs = []
        if rows:
            s_r, c_r, live = rows
            v, c_r = causal_conv1d_math(u[:R], *conv,
                                        c_r.reshape(R, -1, dc), live)
            vs.append(v)
        if seq:
            s_q, c_q, n_valid = seq
            n_valid = self.seq_valid(n_valid, u.shape[0] - R)
            v, c_q = causal_conv1d_math(
                u[R:], *conv, self.carried(c_q.reshape(-1, dc)), n_valid)
            vs.append(v)
        v = jnp.concatenate(vs) if len(vs) > 1 else vs[0]
        n = w['a_log'].shape[0]
        dbc = _mm(v, w['x_w'])
        dt, b, c = self.small_norms(
            w, dbc[:, :-2 * n], dbc[:, -2 * n:-n], dbc[:, -n:])
        dt = jax.nn.softplus(_mm(dt, w['dt_w']) + w['dt_b'].astype(f32))
        a, d = -jnp.exp(w['a_log'].astype(f32)), w['d'].astype(f32)
        ys = []
        if rows:
            y, s_r = selective_state_update_math(
                s_r, v[:R], dt[:R], a, b[:R], c[:R], d, live)
            ys.append(y)
            rows = (s_r, c_r.reshape(R, -1))
        if seq:
            y, s_q = selective_scan(v[R:], dt[R:], a, b[R:], c[R:], d,
                                    s_q, n_valid)
            ys.append(y)
            seq = (s_q, c_q.reshape(-1))
        y = jnp.concatenate(ys) if len(ys) > 1 else ys[0]
        return _mm(y * jax.nn.silu(z), w['out_w']), rows, seq

    def head(self, p, x):
        # x E^T, contracted on the embedding's minor dimension as it
        # lies: no transposed copy of the table
        e = p['jamba_embed']
        return jax.lax.dot_general(
            self.norm(x, p['jamba_norm_f_w']).astype(e.dtype), e,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

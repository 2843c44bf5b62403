"""Liveness-based peak-memory model: modeled HBM residency from the IR.

The Fluid reference shipped a ``memory_optimization_transpiler`` because
activation memory — not FLOPs — is what kills a define-then-run graph on
an accelerator.  The PR-9 cost model says where a step's FLOPs and bytes
*go*; this pass says how many bytes are *resident at once*: a liveness
walk over the post-rewrite, shape-resolved plan IR producing a modeled
**peak resident bytes** per plan plus a per-op live-bytes timeline.  It
runs as a registered ANALYSIS pass (PassManager order 96, right after
the cost model, so it sees the same post-graph-opt post-AMP program and
the same feed-spec-seeded shapes), and its report lands in
``last_graph_opt_report['cost']['memory']``.

Model, in op order over the global block:

- **Persistables** (params, optimizer moments, scale state) are
  resident for the whole step and counted ONCE — their updates are
  donated in-place at the jit boundary, so old+new never coexist in the
  model (an unusable state donation is exactly the regression the
  executor's donation-warning filter re-emits).
- **Feeds** become live before op 0.  When the executor donates the
  staged feed buffers (the default for executor-staged host data), each
  feed buffer is credited back at its LAST USE — XLA reuses the dead
  buffer for intermediates — so it stops counting toward residency
  after that op; ``donate_feeds=False`` models caller-owned buffers
  that stay live across the step.
- **Intermediates** are live from the op that writes them to the op
  that last reads them; fetched names escape the step and stay live to
  the end.  Bytes come from the same resolution the cost walk uses
  (declared VarDesc shapes with the -1 batch bound from feed specs,
  memoized ``core/infer.py`` re-inference for declaration-less
  outputs), so a bf16 value post-AMP counts 2 bytes.
- **The backward** (the single ``autodiff`` op) keeps the activations
  of its loss-contributing forward slice alive until it runs — that
  frontier IS the activation-memory problem.  ``memory_optimize``'s
  rematerialization marker shrinks it to exactly the working set the
  policy claims: ``'dots'`` keeps only matmul-shaped outputs
  (``registry.COST_MAC``) live across the fwd/bwd boundary, ``'full'``
  keeps none (everything recomputes from params + feeds).
- **Sharded plans** (``PADDLE_TPU_MESH``): every name the sharding
  pass assigned a shard divisor — fsdp-sharded params and optimizer
  accumulators, dp-sharded feeds and activations — is resident at
  1/K of its bytes per device, so modeled peak HBM reflects what one
  chip actually holds (``sharding`` block carries the unsharded total
  for comparison).
- **Waived ops** (``WAIVED_OPS`` + control-flow/env/sub-block ops):
  outputs whose dense extent is data-dependent (SelectedRows handles,
  LoDTensorArrays, beam state) carry no per-op live-bytes verdict; they
  are named in ``coverage``, never silently sized 0.

The report's ``watermark`` names the top-K ops by modeled live bytes —
the ops a memory regression hunt should look at first — and
``timeline`` is the full per-op sawtooth the executor exports as a
Chrome trace counter track (``ph:"C"``) next to the measured
``device.memory_stats()`` samples.
"""
from ..core import registry
from . import cost_model as _cm

__all__ = ['analyze_memory', 'page_pool_bytes', 'prefix_cached_bytes',
           'WAIVED_OPS']


def page_pool_bytes(num_pages, page_size, num_heads=None, head_dim=None,
                    dtype='float32', n_layers=1, kv=2, row_widths=None):
    """Modeled HBM residency of the decode engine's paged cache:
    ``n_layers x num_pages x page_size x (what a position caches) x
    dtype`` bytes, where a position caches the rows its block describes
    (``row_widths``, as ``PagedKVCache.rows`` has them: one latent row,
    say) or, by default, ``kv`` rows of ``num_heads x head_dim`` (a K
    and a V of full multi-head attention).  The pools live OUTSIDE any
    program (engine-held, donated chunk→chunk through the decode step),
    so the liveness walk never sees them — this closed form is how the
    engine reports ``resident_bytes`` and what the golden test pins
    (tests/test_memory_model.py)."""
    import numpy as np
    from ..core import datatypes
    itemsize = np.dtype(datatypes.as_numpy_dtype(dtype)).itemsize
    per_position = sum(int(w) for w in row_widths) if row_widths \
        is not None else int(kv) * int(num_heads) * int(head_dim)
    return (int(n_layers) * int(num_pages) * int(page_size)
            * per_position * int(itemsize))


def prefix_cached_bytes(num_cached_pages, page_size, num_heads=None,
                        head_dim=None, dtype='float32', n_layers=1,
                        row_widths=None):
    """Bytes of pool residency currently HELD by the decode prefix
    cache.  Cached pages live inside the engine's page pools — a page
    referenced by three streams and the trie is ONE physical page, so
    ``resident_bytes`` (the pool closed form above) already counts
    every shared page exactly once and tenancy admission charges no
    extra for sharing.  This sizes the trie-held subset only, for the
    ``prefix_cached_bytes`` stats key: how much of the pool an eviction
    sweep could reclaim at zero refs."""
    return page_pool_bytes(num_cached_pages, page_size, num_heads,
                           head_dim, dtype, n_layers=n_layers,
                           row_widths=row_widths)

# Ops with NO per-op live-bytes verdict — same data-dependent-extent
# set the cost model waives (minus 'autodiff', which this model DOES
# handle: its grad outputs are declared and its activation frontier is
# the point of the analysis).  The coverage sweep
# (tests/test_zz_op_coverage.py) asserts every registered op either
# sizes all its outputs or appears here / is structurally waived.
WAIVED_OPS = {k: v for k, v in _cm.WAIVED_OPS.items() if k != 'autodiff'}


def _saved_activations(ops, ad_idx, loss_name, remat_level):
    """Names the backward keeps live across the fwd/bwd boundary: the
    outputs of the loss-contributing forward slice, filtered by the
    program's rematerialization policy (transpiler/memory_optimize.py).
    """
    if remat_level == 'full':
        return set()  # recompute everything: nothing saved
    saved = set()
    for j in _cm._autodiff_slice(ops, ad_idx, loss_name):
        op = ops[j]
        if remat_level == 'dots' and \
                registry.cost_class(op.type) != 'mac':
            continue  # dots_saveable: only matmul-shaped outputs kept
        saved.update(op.output_arg_names)
    return saved


def analyze_memory(program, fetch_names=(), feed_specs=None,
                   donate_feeds=True, top_k=5):
    """Walk the (post-rewrite) global block and model peak residency.

    :param feed_specs: ``{name: (shape, dtype)}`` concrete feed shapes
        from the executor (optional; without them -1 batch dims count 1
        and feed bytes read 0).
    :param donate_feeds: credit each feed buffer back at its last use
        (the executor-staged, donated default).  False models
        caller-owned feed buffers resident across the whole step.
    :param top_k: how many watermark ops to name.
    :returns: report dict — ``peak_bytes`` and its components,
        ``watermark`` (top-K ops by live bytes), ``timeline`` (per-op
        ``{op_seq, live_bytes}`` sawtooth), and a ``coverage`` section
        naming every op type whose outputs could not be sized.
    """
    block = program.global_block()
    ops = block.ops
    batch = _cm._batch_binding(block, feed_specs)
    feed_specs = dict(feed_specs or {})
    env = {}
    for n, (shape, dt) in feed_specs.items():
        env[n] = (tuple(int(d) for d in shape), str(dt))

    persist_names = {v.name for v in program.list_vars()
                     if v.persistable}
    # per-name shard divisors from the sharding-propagation pass
    # (PADDLE_TPU_MESH): a var sharded K ways is resident at 1/K of
    # its bytes PER DEVICE — fsdp's whole point is that params and
    # optimizer accumulators divide, and the model must say so
    shard_plan = getattr(program, '_sharding_plan', None) or {}
    divisors = shard_plan.get('divisors') or {}

    def _div(name):
        return max(int(divisors.get(name, 1)), 1)

    unk = [0]
    persistable_bytes_unsharded = sum(
        _cm._spec_bytes((tuple(v.shape), v.dtype), unk)
        for v in program.list_vars() if v.persistable and v.shape)
    persistable_bytes = sum(
        _cm._spec_bytes((tuple(v.shape), v.dtype), unk) // _div(v.name)
        for v in program.list_vars() if v.persistable and v.shape)

    # -- size every name the walk will see ----------------------------
    sizes = {}
    unsized = set()           # var names with no resolvable bytes
    no_verdict = {}           # op type -> unsized output names
    waived = {}
    for n, spec in env.items():
        sizes[n] = _cm._spec_bytes(spec, unk)
    for op in ops:
        if op.type == 'autodiff':
            # grads are declared vars: size them from declarations
            for n in op.output_arg_names:
                s = _cm._declared_spec(block, n, batch)
                if s is not None and n not in sizes:
                    sizes[n] = _cm._spec_bytes(s, unk)
            continue
        structurally = _cm._structurally_waived(op)
        explicitly = op.type in WAIVED_OPS
        if structurally or explicitly:
            waived[op.type] = (WAIVED_OPS.get(op.type)
                               or 'control-flow/env/sub-block op')
        in_specs = _cm._resolve_in_specs(block, op, env, batch)
        out_specs = _cm._out_specs(block, op, in_specs, env, batch)
        for specs in (in_specs, out_specs):
            for slot, vals in specs.items():
                names = (op.inputs if specs is in_specs
                         else op.outputs)[slot]
                for n, s in zip(names, vals):
                    if s is None:
                        if n not in sizes:
                            unsized.add(n)
                        continue
                    sizes.setdefault(n, _cm._spec_bytes(s, unk))
        if not (structurally or explicitly):
            missing = [n for n in op.output_arg_names
                       if n not in sizes and n not in persist_names]
            if missing:
                no_verdict.setdefault(op.type, sorted(missing))

    # apply the shard divisors to every sized name (feeds and
    # batch-sharded intermediates divide like the persistables above)
    if divisors:
        for n in list(sizes):
            sizes[n] //= _div(n)

    # -- liveness intervals -------------------------------------------
    n_ops = len(ops)
    birth, last_use = {}, {}
    for i, op in enumerate(ops):
        for n in op.input_arg_names:
            last_use[n] = i
        for n in op.output_arg_names:
            birth.setdefault(n, i)
            last_use[n] = max(last_use.get(n, -1), i)
    for n in fetch_names:
        if n in birth or n in feed_specs:
            last_use[n] = n_ops - 1  # escapes the step: live to the end
    for n in feed_specs:
        birth[n] = -1  # staged before op 0
        if not donate_feeds:
            last_use[n] = n_ops - 1
        else:
            last_use.setdefault(n, -1)  # fed but never read: dies at 0

    # the backward keeps its (remat-filtered) activation frontier alive
    remat_level = getattr(program, '_remat_level', None)
    for i, op in enumerate(ops):
        if op.type != 'autodiff':
            continue
        for n in _saved_activations(ops, i, op.attrs.get('loss_name'),
                                    remat_level):
            last_use[n] = max(last_use.get(n, i), i)

    # -- the walk ------------------------------------------------------
    tracked = [n for n in birth
               if n not in persist_names and sizes.get(n)]
    births, deaths = {}, {}
    feed_bytes = 0
    live = 0
    for n in tracked:
        if birth[n] < 0:
            feed_bytes += sizes[n]
            if last_use[n] < 0:
                continue  # fed but never read: dead on arrival
            live += sizes[n]  # feeds: live before op 0
        else:
            births.setdefault(birth[n], []).append(n)
        deaths.setdefault(last_use[n], []).append(n)

    per_op = []
    peak = persistable_bytes + live
    peak_entry = None
    for i, op in enumerate(ops):
        for n in births.get(i, ()):
            live += sizes[n]
        total = persistable_bytes + live
        entry = {'index': i,
                 'op_seq': op.attrs.get('op_seq', i),
                 'type': op.type,
                 'role': _cm._role(op),
                 'live_bytes': total,
                 'intermediate_bytes': live}
        per_op.append(entry)
        if total > peak or peak_entry is None:
            peak = total
            peak_entry = entry
        for n in deaths.get(i, ()):
            live -= sizes[n]

    # collective-overlap in-flight credit: while a bucket's allreduce /
    # reduce-scatter runs concurrently with remaining backward compute,
    # its gradient payload is pinned live NEXT TO the backward frontier
    # — the serial model above would have retired it into the update.
    # Charge the largest bucket (the comm channel runs buckets
    # serially, so at most one is in flight at the peak).
    overlap_bucket_bytes = 0
    ov = (shard_plan or {}).get('overlap') if shard_plan else None
    if ov and ov.get('buckets'):
        overlap_bucket_bytes = max(
            sum(sizes.get(n, 0) for n in b['names'])
            for b in ov['buckets'])
        peak += overlap_bucket_bytes

    watermark = sorted(per_op, key=lambda e: -e['live_bytes'])[:top_k]
    sharding_block = None
    if divisors:
        sharding_block = {
            'mesh_axes': tuple(shard_plan.get('mesh_axes') or ()),
            'sharded_names': len(divisors),
            'persistable_bytes_unsharded':
                int(persistable_bytes_unsharded),
        }
    return {
        'peak_bytes': int(peak),
        'peak_intermediate_bytes': int(
            peak_entry['intermediate_bytes'] if peak_entry else 0),
        'overlap_bucket_bytes': int(overlap_bucket_bytes),
        'persistable_bytes': int(persistable_bytes),
        'feed_bytes': int(feed_bytes),
        'sharding': sharding_block,
        'remat_level': remat_level,
        'donated_feed_credit': bool(donate_feeds),
        'watermark': [dict(e) for e in watermark],
        'timeline': [{'op_seq': e['op_seq'],
                      'live_bytes': e['live_bytes']} for e in per_op],
        'coverage': {
            'ops': n_ops,
            'sized_vars': len(sizes),
            'unsized_vars': sorted(unsized)[:32],
            'no_verdict': sorted(no_verdict),
            'waived': waived,
            'unknown_dims': unk[0],
        },
    }

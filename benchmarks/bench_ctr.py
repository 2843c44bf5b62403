"""BASELINE config 5: CTR DeepFM with high-dim sparse tables —
examples/s (SelectedRows grads keep the vocab-height dense grad off the
chip).

Round 5: Criteo-class scale — 26 sparse slots x ~1e6-row tables (the
r1-r4 line ran 8 slots x 1e5, which never stressed SelectedRows where
it matters).  A second JSON line sweeps the TABLE HEIGHT at a fixed
batch and reports the compiled step's memory_analysis per height.

What the sweep shows (PERF.md "CTR at Criteo scale" has the full
bisect): MEMORY is row-sparse end-to-end — temp bytes stay ~flat vs
table bytes, no [V, K] dense gradient ever materializes — but step
TIME retains a table-height term, because XLA:TPU lowers scatter-add
as a pass over the operand (measured ~1 ns/table-row + ~28 ns/touched
-row; forward/backward are height-flat, only the optimizer scatters
scale).  That is a TensorCore scatter-lowering property (the hardware
answer to it is SparseCore), not a SelectedRows failure: a dense-grad
design would pay the same table passes PLUS dense-grad materialization
and traffic.

Round 6 attacks the scatter term: the ops/pallas/table_update.py
kernels walk only the touched rows (PADDLE_TPU_SPARSE_APPLY, default
pallas on TPU) — the headline and sweep run under the resolved mode
(labeled in their JSON), and `ctr_sparse_apply_micro` A/Bs the fused
Adagrad apply XLA-vs-Pallas across table heights: the pallas column
going height-flat where the xla column grows is the kernel doing its
job.

Round 14 removes the last wall: `--mesh fsdp=4` runs the SHARDED-TABLE
scenario (distributed/embedding_engine.py) — a table height whose
modeled resident bytes exceed PADDLE_TPU_PEAK_HBM_BYTES for one device
but fit per shard (the memory model proves both directions), the
lookup's two all-to-alls priced in the collective table, loss parity
vs the single-device run, and the hot-row cache hit rate under
zipf-skewed ids.
"""
import argparse
import json
import os
import time

import numpy as np

from common import ensure_mesh_devices, run_bench, on_tpu


def _build_fn(arch, sparse_dim, num_slots, embed_dim):
    import paddle_tpu as fluid
    from paddle_tpu import models

    def build():
        main_p, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup):
            feeds, predict, avg_cost, auc = models.ctr.build(
                arch, sparse_dim=sparse_dim, num_slots=num_slots,
                embed_dim=embed_dim)
            fluid.optimizer.AdagradOptimizer(0.01).minimize(avg_cost)
        assert any(op.type == 'sparse_grad_assemble'
                   for op in main_p.global_block().ops)
        return main_p, startup, avg_cost
    return build


def _feed_fn(batch, sparse_dim, num_slots):
    from paddle_tpu.models.ctr import DENSE_DIM
    rng = np.random.default_rng(0)

    def feed():
        ln = np.full((batch,), 1, np.int32)
        out = {'dense': rng.normal(size=(batch, DENSE_DIM)).astype(
            np.float32),
            'label': rng.integers(0, 2, (batch, 1)).astype(np.int32)}
        for i in range(num_slots):
            out['sparse_%d' % i] = (rng.integers(
                0, sparse_dim, (batch, 1, 1)).astype(np.int32), ln)
        return out
    return feed


def _sparse_apply_micro(tpu):
    """Scatter-apply micro: the fused sparse-Adagrad update (param +
    moment) through BOTH lowerings, as a K-step donated-carry scan so
    buffer aliasing matches the real train step.  Emits one JSON line
    with the height sweep; `pallas_ms` staying flat from 1e5 to 1e7
    rows while `xla_ms` grows is the acceptance shape."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.selected_rows import merge_duplicate_rows
    from paddle_tpu.ops.pallas.table_update import sparse_apply_adagrad

    heights = (100003, 1000003, 10000019) if tpu else (1009, 4001)
    k = 131072 if tpu else 256
    d = 8
    steps = 50 if tpu else 2
    lr = jnp.float32(0.01)
    eps = 1e-6
    rng = np.random.default_rng(5)

    def xla_apply(p, mom, rows, vals):
        # ops/optim_ops.py _adagrad sparse branch, verbatim
        mrows, g, valid = merge_duplicate_rows(rows, vals)
        vmask = valid[:, None]
        mom_row = mom[mrows] + jnp.square(g)
        mom_new = mom.at[mrows].add(jnp.where(vmask, jnp.square(g), 0.0))
        step = -lr * g / (jnp.sqrt(mom_row) + eps)
        return p.at[mrows].add(jnp.where(vmask, step, 0.0)), mom_new

    def pallas_apply(p, mom, rows, vals):
        return sparse_apply_adagrad(p, mom, rows, vals, lr, eps)

    def chain(apply, rows, vals):
        def fn(p, mom):
            def body(c, _):
                p, mom = c
                return apply(p, mom, rows, vals), None
            return jax.lax.scan(body, (p, mom), None, length=steps)[0]
        return jax.jit(fn, donate_argnums=(0, 1))

    sweep = []
    for h in heights:
        rows = jnp.asarray(rng.integers(0, h, size=(k,)).astype(np.int32))
        vals = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        row = {'table_rows': h}
        for name, apply in (('xla', xla_apply), ('pallas', pallas_apply)):
            fn = chain(apply, rows, vals)
            p = jnp.asarray(rng.normal(size=(h, d)).astype(np.float32))
            mom = jnp.abs(jnp.asarray(
                rng.normal(size=(h, d)).astype(np.float32)))
            p, mom = jax.block_until_ready(fn(p, mom))  # compile + warm
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                p, mom = jax.block_until_ready(fn(p, mom))
                ts.append((time.perf_counter() - t0) / steps * 1e3)
            row['%s_ms' % name] = round(float(np.median(ts)), 3)
        sweep.append(row)
    print(json.dumps({
        'metric': 'ctr_sparse_apply_micro',
        'value': sweep[-1]['pallas_ms'],
        'sweep': sweep,
        'note': 'fused sparse-Adagrad apply (param+moment), %d touched '
                'rows x %d cols, %d-step donated scan; pallas flat '
                'across heights = O(touched rows), xla grows = the '
                'scatter table pass' % (k, d, steps)}))


def _sharded_table_scenario(mesh_specs, tpu):
    """--mesh mode: the sharded-embedding acceptance scenario — sweep a
    table height whose MODELED resident bytes exceed the single-device
    PADDLE_TPU_PEAK_HBM_BYTES budget but fit per shard (the memory
    model proves it), with the lookup's two all-to-alls priced in the
    collective table, loss parity vs the single-device run, and the
    hot-row cache hit rate under frequency-skewed (zipf) Criteo-style
    ids.  One JSON line per table height plus one for the cache."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.distributed import mesh_flag, embedding_engine as ee

    # the engine's per-shard apply rides the Pallas row-walk (interpret
    # mode on CPU) — the xla scatter path never routes per shard
    os.environ.setdefault('PADDLE_TPU_SPARSE_APPLY', 'pallas')
    if tpu:
        budget = int(os.environ.get('PADDLE_TPU_PEAK_HBM_BYTES')
                     or 16 * 2**30)
        heights, slots, embed_dim, batch, steps = \
            (120_000_000,), 26, 16, 8192, 8
    else:
        # CPU dryrun: a deliberately small modeled budget so the
        # "table cannot fit one device" shape is provable on the smoke
        # box — 2 slots x (8+1) cols x f32 x 262144 rows ~ 18.9 MB
        # vs a 16 MiB budget; fsdp=4 holds ~4.7 MB per device
        budget = 16 * 2**20
        heights, slots, embed_dim, batch, steps = \
            (262_144,), 2, 8, 64, 3
    os.environ['PADDLE_TPU_PEAK_HBM_BYTES'] = str(budget)

    saved = os.environ.get('PADDLE_TPU_MESH')
    try:
        for dim in heights:
            rows, loss_ref = [], None
            feeds = [_feed_fn(batch, dim, slots)()
                     for _ in range(steps)]
            for spec in ['off'] + [s for s in mesh_specs
                                   if s not in ('', 'off', '1')]:
                off = spec == 'off'
                if off:
                    os.environ.pop('PADDLE_TPU_MESH', None)
                else:
                    os.environ['PADDLE_TPU_MESH'] = spec
                devices = 1 if off else mesh_flag.spmd_device_count(
                    mesh_flag.mesh_axes_from_flag(spec))
                main_p, startup, loss = _build_fn(
                    'deepfm', dim, slots, embed_dim)()
                main_p.random_seed = startup.random_seed = 1234
                scope = fluid.core.Scope()
                exe = fluid.Executor(
                    fluid.TPUPlace(0) if tpu else fluid.CPUPlace())
                exe.run(startup, scope=scope)
                out = exe.run_steps(main_p, feed=feeds,
                                    fetch_list=[loss], scope=scope,
                                    return_numpy=False)
                jax.block_until_ready(out[0])  # compile + warm
                t0 = time.perf_counter()
                out = exe.run_steps(main_p, feed=feeds,
                                    fetch_list=[loss], scope=scope,
                                    return_numpy=False)
                losses = np.asarray(out[0]).reshape(-1)
                wall = time.perf_counter() - t0
                rep = exe.last_step_report
                g = exe.last_graph_opt_report
                mem = g['cost']['memory']
                coll = g['cost'].get('collectives') or {}
                a2a = sum(i['ici_bytes']
                          for i in (coll.get('items') or ())
                          if i['kind'] == 'all_to_all')
                step_ms = wall / steps * 1e3
                row = {
                    'mesh': spec, 'devices': devices,
                    'step_ms': round(step_ms, 3),
                    'loss_last': round(float(losses[-1]), 6),
                    'modeled_resident_bytes_per_device':
                        int(mem['persistable_bytes']),
                    'hbm_budget_bytes': budget,
                    'headroom_ratio': round(
                        mem['persistable_bytes'] / budget, 3),
                    'alltoall_ici_bytes_per_step': int(a2a),
                    'alltoall_modeled_bytes_per_s': int(
                        a2a / max(step_ms / 1e3, 1e-9)),
                }
                if off:
                    loss_ref = losses
                    assert row['headroom_ratio'] > 1.0, \
                        "pick a height past the budget: %r" % row
                else:
                    assert row['headroom_ratio'] < 1.0, \
                        "per-shard residency must fit: %r" % row
                    assert a2a > 0, "lookup all-to-alls not priced"
                    # documented tolerance: GSPMD reduction order is
                    # ulp-noisy and amplifies over steps (PERF.md r12)
                    row['loss_max_abs_diff_vs_off'] = float(
                        np.max(np.abs(losses - loss_ref)))
                    assert np.allclose(losses, loss_ref, rtol=1e-3,
                                       atol=1e-4), row
                rows.append(row)
                exe.close()
                del scope
            print(json.dumps({
                'metric': 'ctr_sharded_table_step_ms',
                'value': rows[-1]['step_ms'],
                'table_rows': dim, 'slots': slots,
                'embed_dim': embed_dim, 'batch': batch,
                'sweep': rows,
                'note': 'row-sharded tables (PADDLE_TPU_EMBED_SHARD): '
                        'headroom_ratio>1 single-device vs <1 per '
                        'shard is the memory-model proof; all-to-all '
                        'bytes are the priced lookup collectives'}))

        # hot-row cache under zipf-skewed ids (the Criteo shape)
        dim = heights[0]
        ways = 4
        rng = np.random.default_rng(7)
        import jax.numpy as jnp
        w = jnp.asarray(rng.normal(size=(min(dim, 1 << 18),
                                         embed_dim)).astype(np.float32))
        h = int(w.shape[0])
        cache = ee.HotRowCache(1024, h, embed_dim, ways=ways)
        def zipf_ids(n):
            z = rng.zipf(1.3, size=n)
            return jnp.asarray(((z - 1) % h).astype(np.int32))
        for _ in range(4):
            cache.observe(zipf_ids(batch * slots))  # warm the ranking
        cache.admit(w)
        parity = True
        for _ in range(8):
            ids = zipf_ids(batch * slots)
            got = cache.lookup(w, ids)
            parity &= bool(np.array_equal(
                np.asarray(got), np.asarray(jnp.take(w, ids, axis=0))))
        stats = cache.stats()
        print(json.dumps({
            'metric': 'ctr_embed_cache_hit_rate',
            'value': round(stats['hit_rate'], 4),
            'stats': stats, 'parity': parity,
            'note': 'HotRowCache(1024) under zipf(1.3) ids over %d '
                    'rows: hits are masked out of the all-to-all '
                    'route, so hit_rate is the fraction of lookup '
                    'traffic that never crosses ICI; parity=True is '
                    'the bitwise cached==uncached check' % h}))
        assert stats['hit_rate'] > 0.5 and parity
    finally:
        if saved is None:
            os.environ.pop('PADDLE_TPU_MESH', None)
        else:
            os.environ['PADDLE_TPU_MESH'] = saved


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--mesh', action='append', default=None,
                    metavar='SPEC',
                    help="sharded-embedding-table scenario: one sweep "
                         "row per PADDLE_TPU_MESH spec (repeatable, "
                         "e.g. --mesh fsdp=4); forces virtual host "
                         "devices on CPU")
    args = ap.parse_args(argv)
    if args.mesh:
        # must precede the first jax import (device count freezes)
        ensure_mesh_devices(args.mesh)

    from paddle_tpu.models.ctr import (CRITEO_NUM_SLOTS,
                                       CRITEO_SPARSE_DIM)
    from paddle_tpu.ops.pallas.table_update import sparse_apply_mode

    tpu = on_tpu()
    if args.mesh:
        _sharded_table_scenario(args.mesh, tpu)
        return
    if tpu:
        batch, sparse_dim, num_slots = 32768, CRITEO_SPARSE_DIM, \
            CRITEO_NUM_SLOTS
        steps = 100
    else:
        batch, sparse_dim, num_slots = 64, 1003, 4
        steps = 3

    # headline: Criteo-class DeepFM, K=100 steps per chain
    run_bench('ctr_deepfm_examples_per_sec', batch,
              _build_fn('deepfm', sparse_dim, num_slots, 16),
              _feed_fn(batch, sparse_dim, num_slots), steps=steps,
              note='batch=%d slots=%d dim=%d (criteo-class) '
                   'sparse_apply=%s'
                   % (batch, num_slots, sparse_dim, sparse_apply_mode()),
              compile_stats=True,
              step_breakdown=True)

    # scatter-apply micro: XLA vs Pallas across table heights
    _sparse_apply_micro(tpu)

    # table-height sweep: same batch/slots/embed, tables 1e5 -> 1e7;
    # touched rows per step constant (= batch x slots).  step_ms carries
    # the XLA scatter table pass; mem_temp_over_tables staying ~flat is
    # the no-dense-grad proof.
    import jax
    import paddle_tpu as fluid

    sweep_batch = 16384 if tpu else 64
    sweep_slots = 8 if tpu else 2
    dims = ((100003, 1000003, 10000019) if tpu else (101, 1009))
    rows = []
    for dim in dims:
        build = _build_fn('deepfm', dim, sweep_slots, 8)
        feed = _feed_fn(sweep_batch, dim, sweep_slots)
        main_p, startup, loss = build()
        place = fluid.TPUPlace(0) if tpu else fluid.CPUPlace()
        exe = fluid.Executor(place)
        # a fresh scope per height: the big tables free when it drops
        scope = fluid.core.Scope()
        exe.run(startup, scope=scope)
        # compiled-step memory: temp vs table bytes (dense grads would
        # put #tables extra V-passes in temp)
        fn_c, args_c = exe.compile(main_p, feed=_feed_fn(
            sweep_batch, dim, sweep_slots)(), fetch_list=[loss],
            scope=scope)
        ma = fn_c.lower(*args_c).compile().memory_analysis()
        table_bytes = sweep_slots * dim * (8 + 1) * 4  # embeds + wide
        mem_ratio = ma.temp_size_in_bytes / table_bytes
        f = {k: (tuple(v) if isinstance(v, tuple)
                 else jax.device_put(v, place.jax_device()))
             for k, v in feed().items()}
        k = 50 if tpu else 2
        out = exe.run_steps(main_p, feed=f, fetch_list=[loss],
                            repeat=k, return_numpy=False, scope=scope)
        np.asarray(out[0])  # compile + warm
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = exe.run_steps(main_p, feed=f, fetch_list=[loss],
                                repeat=k, return_numpy=False,
                                scope=scope)
            np.asarray(out[0])
            ts.append((time.perf_counter() - t0) / k * 1e3)
        rows.append({'table_rows': dim,
                     'step_ms': round(float(np.median(ts)), 3),
                     'temp_over_table_bytes': round(mem_ratio, 3)})
        del scope
    print(json.dumps({
        'metric': 'ctr_table_height_sweep_step_ms',
        'value': rows[-1]['step_ms'],
        'sweep': rows,
        'note': 'batch=%d slots=%d embed=8, %d touched rows/step, '
                'sparse_apply=%s; temp bytes ~independent of table '
                'height (the ratio FALLS as tables grow) = no dense '
                '[V,K] grad materializes; under sparse_apply=xla the '
                'step_ms growth is the XLA:TPU scatter table pass, '
                'under pallas it should flatten (PERF.md "Pallas '
                'row-sparse table update")'
                % (sweep_batch, sweep_slots, sweep_batch * sweep_slots,
                   sparse_apply_mode())}))


if __name__ == '__main__':
    main()

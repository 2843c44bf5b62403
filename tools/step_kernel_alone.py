"""The decode step's two attention kernels alone on the chip, one layer,
at the shapes and loads of the benchmark's cells: this tree's kernel
beside another tree's, bit for bit and timed.

    python3 tools/step_kernel_alone.py --against _co/parent [--cases opt,dots]
    python3 tools/step_kernel_alone.py --rehearse      toy shapes, the CPU

One ``STEP_KERNEL`` line a case and a tree: ``slots`` grid steps of which
``live`` hold a context (the others ``--idle-context`` positions of the
trash page), ``positions`` cached positions the live slots read in all,
``bytes_us`` the least time of those positions' bytes at the chip's HBM
peak, ``kernel_us`` the kernel's device time a call and ``call_us`` the
whole jitted call's (the profiler's trace, the median of ``--calls``
calls; the two trees' calls alternate in blocks), ``same_bits`` whether
every output row is the other tree's to the bit.  The cases are those of
PERF.md section 6's table (PR 61, PR 64), so that a later kernel PR is
sized by the same yardstick: what an idle slot costs is (``kernel_us`` -
the same case's with every slot live, or ``bytes_us``) / (``slots`` -
``live``).
"""
import argparse
import glob
import importlib.util
import os
import shutil
import statistics
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
KERNELS = 'paddle_tpu/ops/pallas/paged_attention.py'

# name -> the cell's shapes (its traffic's ``engine`` and its config) and
# a load: ``live`` of ``slots`` slots hold ``positions`` positions in all,
# each between ``lo`` and ``hi``.  ``heads`` query heads over ``kv_heads``
# K/V heads of ``head_dim``; ``table`` page-table columns a slot; a latent
# case has ONE row of ``width`` lanes a position and reads ``value`` of them
CASES = {
    'opt': dict(slots=16, live=2, positions=500, lo=32, hi=704, heads=32,
                kv_heads=32, head_dim=64, page=16, pages=768, table=128,
                dtype='float32'),
    'ouro': dict(slots=16, live=10, positions=7996, lo=64, hi=1536, heads=16,
                 kv_heads=16, head_dim=128, page=16, pages=1536, table=96,
                 dtype='bfloat16'),
    'olmoe': dict(slots=32, live=6, positions=3166, lo=32, hi=1024, heads=16,
                  kv_heads=16, head_dim=128, page=16, pages=2048, table=64,
                  dtype='bfloat16'),
    # PR 28's two loads of the OLMoE shapes: ten slots running, and all
    'pr28_ten': dict(slots=32, live=10, positions=5276, lo=32, hi=1024,
                     heads=16, kv_heads=16, head_dim=128, page=16,
                     pages=2048, table=64, dtype='bfloat16'),
    'pr28_all': dict(slots=32, live=32, positions=32768, lo=1024, hi=1024,
                     heads=16, kv_heads=16, head_dim=128, page=16,
                     pages=2048, table=64, dtype='bfloat16'),
    'dots': dict(slots=64, live=37, positions=54004, lo=512, hi=3840,
                 heads=128, width=640, value=512, page=16, pages=16384,
                 table=256, dtype='bfloat16'),
    'laguna_full': dict(slots=32, live=10, positions=82500, lo=1024,
                        hi=17152, heads=48, kv_heads=8, head_dim=128,
                        page=16, pages=34816, table=1088, dtype='bfloat16'),
    # the same streams through a window layer: rings of 65 pages
    'laguna_window': dict(slots=32, live=10, positions=82500, lo=1024,
                          hi=17152, heads=72, kv_heads=8, head_dim=128,
                          page=16, pages=2080, table=65, window=512,
                          dtype='bfloat16'),
    'jamba': dict(slots=64, live=22, positions=197990, lo=1024, hi=17152,
                  heads=20, kv_heads=1, head_dim=128, page=128, pages=8704,
                  table=136, dtype='bfloat16'),
}
# the toy load of ``--rehearse``: the cases' groupings and dtypes, pools
# and contexts the interpreter walks in seconds
TOY = dict(positions=None, lo=1, hi=40, page=16, pages=160, table=4)


def load_kernels(tree, name):
    """The kernels' module of the tree at ``tree`` as module ``name``
    (it imports nothing of its package)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(tree, KERNELS))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def contexts(rng, live, total, lo, hi):
    """``live`` lengths in [lo, hi] that sum to ``total`` (any, if None)."""
    ctx = rng.integers(lo, hi + 1, live)
    while total is not None and ctx.sum() != total:
        i = rng.integers(live)
        ctx[i] = np.clip(ctx[i] + total - ctx.sum(), lo, hi)
    return ctx


def build(name, case, seed, idle_context):
    """(arguments of the kernel's call, its keywords, whether it is the
    latent kernel, positions read, their bytes)."""
    import jax
    import jax.numpy as jnp
    c = case
    rng = np.random.default_rng([seed, sum(name.encode())])
    slots, live, page, table = c['slots'], c['live'], c['page'], c['table']
    window, dtype = c.get('window'), jnp.dtype(c['dtype'])
    ctx = np.full(slots, idle_context, np.int64)
    # the running streams sit where they were admitted: anywhere
    running = np.sort(rng.permutation(slots)[:live])
    ctx[running] = contexts(
        rng, live, c['positions'], c['lo'], min(c['hi'], table * page)
        if window is None else c['hi'])
    # a live slot's pages distinct and scattered over the pool; every
    # other column, and an idle slot's row, the trash page (the last)
    pt = np.full((slots, table), c['pages'], np.int32)
    free = rng.permutation(c['pages'])
    for s in running:
        n = min(-(-int(ctx[s]) // page), table)
        pt[s, :n], free = free[:n], free[n:]
    latent = 'width' in c
    row = c['width'] if latent else c['kv_heads'] * c['head_dim']
    read = int(np.minimum(ctx[running], window or ctx.max()).sum())
    nbytes = read * row * dtype.itemsize * (1 if latent else 2)

    keys = iter(jax.random.split(jax.random.key(seed), 2))

    def pool():
        return (jax.random.normal(next(keys), (c['pages'] + 1, page, row),
                                  jnp.float32) * 0.5).astype(dtype)
    q = jnp.asarray(rng.standard_normal(
        (slots, c['heads'], row if latent else c['head_dim']), np.float32),
        dtype)
    tail = (jnp.asarray(pt), jnp.asarray(ctx, jnp.int32))
    if latent:
        return (q, pool()) + tail, dict(scale=row ** -0.5,
                                        value_dim=c['value']), True, \
            read, nbytes
    kw = {} if window is None else {'window': window}
    return (q, pool(), pool()) + tail, kw, False, read, nbytes


def device_us(trace_dir, programs):
    """{program: (kernel us a call, whole call us)} from the profiler's
    trace: the medians over the program's executions."""
    from chipbench import xplane
    paths = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                      recursive=True)
    tr = xplane.load(max(paths, key=os.path.getmtime))
    plane = xplane.device_planes(tr)[0]
    out = {}
    for prog in programs:
        calls = sorted((s, s + d) for n, s, d
                       in xplane.line_events(plane, xplane.MODULES_LINE)
                       if n.startswith('jit_' + prog))
        kernel = [0] * len(calls)
        for n, s, d in xplane.line_events(plane, xplane.OPS_LINE):
            if 'paged_attention_live_pages' in n:
                for i, (lo, hi) in enumerate(calls):
                    if lo <= s < hi:
                        kernel[i] += d
        out[prog] = (statistics.median(kernel) / 1e3,
                     statistics.median(hi - lo for lo, hi in calls) / 1e3)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--against', default=None,
                    help='a second tree (a checkout\'s root) to run beside')
    ap.add_argument('--cases', default=','.join(CASES))
    ap.add_argument('--calls', type=int, default=40)
    ap.add_argument('--seed', type=int, default=64)
    ap.add_argument('--idle-context', type=int, default=0,
                    help='the context an idle slot is given: 0, or 1 as '
                    'the engine gave its idle slots until PR 64 (one '
                    'position of the trash page, which such a slot reads)')
    ap.add_argument('--rehearse', action='store_true',
                    help='toy loads, interpreted on the CPU: no times')
    args = ap.parse_args()
    if args.rehearse:
        os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    if not args.rehearse and jax.default_backend() != 'tpu':
        sys.exit('no TPU here: the times are the chip\'s (--rehearse '
                 'walks the cases on the CPU)')
    from chipbench import peaks
    dev = jax.devices()[0]
    print('DEVICE', dev.platform, dev.device_kind, flush=True)
    # (a rehearsal's ``bytes_us`` is arithmetic on a v5e's published peak)
    hbm_bytes_per_us = peaks.lookup(
        'TPU v5e' if args.rehearse else dev.device_kind
    )['hbm_bytes_per_s'] / 1e6
    trees = [('tree', load_kernels(ROOT, 'step_kernels_0'))]
    if args.against:
        trees.append((args.against,
                      load_kernels(args.against, 'step_kernels_1')))
    for name in args.cases.split(','):
        case = dict(CASES[name])
        if args.rehearse:
            case.update(TOY, **({'window': 20} if 'window' in case else {}))
        call_args, kw, latent, read, nbytes = build(
            name, case, args.seed, args.idle_context)
        if args.rehearse:
            kw = dict(kw, interpret=True)
        fns = {}
        for i, (_label, mod) in enumerate(trees):
            kernel = mod.latent_paged_attention if latent \
                else mod.paged_attention

            def call(*a, kernel=kernel):
                return kernel(*a, **kw)
            call.__name__ = 'step_kernel_%d' % i
            fns[call.__name__] = jax.jit(call)
        outs = {p: np.asarray(f(*call_args).astype('float32'))
                for p, f in fns.items()}        # compiled, and compared
        us = {}
        if not args.rehearse:
            # the device alone, as the benchmark traces (harness.traced)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = opts.host_tracer_level = 0
            trace_dir = tempfile.mkdtemp(prefix='step_kernel_')
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                for p in list(fns) + list(fns)[::-1]:       # A B B A
                    for _ in range(args.calls // 2):
                        out = fns[p](*call_args)
                    out.block_until_ready()
            finally:
                jax.profiler.stop_trace()
            us = device_us(trace_dir, fns)
            shutil.rmtree(trace_dir, ignore_errors=True)
        first = outs['step_kernel_0']
        for (label, _mod), p in zip(trees, fns):
            same = 'alone' if len(trees) < 2 else bool(np.array_equal(
                outs[p].view(np.uint32), first.view(np.uint32)))
            times = 'kernel_us=%.1f call_us=%.1f' % us[p] if us else \
                'kernel_us=not_measured call_us=not_measured'
            print('STEP_KERNEL case=%s kernel=%s slots=%d live=%d idle_ctx=%d '
                  'positions=%d bytes_us=%.1f same_bits=%s finite=%s '
                  'empty_rows_zero=%s %s' % (
                      name, label, case['slots'], case['live'],
                      args.idle_context, read, nbytes / hbm_bytes_per_us,
                      same, bool(np.isfinite(outs[p]).all()),
                      not outs[p][np.asarray(call_args[-1]) == 0].any(),
                      times), flush=True)


if __name__ == '__main__':
    main()

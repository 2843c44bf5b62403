#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

One process, one chip, the entry points a user calls, at the full width
of the models the benches run (depth cut, weights random from a seed):

  train    ResNet-50 b64 bf16 NHWC + momentum through Executor.run (numpy
           feeds, then runtime.FeedPipeline) and one run_steps chain
  serve    the decode flagship (L=6 D=512 H=8 V=30000 T=512) through
           DecodeEngine.warmup and a DecodeServer answering 8 requests,
           paged logits checked against the full-context forward
  kernels  every Pallas family compiled by Mosaic at its bench shape and
           compared with its jax.numpy reference
  trace    three train steps under fluid.profiler; the xplane must hold
           a device plane with events (it is read, then removed)

    python chip_smoke.py                  # on a machine with one TPU chip
    python chip_smoke.py --mesh dp=4 --mesh fsdp=4     # four chips
    python chip_smoke.py --rehearse       # toy sizes, CPU, interpreted

It exits non-zero, before any model is built, when jax finds no TPU
(--rehearse is never chosen for the caller), and non-zero when any phase
fails.  Every phase prints one JSON line stamped with the device; the
last line of stdout is {"ok": true, "device": {...}}.  Lines are also
appended to <out>/chip_smoke.jsonl.  It prints walls and bytes as
information under names that say what they are — no utilisation figure,
and nothing here is a benchmark.
"""
import argparse
import contextlib
import functools
import glob
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

SEED = 21


# ---------------------------------------------------------------------------
# sizes: the chip runs the bench widths, the rehearsal a toy of each
# ---------------------------------------------------------------------------

CHIP = dict(
    # bench.py's headline model
    train=dict(depth=50, hw=224, classes=1000, batch=64, chain=8),
    mesh_batch=256,
    # benchmarks/bench_serving.py decode flagship on a TPU
    serve=dict(L=6, D=512, H=8, V=30000, T=512, page=16, streams=16,
               bucket=256, prompt=(4, 200), new=(8, 32)),
    flash=dict(B=2, T=8192, H=8, D=64),         # bench_attention.py
    # one layer of the olmoe-1b-7b_serve_chat32_chunked decode step
    paged=dict(S=32, H=16, D=128, P=16, MPP=64, N=2049),
    # query heads in groups over 8 K/V heads: 48 over a stream's whole
    # table, 72 over a ring that holds a window of 512 and a chunk of 512
    grouped=dict(S=32, HKV=8, D=128, P=16, N=12289, C=512,
                 kinds=dict(full=dict(H=48, MPP=1088, window=None),
                            window=dict(H=72, MPP=65, window=512))),
    latent=dict(S=64, H=128, W=640, C=512, P=16, MPP=256, N=4097),
    # 20 query heads over ONE K/V head (the two attention layers of
    # ai21-jamba2-3b_serve_longdoc64_chunked), and a chunk's selective
    # scan in one of its 26 state-space layers
    mqa=dict(S=64, H=20, HKV=1, D=128, P=16, MPP=1088, N=8705, C=512,
             window=None),
    ssm=dict(T=512, DC=5120, NS=16, valid=301),
    lstm=dict(T=128, B=256, H=256),             # bench_lstm_lm.py
    gru=dict(T=64, B=512, H=512),               # bench_seq2seq.py
    sparse=dict(H=1000003, D=16, K=32768),      # bench_ctr.py
)
TOY = dict(
    train=dict(depth=18, hw=32, classes=10, batch=8, chain=4),
    mesh_batch=8,
    serve=dict(L=2, D=64, H=4, V=200, T=64, page=8, streams=4,
               bucket=32, prompt=(4, 20), new=(3, 6)),
    flash=dict(B=1, T=256, H=2, D=64),
    paged=dict(S=4, H=2, D=128, P=16, MPP=10, N=25),
    grouped=dict(S=4, HKV=8, D=128, P=16, N=49, C=32,
                 kinds=dict(full=dict(H=16, MPP=10, window=None),
                            window=dict(H=24, MPP=5, window=40))),
    latent=dict(S=4, H=4, W=128, C=96, P=16, MPP=10, N=25),
    mqa=dict(S=4, H=20, HKV=1, D=128, P=16, MPP=10, N=49, C=32,
             window=None),
    ssm=dict(T=32, DC=256, NS=16, valid=19),
    lstm=dict(T=6, B=8, H=128),
    gru=dict(T=6, B=8, H=128),
    sparse=dict(H=1003, D=16, K=64),
)


class Smoke(object):
    """What the phases share: sizes, the output directory, the device
    stamp, and the one ResNet rig `train` and `trace` both step."""

    def __init__(self, rehearse, out_dir):
        import jax
        self.rehearse = rehearse
        self.cfg = TOY if rehearse else CHIP
        self.out_dir = out_dir
        d = jax.devices()[0]
        self.device = {'platform': d.platform, 'kind': d.device_kind,
                       'count': len(jax.devices())}
        self._rig = None

    def emit(self, obj):
        obj = dict(obj, platform=self.device['platform'],
                   device_kind=self.device['kind'])
        if self.rehearse:
            obj['rehearsal'] = True
        line = json.dumps(obj)
        print(line, flush=True)
        with open(os.path.join(self.out_dir, 'chip_smoke.jsonl'),
                  'a') as f:
            f.write(line + '\n')

    def place(self):
        import paddle_tpu as fluid
        return fluid.CPUPlace() if self.rehearse else fluid.TPUPlace(0)

    def rig(self):
        if self._rig is None:
            self._rig = TrainRig(self, self.cfg['train']['batch'])
        return self._rig


def run_phases(phases, emit):
    """Run each (name, fn) in turn; fn returns the phase's info dict.
    Any exception fails that phase — its traceback goes to stderr, its
    line says ok=false — and the rest still run, so one chip call shows
    everything that is broken.  Returns the names that failed."""
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            info = fn() or {}
        except Exception as e:
            traceback.print_exc()
            failed.append(name)
            emit({'phase': name, 'ok': False, 'error': repr(e)[:2000],
                  'phase_wall_s': round(time.perf_counter() - t0, 2)})
        else:
            emit({'phase': name, 'ok': True, **info,
                  'phase_wall_s': round(time.perf_counter() - t0, 2)})
    return failed


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def rel_err(got, want):
    """max|got - want| over the reference's own scale: one number per
    tensor that reads the same for logits, gradients and tables."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape,
          'shape %s != %s' % (got.shape, want.shape))
    check(np.isfinite(got).all(), 'non-finite values')
    return float(np.max(np.abs(got - want))
                 / (np.max(np.abs(want)) + 1e-30))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

class TrainRig(object):
    """ResNet as bench.py builds it, in its own scope, on the smoke's
    place — or over PADDLE_TPU_MESH when `mesh` names one."""

    def __init__(self, smoke, batch, mesh=None):
        import paddle_tpu as fluid
        from paddle_tpu.models import resnet
        c = smoke.cfg['train']
        self.batch = batch
        self.shape = (c['hw'], c['hw'], 3)
        self.classes = c['classes']
        self.mesh = mesh
        self.main, self.startup = fluid.Program(), fluid.Program()
        self.main.random_seed = self.startup.random_seed = SEED
        with fluid.program_guard(self.main, self.startup):
            _img, _label, _pred, self.loss, _acc = resnet.build_imagenet(
                depth=c['depth'], num_classes=c['classes'],
                image_shape=self.shape, dtype='bfloat16', layout='NHWC')
            # bench.py steps at 0.1; a smoke that must see the loss FALL
            # on eight repeats of one random batch, from a cold start
            # with no warm-up schedule, steps smaller
            fluid.optimizer.MomentumOptimizer(
                learning_rate=0.01, momentum=0.9).minimize(self.loss)
        self.scope = fluid.Scope()
        with self._env():
            self.exe = fluid.Executor(smoke.place())
            t0 = time.perf_counter()
            self.exe.run(self.startup, scope=self.scope)
            self.startup_wall_s = time.perf_counter() - t0

    @contextlib.contextmanager
    def _env(self):
        """PADDLE_TPU_MESH for the rig's calls only — the flag is re-read
        on every executor call, and the other rigs run without it."""
        if self.mesh is None:
            yield
            return
        old = os.environ.get('PADDLE_TPU_MESH')
        os.environ['PADDLE_TPU_MESH'] = self.mesh
        try:
            yield
        finally:
            if old is None:
                del os.environ['PADDLE_TPU_MESH']
            else:
                os.environ['PADDLE_TPU_MESH'] = old

    def feed(self, step):
        rng = np.random.default_rng(SEED + step)
        return {'img': rng.normal(size=(self.batch,) + self.shape)
                .astype(np.float32),
                'label': rng.integers(0, self.classes,
                                      size=(self.batch, 1)).astype(np.int32)}

    def step(self, feed):
        """One synced Executor.run: (loss, wall seconds)."""
        t0 = time.perf_counter()
        with self._env():
            out, = self.exe.run(self.main, feed=feed,
                                fetch_list=[self.loss], scope=self.scope)
        loss = float(np.asarray(out).ravel()[0])
        check(np.isfinite(loss), 'loss went non-finite: %r' % loss)
        return loss, time.perf_counter() - t0

    def passes_ok(self):
        passes = self.exe.last_graph_opt_report['passes']
        bad = [(e['name'], e['status']) for e in passes
               if e['status'] != 'ok']
        check(not bad, 'pass pipeline entries not ok: %r' % bad)
        return [e['name'] for e in passes]


def phase_train(smoke):
    import jax
    from paddle_tpu.runtime import FeedPipeline, native
    rig = smoke.rig()
    info = {'config': 'resnet%d %dx%d b%d bf16 NHWC momentum' % (
        smoke.cfg['train']['depth'], rig.shape[0], rig.shape[1],
        rig.batch), 'startup_wall_s': round(rig.startup_wall_s, 2)}

    # three run() steps fed fresh numpy batches; the first one compiles
    losses, walls = zip(*[rig.step(rig.feed(i)) for i in range(3)])
    info['compile_plus_first_step_wall_s'] = round(walls[0], 2)
    info['run_numpy_feed_synced_step_wall_s'] = round(min(walls[1:]), 4)
    info['passes'] = rig.passes_ok()

    # three more through the README's feed path (a second plan: these
    # feeds arrive as device arrays the executor must not donate)
    info['native_runtime'] = native.available()

    def fill(views, step):
        if step >= 3:
            return False
        for n, v in rig.feed(3 + step).items():
            views[n][:] = v

    pipe = FeedPipeline(
        {'img': ((rig.batch,) + rig.shape, np.float32),
         'label': ((rig.batch, 1), np.int32)}, fill, depth=3,
        device=smoke.place().jax_device())
    try:
        p_losses, p_walls = zip(*[rig.step(f) for f in pipe])
    finally:
        pipe.close()
    check(len(p_losses) == 3, 'FeedPipeline yielded %d batches, not 3'
          % len(p_losses))
    info['run_pipeline_feed_synced_step_wall_s'] = round(
        min(p_walls[1:]), 4)

    # one chain: K steps on one repeated batch as a single computation
    k = smoke.cfg['train']['chain']
    batch = rig.feed(0)
    chain_walls = []
    for _ in range(2):  # the first call compiles the scan
        t0 = time.perf_counter()
        out, = rig.exe.run_steps(rig.main, feed=batch,
                                 fetch_list=[rig.loss], repeat=k,
                                 scope=rig.scope)
        chain_walls.append(time.perf_counter() - t0)
        chain = np.asarray(out).ravel()
        check(chain.shape == (k,) and np.isfinite(chain).all(),
              'run_steps losses %r' % (chain,))
    info['chain_compile_plus_first_call_wall_s'] = round(chain_walls[0], 2)
    info['chain_synced_wall_per_step_s'] = round(chain_walls[1] / k, 4)
    check(chain[-1] < chain[0],
          'loss did not fall over %d steps on one repeated batch: %r'
          % (k, chain.tolist()))
    info['losses'] = {'run': [round(x, 4) for x in losses + p_losses],
                      'chain_last_call': [round(float(x), 4)
                                          for x in chain]}
    stats = jax.devices()[0].memory_stats()
    info['peak_bytes_in_use'] = (stats or {}).get('peak_bytes_in_use')
    check(smoke.rehearse or info['peak_bytes_in_use'],
          'the TPU reports no memory_stats()')
    return info


# ---------------------------------------------------------------------------
# train over a mesh (run by the builder on the four-chip host)
# ---------------------------------------------------------------------------

# Loss agreement, mesh against one chip, two steps: the convolutions take
# bf16 operands, the partial sums of every batch-norm statistic and
# gradient cross chips in another order than on one chip, and a flipped
# bf16 rounding in one layer feeds the next — and the second loss sits
# behind a whole update built from those gradients.  Two percent covers
# that (the toy rehearsal, where one step moves the loss by a third,
# shows 1.6e-2), and is far below what a wrong shard or a lost
# gradient moves the loss by.
MESH_LOSS_RTOL = 2e-2


def mesh_spec(text):
    """argparse type for --mesh: one AXIS=N pair."""
    axis, _, size = text.partition('=')
    if axis not in ('dp', 'fsdp') or not size.isdigit() or int(size) < 2:
        raise argparse.ArgumentTypeError(
            '%r is not dp=N or fsdp=N with N >= 2' % text)
    return text


def mesh_size(spec):
    return int(spec.partition('=')[2])


def phase_mesh(smoke, spec, hlo_dir):
    import jax
    n = mesh_size(spec)
    pattern = os.path.join(hlo_dir, '*jit_step_fn*after_optimizations.txt')
    dumped_before = set(glob.glob(pattern))
    rig = TrainRig(smoke, smoke.cfg['mesh_batch'], mesh=spec)
    losses = [rig.step(rig.feed(i))[0] for i in range(2)]
    info = {'mesh': spec, 'global_batch': rig.batch, 'losses': losses}
    passes = rig.passes_ok()
    check('sharding' in passes, 'no sharding pass ran: %r' % passes)

    # where the state lives after two steps.  (Read-only state — the
    # learning rate — is staged from the scope on every call and never
    # written back, so the scope's copy stays where startup put it.)
    state = {name: rig.scope.get(name)
             for name, v in rig.main.global_block().vars.items()
             if v.persistable and rig.scope.has(name)}
    total = sum(a.nbytes for a in state.values())
    off_mesh = {name: a.nbytes for name, a in state.items()
                if not (isinstance(a, jax.Array)
                        and len(a.sharding.device_set) == n)}
    info['state_bytes'] = total
    info['state_not_on_all_devices'] = off_mesh
    check(total and sum(off_mesh.values()) <= 1e-4 * total,
          'state not on all %d devices: %r' % (n, off_mesh))
    quarter = sum(a.nbytes for name, a in state.items()
                  if name not in off_mesh
                  and a.addressable_shards[0].data.size * n == a.size)
    info['state_bytes_sharded_1_over_n'] = quarter
    if spec.startswith('fsdp'):
        # what cannot split n ways (scalars, a 7-wide stem dim) stays
        # whole; everything that carries weight must be a 1/n shard
        check(quarter >= 0.95 * total,
              'fsdp=%d shards only %d of %d state bytes'
              % (n, quarter, total))
    else:
        check(quarter == 0, 'dp=%d sharded %d state bytes' % (n, quarter))

    in_use = [(d.memory_stats() or {}).get('bytes_in_use')
              for d in jax.devices()[:n]]
    info['bytes_in_use_per_device'] = in_use
    if not smoke.rehearse:
        check(all(in_use) and max(in_use) <= 2 * min(in_use),
              'device memory is not spread evenly: %r' % in_use)

    # the collectives XLA actually compiled into the step
    dumped = set(glob.glob(pattern)) - dumped_before
    check(dumped, 'XLA dumped no compiled step under %s' % hlo_dir)
    with open(max(dumped, key=os.path.getmtime)) as f:  # startup is older
        hlo = f.read()
    found = {op: hlo.count(' %s(' % op) + hlo.count(' %s-start(' % op)
             for op in ('all-reduce', 'reduce-scatter', 'all-gather',
                        'all-to-all', 'collective-permute')}
    info['collectives_in_compiled_step'] = found
    info['tpu_custom_calls_in_compiled_step'] = hlo.count('tpu_custom_call')
    if spec.startswith('fsdp'):
        check(found['reduce-scatter'] or found['all-gather'],
              'no reduce-scatter/all-gather in the fsdp step: %r' % found)
    else:
        check(found['all-reduce'], 'no all-reduce in the dp step')
    return info


def phase_mesh_reference(smoke, mesh_infos):
    """The same two steps on one chip, after the mesh runs (so that its
    state does not weigh on device 0 while they are measured)."""
    rig = TrainRig(smoke, smoke.cfg['mesh_batch'])
    ref = [rig.step(rig.feed(i))[0] for i in range(2)]
    info = {'one_chip_losses': ref, 'rtol': MESH_LOSS_RTOL, 'rel_diff': {}}
    check(mesh_infos, 'no mesh phase succeeded to compare against')
    for spec, losses in mesh_infos.items():
        diff = max(abs(a - b) / abs(b) for a, b in zip(losses, ref))
        info['rel_diff'][spec] = diff
        check(diff <= MESH_LOSS_RTOL,
              '%s losses %r vs one chip %r' % (spec, losses, ref))
    return info


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

# Paged logits against the full-context forward, on the same device.
# On the CPU the two agree to f32 ulps (tests/test_decode.py, 2e-6).  On
# a TPU both run their f32 matmuls as single bf16 MXU passes (jax's
# default precision): they round the same operands but accumulate in
# different orders, and one flipped bf16 rounding feeds the next layer,
# so agreement is to bf16 resolution (2^-8 = 4e-3) grown over six
# layers.  1.5e-2 of the logits' scale is that, and two orders below a
# wrong page or a stale cache line (which moves logits by their scale).
SERVE_LOGITS_TOL = 1.5e-2


def phase_serve(smoke):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.inference.decode import (DecodeEngine, DecodeServer,
                                             extract_params)
    from paddle_tpu.models import transformer
    # the plain full-context forward lives with the tests
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tests'))
    from reference_opt import forward as _forward
    c = smoke.cfg['serve']
    L, H, V = c['L'], c['H'], c['V']
    scope = fluid.Scope()
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = SEED
    with fluid.program_guard(main_p, startup):
        transformer.build(vocab_size=V, seq_len=c['T'], n_layers=L,
                          d_model=c['D'], n_heads=H)
    fluid.Executor(smoke.place()).run(startup, scope=scope)
    params = extract_params(scope, L)
    eng = DecodeEngine(params, n_layers=L, n_heads=H,
                       page_size=c['page'], max_streams=c['streams'],
                       prefill_bucket=c['bucket'])
    t0 = time.perf_counter()
    eng.warmup()
    info = {'config': 'L=%(L)d D=%(D)d H=%(H)d V=%(V)d T=%(T)d page=%(page)d '
                      'streams=%(streams)d bucket=%(bucket)d' % c,
            'warmup_compiles': eng.compiles_total,
            'warmup_wall_s': round(time.perf_counter() - t0, 2)}

    rng = np.random.default_rng(SEED)
    plens = rng.integers(c['prompt'][0], c['prompt'][1] + 1, 8)
    plens[0], plens[1] = c['prompt']  # both ends of the range are served
    nnews = rng.integers(c['new'][0], c['new'][1] + 1, 8)
    prompts = [rng.integers(1, V, int(p)).astype(np.int64) for p in plens]
    srv = DecodeServer(eng)
    try:
        t0 = time.perf_counter()
        streams = [srv.submit(p, max_new_tokens=int(n))
                   for p, n in zip(prompts[:4], nnews[:4])]
        # the rest arrive while the first four decode
        deadline = time.perf_counter() + 120
        while not streams[0].tokens and time.perf_counter() < deadline:
            time.sleep(0.001)
        check(streams[0].tokens, 'no first token within 120 s')
        joined_mid_decode = streams[0].done_t is None
        streams += [srv.submit(p, max_new_tokens=int(n))
                    for p, n in zip(prompts[4:], nnews[4:])]
        check(srv.drain(timeout=300.0), 'decode drain timed out')
        info['serve_wall_s'] = round(time.perf_counter() - t0, 3)
        stats = srv.stats()
    finally:
        srv.close()
    for st, n in zip(streams, nnews):
        check(st.error is None, 'stream failed: %r' % (st.error,))
        check(len(st.result(timeout=1.0)) == int(n),
              '%s gave %d of %d tokens' % (st.request_id, len(st.tokens), n))
    check(stats['completed'] == 8 and stats['dropped'] == 0, repr(stats))
    check(stats['compiles_after_warmup'] == 0, repr(stats))
    info.update(completed=stats['completed'],
                compiles_after_warmup=stats['compiles_after_warmup'],
                joined_mid_decode=joined_mid_decode,
                tokens_generated=int(sum(nnews)),
                prompt_lens=[int(p) for p in plens])

    # two requests again, by hand through the same engine, teacher-forced
    # with the tokens the server emitted: every step's paged logits
    # against the full-context forward over the same tokens
    pad = c['bucket']  # one reference compile covers both requests
    ref_fn = jax.jit(lambda p, t: _forward(p, t, L, H)[0])
    mpp = eng.pages_per_stream
    errs, agree = [], []
    for idx in (0, 1):
        prompt, gen = list(prompts[idx]), streams[idx].tokens
        seq = prompt + gen
        check(len(seq) <= pad, 'request longer than the reference pad')
        toks = np.zeros((1, pad), np.int32)
        toks[0, :len(seq)] = seq
        ref = np.asarray(ref_fn(params, jnp.asarray(toks)))[0]
        pages = eng.cache.alloc(-(-len(seq) // eng.page_size))
        check(pages is not None, 'page pool did not drain')
        rows = [eng.prefill_into(np.asarray(prompt), pages)]
        for j, tok in enumerate(gen[:-1]):
            pt = np.full((eng.max_streams, mpp), eng.cache.trash, np.int32)
            pt[0, :len(pages)] = pages
            t_in = np.zeros((eng.max_streams,), np.int64)
            t_in[0] = tok
            ctx = np.zeros((eng.max_streams,), np.int32)
            ctx[0] = len(prompt) + j
            rows.append(eng.step(t_in, pt, ctx)[1][0])
        eng.cache.free(pages)
        paged = np.stack(rows)
        want = ref[len(prompt) - 1:len(seq) - 1]
        errs.append(rel_err(paged, want))
        agree.append(float(np.mean(np.argmax(paged, -1) == np.asarray(gen))))
    info['paged_vs_full_context_logits_rel_err'] = errs
    info['logits_tol'] = SERVE_LOGITS_TOL
    info['served_tokens_equal_replayed_argmax'] = agree  # information
    check(max(errs) <= SERVE_LOGITS_TOL, 'paged logits off: %r' % errs)
    check(eng.compiles_after_warmup == 0, 'the replay compiled')
    return info


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

class KernelCase(object):
    """One Pallas entry point at one shape.  `specs` are the operands
    ((shape, dtype) each), `kernel(*ops, interpret=)` and `reference(*ops)`
    return the same pytree, `tol` bounds rel_err leaf by leaf and `why`
    says where the bound comes from.  `make` builds operands that are not
    plain normals (row ids)."""

    def __init__(self, name, specs, kernel, reference, tol, why,
                 make=None, timed=False):
        self.name, self.specs = name, specs
        self.kernel, self.reference = kernel, reference
        self.tol, self.why, self.make, self.timed = tol, why, make, timed

    def operands(self, rng):
        if self.make is not None:
            return self.make(rng)
        return [(rng.standard_normal(s) * 0.5).astype(np.float32)
                .astype(dt) for s, dt in self.specs]


# bf16 operands carry 8 bits of mantissa and the kernel rounds P to bf16
# before the PV product; sums are f32.  Against an f32 reference that is
# a few bf16 ulps (2^-8 = 4e-3) of each tensor's scale.
TOL_BF16 = (2e-2, 'bf16 operands and a bf16 P tile, f32 accumulation')
# the fused recurrence against the lax.scan it replaces, both at jax's
# default matmul precision — on a TPU one bf16 MXU pass for f32 operands,
# in the kernel and in XLA alike, so the two differ by accumulation order
# only: hs came out bit-equal and the gradients within 2.7e-4 on the v5e
# (PR 21).  Against an f32-exact scan the SAME kernel is off by 8e-2
# after 128 steps of a unit-gain random recurrence, and so is XLA's own
# scan: the operands below are scaled to contract, so that the check
# measures the kernel and not the conditioning of the test.
TOL_RNN = (2e-3, 'same bf16-pass matmul on both sides, f32 elsewhere; '
                 'seven times the 2.7e-4 observed on a v5e')
# elementwise f32: kernel and XLA may contract multiply-adds differently
# and expand sqrt/divide differently — ulps, not bits
TOL_F32 = (2e-6, 'elementwise f32, differs by fma contraction and '
                 'sqrt/divide expansion only')


def kernel_cases(cfg):
    """Every public Pallas entry point at its bench shape.  Shared with
    tests/test_tpu_lowering.py, which lowers each for the TPU without a
    chip."""
    import importlib

    import jax
    import jax.numpy as jnp
    # (ops.pallas re-exports a function under the flash module's name)
    fa = importlib.import_module('paddle_tpu.ops.pallas.flash_attention')
    from paddle_tpu.core.selected_rows import merge_duplicate_rows
    from paddle_tpu.ops.pallas import lstm_cell as lc
    from paddle_tpu.ops.pallas import table_update as tu
    f32, bf16 = jnp.float32, jnp.bfloat16
    cases = []

    # -- flash attention, causal, forward and backward -------------------
    c = cfg['flash']
    qkv = ((c['B'], c['T'], c['H'], c['D']), bf16)

    def with_grads(attn):
        def f(q, k, v, cot):
            out, vjp = jax.vjp(attn, q, k, v)
            return (out,) + vjp(cot.astype(out.dtype))
        return f

    def attn_ref(q, k, v):
        # one (batch, head) at a time, recomputed in the backward: the
        # [T, T] scores of all sixteen at T=8192 would not fit
        scale = q.shape[-1] ** -0.5

        def one(args):
            qh, kh, vh = (a.astype(f32) for a in args)
            s = jnp.matmul(qh, kh.T, precision='highest') * scale
            t = s.shape[0]
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
            return jnp.matmul(jax.nn.softmax(s, axis=-1), vh,
                              precision='highest')

        b, t, h, d = q.shape
        flat = [jnp.moveaxis(a, 2, 1).reshape(b * h, t, d)
                for a in (q, k, v)]
        out = jax.lax.map(jax.checkpoint(one), tuple(flat))
        return jnp.moveaxis(out.reshape(b, h, t, d), 1, 2).astype(q.dtype)

    cases.append(KernelCase(
        'flash_attention_causal_fwd_bwd', [qkv] * 4,
        lambda q, k, v, cot, interpret: with_grads(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, interpret=interpret))(q, k, v, cot),
        with_grads(attn_ref), *TOL_BF16))

    # -- paged decode attention over live pages ---------------------------
    from paddle_tpu.ops.attention import paged_attention_math
    from paddle_tpu.ops.pallas import paged_attention
    pg = cfg['paged']
    pool = ((pg['N'], pg['P'], pg['H'] * pg['D']), bf16)

    def running_slots(rng, g):
        # a third of the slots run, contexts anywhere up to max_seq, on
        # pages scattered over the pool; the others idle on the trash page
        s, mpp, n = g['S'], g['MPP'], g['N']
        pt = np.full((s, mpp), n - 1, np.int32)
        ctx = np.ones((s,), np.int32)
        free = rng.permutation(n - 1)
        for slot in rng.permutation(s)[:max(1, s // 3)]:
            ctx[slot] = rng.integers(1, mpp * g['P'] + 1)
            pages = -(-int(ctx[slot]) // g['P'])
            pt[slot, :pages], free = free[:pages], free[pages:]
        return [pt, ctx]

    def paged_make(rng):
        kv = [(rng.standard_normal(pool[0]) * 0.5).astype(np.float32)
              .astype(bf16) for _ in range(2)]
        return [rng.standard_normal((pg['S'], pg['H'], pg['D']))
                .astype(np.float32)] + kv + running_slots(rng, pg)

    cases.append(KernelCase(
        'paged_attention_live_pages',
        [((pg['S'], pg['H'], pg['D']), f32), pool, pool,
         ((pg['S'], pg['MPP']), jnp.int32), ((pg['S'],), jnp.int32)],
        lambda q, k, v, pt, ctx, interpret: paged_attention(
            q, k, v, pt, ctx, interpret=interpret),
        paged_attention_math, *TOL_BF16, make=paged_make, timed=True))

    # -- the same with query heads in groups over fewer K/V heads, over a
    # whole table and over a ring that holds a window; and a prompt
    # chunk's rows over the stream's live pages ----------------------------
    from paddle_tpu.ops.attention import chunked_prefill_attention_math
    from paddle_tpu.ops.pallas.paged_attention import chunk_paged_attention
    gq = cfg['grouped']
    gpool = ((gq['N'], gq['P'], gq['HKV'] * gq['D']), bf16)

    def grouped_make(kind, chunk, gq=gq, gpool=gpool):
        def make(rng):
            g = dict(gq, **kind)
            kv = [(rng.standard_normal(gpool[0]) * 0.5).astype(np.float32)
                  .astype(bf16) for _ in range(2)]
            pt, ctx = running_slots(rng, g)
            if kind['window']:      # a ring holds the newest of any number
                ctx = ctx + np.where(ctx > 1, 3 * g['MPP'] * g['P'], 0
                                     ).astype(np.int32)
            if chunk:   # one stream's chunk, the queries as the kernel
                # rounds them, behind three chunks' worth of context
                slot = int(np.argmax(ctx))
                q = rng.standard_normal((g['C'], g['H'], g['D'])).astype(
                    np.float32).astype(bf16).astype(np.float32)
                return [q] + kv + [pt[slot], np.int32(3 * g['C'])]
            return [rng.standard_normal((g['S'], g['H'], g['D']))
                    .astype(np.float32)] + kv + [pt, ctx]
        return make

    def slot_blocks(math, block=8):
        # over all 32 slots at once the expression's gathered K/V,
        # repeated to 48 query heads, is 15 GB: more than the chip has
        def blocked(q, k, v, pt, ctx):
            return jnp.concatenate([
                math(q[i:i + block], k, v, pt[i:i + block],
                     ctx[i:i + block])
                for i in range(0, q.shape[0], block)])
        return blocked

    for kind_name, kind in sorted(gq['kinds'].items()):
        win = kind['window']
        cases.append(KernelCase(
            'paged_attention_grouped_%s' % kind_name,
            [((gq['S'], kind['H'], gq['D']), f32), gpool, gpool,
             ((gq['S'], kind['MPP']), jnp.int32), ((gq['S'],), jnp.int32)],
            functools.partial(
                lambda q, k, v, pt, ctx, interpret, win: paged_attention(
                    q, k, v, pt, ctx, window=win, interpret=interpret),
                win=win),
            slot_blocks(functools.partial(paged_attention_math,
                                          window=win)),
            *TOL_BF16, make=grouped_make(kind, False), timed=True))
        cases.append(KernelCase(
            'chunk_paged_attention_%s' % kind_name,
            [((gq['C'], kind['H'], gq['D']), f32), gpool, gpool,
             ((kind['MPP'],), jnp.int32), ((), jnp.int32)],
            functools.partial(
                lambda q, k, v, pt, pos0, interpret, win:
                chunk_paged_attention(q, k, v, pt, pos0, window=win,
                                      interpret=interpret), win=win),
            functools.partial(chunked_prefill_attention_math, window=win),
            *TOL_BF16, make=grouped_make(kind, True), timed=True))

    # -- every query head over ONE K/V head (multi-query) --------------------
    mq = cfg['mqa']
    mpool = ((mq['N'], mq['P'], mq['D']), bf16)
    cases.append(KernelCase(
        'paged_attention_mqa',
        [((mq['S'], mq['H'], mq['D']), f32), mpool, mpool,
         ((mq['S'], mq['MPP']), jnp.int32), ((mq['S'],), jnp.int32)],
        lambda q, k, v, pt, ctx, interpret: paged_attention(
            q, k, v, pt, ctx, interpret=interpret),
        slot_blocks(paged_attention_math),
        *TOL_BF16, make=grouped_make(mq, False, mq, mpool), timed=True))
    cases.append(KernelCase(
        'chunk_paged_attention_mqa',
        [((mq['C'], mq['H'], mq['D']), f32), mpool, mpool,
         ((mq['MPP'],), jnp.int32), ((), jnp.int32)],
        lambda q, k, v, pt, pos0, interpret: chunk_paged_attention(
            q, k, v, pt, pos0, interpret=interpret),
        chunked_prefill_attention_math,
        *TOL_BF16, make=grouped_make(mq, True, mq, mpool), timed=True))

    # -- a chunk's selective scan, the state in VMEM for all its tokens -----
    from paddle_tpu.ops.pallas.selective_scan import selective_scan
    from paddle_tpu.ops.ssm import selective_scan_math
    sm = cfg['ssm']
    seq, lanes = (sm['T'], sm['DC']), (sm['NS'], sm['DC'])

    def ssm_make(rng):
        f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
        return [f(*seq), np.log1p(np.exp(f(*seq) - 3.0)),
                -np.exp(f(*lanes) * 0.5), f(sm['T'], sm['NS']),
                f(sm['T'], sm['NS']), f(sm['DC']), f(*lanes),
                np.int32(sm['valid'])]

    def scan_both(fn):
        # the rows past ``valid`` are padding: whatever a form leaves there
        def f(*ops, **kw):
            y, s = fn(*ops, **kw)
            return jnp.where(jnp.arange(sm['T'])[:, None] < ops[-1], y,
                             0.0), s
        return f

    cases.append(KernelCase(
        'selective_scan',
        [(seq, f32), (seq, f32), (lanes, f32), ((sm['T'], sm['NS']), f32),
         ((sm['T'], sm['NS']), f32), ((sm['DC'],), f32), (lanes, f32),
         ((), jnp.int32)],
        scan_both(selective_scan), scan_both(selective_scan_math),
        2e-5, 'float32 on both sides: the order of the sums over the '
        'state\'s lanes and the expansion of exp differ',
        make=ssm_make, timed=True))

    # -- the same over ONE latent row a position (MLA, absorbed form) ------
    from paddle_tpu.ops.attention import latent_paged_attention_math
    from paddle_tpu.ops.pallas.paged_attention import latent_paged_attention
    lt = cfg['latent']
    scale = lt['W'] ** -0.5

    def latent_make(rng):
        # the queries as the kernel rounds them, so that the math (which
        # keeps float32 queries) sees the same numbers
        rows = (rng.standard_normal((lt['N'], lt['P'], lt['W'])) * 0.5
                ).astype(np.float32).astype(bf16)
        q = rng.standard_normal((lt['S'], lt['H'], lt['W'])).astype(
            np.float32).astype(bf16).astype(np.float32)
        return [q, rows] + running_slots(rng, lt)

    cases.append(KernelCase(
        'latent_paged_attention_live_pages',
        [((lt['S'], lt['H'], lt['W']), f32),
         ((lt['N'], lt['P'], lt['W']), bf16),
         ((lt['S'], lt['MPP']), jnp.int32), ((lt['S'],), jnp.int32)],
        lambda q, pool_, pt, ctx, interpret: latent_paged_attention(
            q, pool_, pt, ctx, scale, lt['C'], interpret=interpret),
        lambda q, pool_, pt, ctx: latent_paged_attention_math(
            q, pool_, pt, ctx, scale, lt['C']),
        *TOL_BF16, make=latent_make, timed=True))

    # -- fused recurrences, forward and backward -------------------------
    def scan_case(name, c, gates, kernel, reference):
        t, b, h = c['T'], c['B'], c['H']
        specs = [((t, b, gates * h), f32), ((h, gates * h), f32)]
        n_out = 2 if gates == 4 else 1
        if gates == 4:
            specs.append(((3, h), f32))  # peepholes, as dynamic_lstm has
        specs += [((t, b, h), f32)] * n_out  # cotangents

        def make(rng):
            ops = [rng.standard_normal(s).astype(np.float32)
                   for s, _ in specs]
            ops[0] *= 0.5               # gate pre-activations
            ops[1] *= 0.5 * h ** -0.5   # recurrent weight: gain 1/2
            if gates == 4:
                ops[2] *= 0.1           # peepholes
            return ops

        def wrap(fn):
            def f(*ops):
                ins, cots = ops[:-n_out], ops[-n_out:]
                out, vjp = jax.vjp(fn, *ins)
                return (out,) + vjp(cots if n_out > 1 else cots[0])
            return f

        return KernelCase(
            name, specs,
            lambda *ops, interpret: wrap(
                lambda *ins: kernel(*ins, interpret=interpret))(*ops),
            wrap(reference), *TOL_RNN, make=make)

    cases.append(scan_case('lstm_scan_fwd_bwd', cfg['lstm'], 4,
                           lc.lstm_scan, lc._scan_reference))
    cases.append(scan_case(
        'gru_scan_fwd_bwd', cfg['gru'], 3,
        lambda x, w, interpret: lc.gru_scan(x, w, interpret=interpret),
        lc._gru_scan_reference))

    # -- row-sparse applies (ops/optim_ops.py sparse branches) -----------
    c = cfg['sparse']
    hgt, wid, k = c['H'], c['D'], c['K']
    b1, b2, eps = 0.9, 0.999, 1e-8
    tab, rows_s, vals_s = ((hgt, wid), f32), ((k,), jnp.int32), ((k, wid), f32)

    def sparse_make(n_tabs):
        def make(rng):
            rows = rng.integers(0, hgt, k).astype(np.int32)
            rows[:k // 8] = rows[k // 8:k // 4]  # duplicates
            rows[-k // 16:] = hgt                # sentinel padding
            rows[0], rows[1] = hgt - 1, 0        # both ends of the table
            rng.shuffle(rows)
            tabs = [np.abs(rng.standard_normal((hgt, wid)))
                    .astype(np.float32) for _ in range(n_tabs)]
            return tabs + [rows, rng.standard_normal((k, wid))
                           .astype(np.float32), np.float32(0.01)]
        return make

    def adagrad_ref(p, mom, rows, vals, lr):
        r, g, valid = merge_duplicate_rows(rows, vals)
        mask = valid[:, None]
        step = -lr * g / (jnp.sqrt(mom[r] + jnp.square(g)) + 1e-6)
        return (p.at[r].add(jnp.where(mask, step, 0.0)),
                mom.at[r].add(jnp.where(mask, jnp.square(g), 0.0)))

    def adam_sparse_ref(p, m, v, rows, vals, lr):
        r, g, valid = merge_duplicate_rows(rows, vals)
        mask = valid[:, None]
        m_row = b1 * m[r] + (1 - b1) * g
        v_row = b2 * v[r] + (1 - b2) * jnp.square(g)
        step = -lr * m_row / (jnp.sqrt(v_row) + eps)
        return (p.at[r].add(jnp.where(mask, step, 0.0)),
                m.at[r].add(jnp.where(mask, m_row - m[r], 0.0)),
                v.at[r].add(jnp.where(mask, v_row - v[r], 0.0)))

    s = ((), f32)
    cases.append(KernelCase(
        'sparse_apply_sgd', [tab, rows_s, vals_s, s],
        lambda p, r, v, lr, interpret: tu.sparse_apply_sgd(
            p, r, v, lr, interpret=interpret),
        lambda p, r, v, lr: p.at[r].add(-lr * v),
        *TOL_F32, make=sparse_make(1), timed=True))
    cases.append(KernelCase(
        'sparse_apply_adagrad', [tab, tab, rows_s, vals_s, s],
        lambda p, m, r, v, lr, interpret: tu.sparse_apply_adagrad(
            p, m, r, v, lr, 1e-6, interpret=interpret),
        adagrad_ref, *TOL_F32, make=sparse_make(2), timed=True))
    cases.append(KernelCase(
        'sparse_apply_adam', [tab, tab, tab, rows_s, vals_s, s],
        lambda p, m, v, r, g, lr, interpret: tu.sparse_apply_adam(
            p, m, v, r, g, lr, b1, b2, eps, interpret=interpret),
        adam_sparse_ref, *TOL_F32, make=sparse_make(3), timed=True))
    return cases


def _median_wall_ms(fn, ops, reps=5):
    import jax
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*ops))
        walls.append(time.perf_counter() - t0)
    return round(1e3 * sorted(walls)[len(walls) // 2], 3)


def phase_kernels(smoke):
    import jax
    from paddle_tpu.ops.pallas.table_update import sparse_apply_mode
    interpret = smoke.rehearse  # on the chip Mosaic compiles every one
    info = {'interpret': interpret, 'kernels': {},
            'sparse_apply_mode': sparse_apply_mode()}
    check(interpret or jax.default_backend() == 'tpu',
          'kernels would be interpreted')
    rng = np.random.default_rng(SEED)
    failed = []
    for case in kernel_cases(smoke.cfg):
        row = info['kernels'][case.name] = {'tol': case.tol}
        try:
            ops = [jax.device_put(o) for o in case.operands(rng)]
            kern = jax.jit(functools.partial(case.kernel,
                                             interpret=interpret))
            ref = jax.jit(case.reference)
            if not interpret:
                check('tpu_custom_call' in kern.lower(*ops).as_text(),
                      'no tpu_custom_call in the lowered kernel')
            t0 = time.perf_counter()
            got = jax.block_until_ready(kern(*ops))
            row['compile_plus_first_call_wall_s'] = round(
                time.perf_counter() - t0, 2)
            want = jax.block_until_ready(ref(*ops))
            row['rel_err'] = [rel_err(g, w) for g, w in zip(
                jax.tree_util.tree_leaves(got),
                jax.tree_util.tree_leaves(want))]
            check(max(row['rel_err']) <= case.tol,
                  'rel_err %r over %g (%s)'
                  % (row['rel_err'], case.tol, case.why))
            if case.timed and not interpret:
                # information for ROADMAP S2/S3, not a claim: synced
                # wall of the whole call, operands not donated
                row['pallas_call_wall_ms'] = _median_wall_ms(kern, ops)
                row['xla_expr_wall_ms'] = _median_wall_ms(ref, ops)
            del ops, got, want
        except Exception as e:  # every family reports; the phase fails
            traceback.print_exc()
            row['error'] = repr(e)[:1000]
            failed.append(case.name)
    smoke.emit({'phase': 'kernels', 'detail': info})
    check(not failed, 'kernels failed: %r' % failed)
    return {'compiled_and_matched': sorted(info['kernels']),
            'interpret': interpret,
            'sparse_apply_mode': info['sparse_apply_mode']}


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------

def phase_trace(smoke):
    import jax
    import paddle_tpu as fluid
    rig = smoke.rig()
    rig.step(rig.feed(0))  # the plan is compiled before the window opens
    log_dir = os.path.join(smoke.out_dir, 'trace')
    with fluid.profiler.profiler(log_dir=log_dir):
        for i in range(3):
            rig.step(rig.feed(i))
    paths = glob.glob(os.path.join(log_dir, '**', '*.xplane.pb'),
                      recursive=True)
    check(paths, 'no .xplane.pb under %s' % log_dir)
    path = max(paths, key=os.path.getmtime)
    planes = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        planes[plane.name] = sum(len(list(line.events))
                                 for line in plane.lines)
    device = {n: c for n, c in planes.items() if n.startswith('/device:')}
    check(smoke.rehearse or any(device.values()),
          'no device plane with events: %r' % planes)
    check(any(planes.values()), 'the trace holds no events')
    xplane_bytes = os.path.getsize(path)
    # read, not kept: 33 MB a run (750k host events beside the device's
    # 22k) crowds out what the chip tool copies back; S1 owns traces
    shutil.rmtree(log_dir)
    return {'xplane_bytes': xplane_bytes, 'events_per_plane': planes}


# ---------------------------------------------------------------------------

PHASES = {'train': phase_train, 'serve': phase_serve,
          'kernels': phase_kernels, 'trace': phase_trace}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--rehearse', action='store_true',
                    help='toy sizes on the CPU, kernels interpreted; every '
                         'line says "rehearsal": true')
    ap.add_argument('--mesh', action='append', metavar='AXIS=N',
                    type=mesh_spec,
                    help='run the train phase over PADDLE_TPU_MESH=AXIS=N '
                         '(dp=4, fsdp=4; repeatable) against one chip, '
                         'instead of the four phases')
    ap.add_argument('--phases', default=','.join(PHASES),
                    help='comma-separated subset of %s' % ','.join(PHASES))
    ap.add_argument('--out', default=os.path.join('chiprun_out',
                                                  'chip_smoke'),
                    help='output directory (default %(default)s)')
    args = ap.parse_args(argv)

    hlo_dir = None
    if args.rehearse:
        os.environ['JAX_PLATFORMS'] = 'cpu'
    if args.mesh:
        # the compiled step's collectives are read from XLA's own dump;
        # a step served from the compile cache would dump nothing
        hlo_dir = tempfile.mkdtemp(prefix='chip_smoke_hlo_')
        os.environ['JAX_ENABLE_COMPILATION_CACHE'] = 'false'
        flags = ['--xla_dump_to=' + hlo_dir, '--xla_dump_hlo_as_text',
                 '--xla_dump_hlo_module_re=jit_step_fn']
        if args.rehearse:
            flags.append('--xla_force_host_platform_device_count=%d'
                         % max(map(mesh_size, args.mesh)))
        os.environ['XLA_FLAGS'] = ' '.join(
            [os.environ.get('XLA_FLAGS', '')] + flags).strip()

    import importlib.metadata

    import jax
    import jaxlib
    import paddle_tpu  # (fails in a directory without the repo)
    smoke = Smoke(args.rehearse, args.out)
    try:
        libtpu = importlib.metadata.version('libtpu')
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    found = dict(smoke.device, jax=jax.__version__,
                 jaxlib=jaxlib.__version__, libtpu=libtpu)
    if smoke.device['platform'] != 'tpu' and not args.rehearse:
        print('chip_smoke: jax found no TPU: %s' % json.dumps(found),
              file=sys.stderr)
        return 2

    os.makedirs(args.out, exist_ok=True)
    smoke.emit({'chip_smoke': 'start', 'found': found, 'argv': sys.argv[1:],
                'compile_cache_dir': paddle_tpu.compile_cache
                .compile_cache_dir()})
    if args.mesh:
        if max(map(mesh_size, args.mesh)) > smoke.device['count']:
            raise SystemExit('--mesh %s needs more than the %d device(s) '
                             'jax found' % (' '.join(args.mesh),
                                            smoke.device['count']))
        mesh_losses = {}

        def mesh_phase(spec):
            info = phase_mesh(smoke, spec, hlo_dir)
            mesh_losses[spec] = info['losses']
            return info

        phases = [('train_mesh_' + s, functools.partial(mesh_phase, s))
                  for s in args.mesh]
        phases.append(('train_one_chip_reference', functools.partial(
            phase_mesh_reference, smoke, mesh_losses)))
    else:
        names = [n for n in args.phases.split(',') if n]
        unknown = sorted(set(names) - set(PHASES))
        if unknown:
            raise SystemExit('unknown phase(s): %s' % ', '.join(unknown))
        phases = [(n, functools.partial(PHASES[n], smoke)) for n in names]
    failed = run_phases(phases, smoke.emit)
    if hlo_dir:
        shutil.rmtree(hlo_dir, ignore_errors=True)
    result = {'ok': not failed, 'device': smoke.device}
    if failed:
        result['failed'] = failed
    if args.rehearse:
        result['rehearsal'] = True
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main())

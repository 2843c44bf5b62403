"""PipelineTranspiler — Program-level pipeline parallelism.

Reference parity: the reference's distribution story rewrites whole user
programs (python/paddle/v2/fluid/distribute_transpiler.py splits a
Program into trainer/pserver programs); this transpiler gives the same
Program-level capability to pipeline parallelism: it cuts a fluid
Program's forward at user-annotated boundary vars into S stage
subgraphs and trains it with the 1F1B engine
(parallel/pipeline.pipeline_train_1f1b) over a 'pp' mesh axis — the
backward rides the same scan as the forward, so activation liveness is
bounded by the pipeline depth, not the microbatch count.

TPU-native design decisions:
- Stages run as `lax.switch` branches inside ONE SPMD program (the
  mesh stays a single jit; no per-stage processes).  Each member
  executes only its own branch at runtime.
- The stage interface is the cut var, flattened and zero-padded to one
  uniform [mb, W] buffer so heterogeneous cut widths still ride one
  ppermute channel.
- Params are replicated over the pp axis (activation memory is what
  the pipeline axis owns; shard params over an orthogonal fsdp axis
  for param memory).  Each member produces its own stage's grads; one
  psum replicates the full gradient, and the PROGRAM'S OWN
  backward/optimize-role ops (grad clip, regularizers, sgd/adam, LR
  schedules) then run on it — any optimizer the Program was built
  with works unchanged.
- A second mesh axis composes as DATA-PARALLEL replicas of the whole
  pipeline: microbatch contents shard over it, loss/grads pmean, and
  each replica folds its dp index into the PRNG keys (the ParallelDo
  convention).  Deterministic programs train with exact single-device
  parity; stochastic ones draw distinct per-replica randomness.
- The per-microbatch loss must be an example-mean (fluid's
  `mean(...)` convention): the pipeline's total is the mean over
  microbatches, which equals the full-batch loss when the batch splits
  evenly.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.executor import ExecutionContext, _run_one
from ..core.program import Variable, default_main_program
from ..core.scope import global_scope
from ..parallel import collective
from ..parallel.pipeline import pipeline_train_1f1b

__all__ = ['PipelineTranspiler', 'annotate_pp_cut', 'from_mesh']


def annotate_pp_cut(var, program=None):
    """Mark ``var`` as a pipeline-stage boundary candidate.

    The name lands on ``program._pp_cut_names`` where BOTH consumers
    read it: the sharding pass's pp planner (bubble/ppermute terms in
    the cost report when PADDLE_TPU_MESH carries a pp axis) and
    :func:`from_mesh` (actual stage cutting).  Annotating more
    boundaries than stages is encouraged — the planner picks the
    compute-balanced subset (``transpiler.sharding.select_pp_cuts``).
    Returns ``var`` so the call nests inside layer expressions.
    """
    program = program or default_main_program()
    name = var.name if isinstance(var, Variable) else str(var)
    cuts = getattr(program, '_pp_cut_names', None)
    if cuts is None:
        cuts = []
        program._pp_cut_names = cuts
    if name not in cuts:
        cuts.append(name)
    return var


def from_mesh(program=None, pp_axis='pp', cut_vars=None,
              num_microbatches=None):
    """Mesh-driven pipeline entry: the PADDLE_TPU_MESH counterpart of
    hand-constructing a :class:`PipelineTranspiler`.

    Reads the pipeline depth from the mesh flag's ``pp`` axis (e.g.
    ``PADDLE_TPU_MESH=pp2,dp=2`` — compact and ``axis=size`` forms both
    parse), cuts the program at its :func:`annotate_pp_cut` boundaries
    (auto-balancing when more were annotated than needed), builds the
    mesh, and returns the transpiled instance with ``mesh`` and
    ``num_microbatches`` (PADDLE_TPU_PP_MICROBATCHES unless overridden)
    attached — drive steps with :meth:`PipelineTranspiler.run_mesh_step`.

    This is the path the SPMD executor's pp refusal points at: a pp
    axis shards TIME, so it cannot lower as one pjit program — it needs
    the 1F1B engine's per-stage branches and ppermute transfers.
    """
    from ..flags import FLAGS
    from . import mesh_flag
    program = program or default_main_program()
    axes = mesh_flag.mesh_axes_from_flag()
    sizes = dict(axes or ())
    stages = int(sizes.get(pp_axis, 0))
    if stages < 2:
        raise ValueError(
            "from_mesh needs a %r axis of size >= 2 in PADDLE_TPU_MESH "
            "(e.g. PADDLE_TPU_MESH=%s2,dp=2); got %r"
            % (pp_axis, pp_axis, dict(sizes)))
    if cut_vars is None:
        from ..transpiler.sharding import select_pp_cuts
        names = list(getattr(program, '_pp_cut_names', ()) or ())
        cuts = select_pp_cuts(program, names, stages)
        if cuts is None:
            raise ValueError(
                "a %d-stage pipeline needs at least %d annotated "
                "boundaries; annotate forward activations with "
                "distributed.pipeline.annotate_pp_cut(var) (got %d "
                "usable: %s)" % (stages, stages - 1, len(names), names))
        cut_vars = list(cuts)
    t = PipelineTranspiler()
    t.transpile(program, cut_vars=cut_vars, pp_axis=pp_axis)
    t.mesh = mesh_flag.mesh_for(axes)
    t.num_microbatches = max(
        int(num_microbatches or FLAGS.pp_microbatches or 1), 1)
    return t


class PipelineTranspiler(object):
    """Cut a Program at boundary vars and train it pipelined.

    Usage::

        t = PipelineTranspiler()
        t.transpile(main_prog, cut_vars=[h1, h2, h3])   # 4 stages
        with api.mesh_guard(mesh):                      # ('pp', S) axis
            loss = t.run_step(exe, feed={'x': xb, 'y': yb},
                              num_microbatches=8)
    """

    def transpile(self, program=None, cut_vars=None, pp_axis='pp'):
        program = program or default_main_program()
        if not cut_vars:
            raise ValueError("cut_vars: list of boundary Variables "
                             "(S-1 cuts for S stages)")
        self.program = program
        self.pp_axis = pp_axis
        self.cut_names = [v.name if isinstance(v, Variable) else str(v)
                          for v in cut_vars]
        block = program.global_block()
        ops = block.ops

        ad_idxs = [i for i, op in enumerate(ops)
                   if op.type == 'autodiff']
        if len(ad_idxs) != 1:
            raise ValueError(
                "PipelineTranspiler needs a single-minimize Program "
                "(one autodiff op), got %d" % len(ad_idxs))
        ad = ops[ad_idxs[0]]
        self.loss_name = ad.attrs['loss_name']
        self.param_names = list(ad.attrs['param_names'])
        self.grad_names = list(ad.attrs['grad_names'])
        persistable = {v.name for v in program.list_vars()
                       if v.persistable}
        sparse = [n for n in self.param_names if n not in persistable]
        if sparse:
            # core/backward.py swaps is_sparse embedding params to their
            # lookup-output vars; the pipeline's per-stage vjp has no
            # sparse_grad_assemble path
            raise ValueError(
                "program uses sparse-grad (is_sparse=True) embeddings "
                "%s — not supported by PipelineTranspiler; build the "
                "embedding with is_sparse=False" % sparse)
        # everything after the autodiff op (grad clip, regularizers,
        # optimizer rules, LR schedules) replays on the pipeline grads
        self.post_ops = ops[ad_idxs[0] + 1:]
        fwd_ops = [op for op in ops[:ad_idxs[0]]
                   if op.attrs.get('op_role', 'forward') == 'forward']

        # program-order cutting: a stage ends at the op that produces
        # its cut var
        S = len(self.cut_names) + 1
        stage_ops = [[] for _ in range(S)]
        cur = 0
        for op in fwd_ops:
            stage_ops[cur].append(op)
            if cur < S - 1 and self.cut_names[cur] in op.output_arg_names:
                cur += 1
        if cur != S - 1:
            raise ValueError(
                "cut vars %s not produced in program order (stopped at "
                "cut %d)" % (self.cut_names, cur))
        self.stage_ops = stage_ops
        self.num_stages = S

        # classify every stage input: produced upstream (must be the
        # stage's cut), a parameter/persistable, or a data feed (@LEN
        # companions of ragged data vars are data vars themselves —
        # layers/io.py creates them with is_data=True)
        self.data_names = sorted({
            v.name for v in program.list_vars()
            if getattr(v, 'is_data', False)})
        self.stage_params = []
        for s in range(S):
            outs = set()
            for op in stage_ops[s]:
                outs.update(op.output_arg_names)
            ins = set()
            for op in stage_ops[s]:
                ins.update(op.input_arg_names)
            ext = ins - outs
            pp = sorted(n for n in ext if n in persistable)
            bad = [n for n in ext
                   if n not in persistable and n not in self.data_names
                   and not (s > 0 and n == self.cut_names[s - 1])]
            if bad:
                raise ValueError(
                    "stage %d reads %s which is neither its cut input, "
                    "a parameter, nor a data feed — choose cuts so each "
                    "stage depends only on the previous cut" % (s, bad))
            for op in stage_ops[s]:
                wp = [n for n in op.output_arg_names if n in persistable]
                if wp:
                    raise ValueError(
                        "stage %d op %s writes persistable %s — "
                        "in-pipeline state updates (e.g. batch_norm "
                        "running stats) are not supported; use a "
                        "stateless forward" % (s, op.type, wp))
            self.stage_params.append(pp)
        self._plan_cache = {}
        return self

    # ------------------------------------------------------------------
    def _iface(self, scope):
        """(flat width, dtype) of the padded stage-interface buffer.
        The buffer carries activations in the CUT VARS' OWN dtype (all
        cuts must agree) so a bf16 program stays bf16 across stage
        boundaries — numerically the same program as single-device."""
        from ..core import datatypes
        block = self.program.global_block()
        widths, dtypes = [], []
        for n in self.cut_names:
            var = block.var(n)
            v = scope.find_var(n)
            if v is not None:
                shp = np.shape(v)[1:]
            else:
                shp = tuple(int(d) for d in var.shape[1:])
            widths.append(int(np.prod(shp)) if shp else 1)
            dtypes.append(jnp.dtype(datatypes.as_numpy_dtype(var.dtype)))
        if len(set(dtypes)) > 1:
            raise ValueError(
                "cut vars mix dtypes %s — the stage interface needs one"
                % sorted({str(d) for d in dtypes}))
        return max(widths), dtypes[0]

    def _stage_fn(self, s, mb, width, cut_shapes, idt):
        """Build stage s's branch: (params_tuple, x_flat, mb_feeds, m)
        -> (y_flat, loss_mb).  The per-microbatch PRNG key rides the
        feed stream (``__rng__``, derived from the executor's
        (seed, step) chain), so stochastic ops are deterministic,
        advance across steps, and replay identically in the 1F1B
        backward recompute — though the stream itself differs from the
        single-device executor's (per-stage op indexing)."""
        prog = self.program
        S = self.num_stages
        ops = self.stage_ops[s]
        cut_in = self.cut_names[s - 1] if s > 0 else None
        cut_out = self.cut_names[s] if s < S - 1 else None
        loss_name = self.loss_name

        def stage(params_tuple, x_flat, mb_feeds, m):
            env = dict(params_tuple[s])
            env.update(mb_feeds)
            if cut_in is not None:
                shp = cut_shapes[s - 1]
                w = int(np.prod(shp[1:])) if len(shp) > 1 else 1
                env[cut_in] = x_flat[:, :w].reshape(shp)
            ctx = ExecutionContext(prog, prog.global_block(),
                                   mb_feeds['__rng__'],
                                   uid_prefix=2000 + s)
            for i, op in enumerate(ops):
                _run_one(op, env, ctx, i)
            if cut_out is not None:
                y = env[cut_out].reshape(mb, -1).astype(idt)
                pad = width - y.shape[1]
                if pad:
                    y = jnp.pad(y, ((0, 0), (0, pad)))
                loss = jnp.float32(0.0)
            else:
                y = jnp.zeros((mb, width), idt)
                loss = jnp.sum(env[loss_name]).astype(jnp.float32)
            return y, loss

        return stage

    # ------------------------------------------------------------------
    def run_mesh_step(self, exe, feed, scope=None):
        """One pipelined step under the :func:`from_mesh` configuration
        (the flag-derived mesh and microbatch count attached there)."""
        mesh = getattr(self, 'mesh', None)
        if mesh is None:
            raise RuntimeError(
                "run_mesh_step needs a from_mesh()-built transpiler "
                "(no mesh attached); use run_step(exe, feed, M, "
                "mesh=...) directly")
        return self.run_step(exe, feed, self.num_microbatches,
                             scope=scope, mesh=mesh)

    def run_step(self, exe, feed, num_microbatches, scope=None,
                 mesh=None):
        """One pipelined train step: split `feed` into M microbatches,
        run the 1F1B fwd+bwd pipeline over the mesh's pp axis, replay
        the Program's optimizer ops on the psum'd grads, write updated
        persistables back to the scope.  Returns the scalar loss."""
        from ..parallel import api
        scope = scope or global_scope()
        mesh = mesh or api.current_mesh()
        if mesh is None or self.pp_axis not in mesh.axis_names:
            raise RuntimeError(
                "run_step needs a mesh_guard with a %r axis"
                % self.pp_axis)
        S = self.num_stages
        if mesh.shape[self.pp_axis] != S:
            raise ValueError(
                "mesh axis %r has %d members but the program was cut "
                "into %d stages" % (self.pp_axis,
                                    mesh.shape[self.pp_axis], S))
        # any second mesh axis runs data-parallel REPLICAS of the
        # pipeline: microbatch contents shard over it, grads pmean
        other = [a for a in mesh.axis_names if a != self.pp_axis
                 and mesh.shape[a] > 1]
        if len(other) > 1:
            raise ValueError(
                "mesh %s has more than one non-pp axis %s — compose "
                "pp with at most one dp axis" % (dict(mesh.shape),
                                                 other))
        dp_axis = other[0] if other else None
        dp = mesh.shape[dp_axis] if dp_axis else 1
        M = int(num_microbatches)

        # expand feed entries exactly like the executor (ragged
        # (data, lengths) tuples and LoDTensors become the padded array
        # plus an @LEN companion), then split every array into M
        # microbatches along the batch axis — the lengths stream with
        # their data
        from ..core.executor import _to_feed_arrays
        block = self.program.global_block()
        flat = {}
        for name, value in feed.items():
            flat.update(_to_feed_arrays(name, value,
                                        block.vars.get(name)))
        feeds = {}
        for name, value in flat.items():
            # keep device-resident arrays on device (the reshape is
            # metadata-only); np.asarray would round-trip them to host
            arr = value if isinstance(value, jax.Array) \
                else np.asarray(value)
            if arr.shape[0] % (M * dp):
                raise ValueError(
                    "batch %d does not split into %d microbatches x "
                    "%d dp replicas" % (arr.shape[0], M, dp))
            feeds[name] = arr.reshape((M, arr.shape[0] // M)
                                      + tuple(arr.shape[1:]))
        mb = next(iter(feeds.values())).shape[1]

        persist_names = sorted(
            v.name for v in self.program.list_vars()
            if v.persistable and scope.has(v.name))
        key = (self.program._uid, self.program.version, M, mb,
               tuple(sorted((n, v.shape, str(v.dtype))
                            for n, v in feeds.items())), mesh)
        plan = self._plan_cache.get(key)
        if plan is None:
            plan = self._build_plan(mesh, M, mb, feeds, persist_names,
                                    dp_axis)
            self._plan_cache[key] = plan
        fn = plan

        # api._place handles the multi-host mesh (each process holds the
        # same global value and materializes only its addressable
        # shards — device_put cannot target non-addressable devices)
        dev = NamedSharding(mesh, P())
        state = {n: api._place(scope.get(n), dev)
                 for n in persist_names}
        feeds_dev = {n: api._place(v, dev) for n, v in feeds.items()}
        # the executor's (seed, step) PRNG chain drives stochastic ops,
        # exactly as in exe.run; the step advances per pipelined step
        key0 = api._place(exe._rng_key(self.program), dev)
        exe._step += 1
        loss, new_state = fn(state, feeds_dev, key0)
        for n, v in new_state.items():
            scope.set(n, v)
        return api._fetch_np(loss)

    def _build_plan(self, mesh, M, mb, feeds, persist_names,
                    dp_axis=None):
        from jax import lax
        S = self.num_stages
        dp = mesh.shape[dp_axis] if dp_axis else 1
        mb_local = mb // dp  # examples per microbatch per dp replica
        width, idt = self._iface(global_scope())
        block = self.program.global_block()
        scope = global_scope()
        cut_shapes = []
        for n in self.cut_names:
            v = scope.find_var(n)
            if v is not None:
                cut_shapes.append((mb_local,) + tuple(np.shape(v)[1:]))
            else:
                cut_shapes.append(
                    (mb_local,) + tuple(int(d)
                                        for d in block.var(n).shape[1:]))
        stage_fns = [self._stage_fn(s, mb_local, width, cut_shapes, idt)
                     for s in range(S)]
        prog = self.program
        post_ops = self.post_ops
        param_names = self.param_names
        grad_names = self.grad_names
        loss_name = self.loss_name
        pp_axis = self.pp_axis

        def pipe_body(params_tuple, feeds):
            if dp_axis is not None:
                # distinct randomness per dp replica (each holds
                # different examples) — the ParallelDo convention of
                # folding the member index into the key
                r = lax.axis_index(dp_axis)
                feeds = dict(feeds)
                feeds['__rng__'] = jax.vmap(
                    lambda k2: jax.random.fold_in(k2, r))(
                        feeds['__rng__'])
            loss, grads = pipeline_train_1f1b(
                stage_fns, params_tuple, feeds, M, pp_axis,
                (mb_local, width), idt)
            if dp_axis is not None:
                # each replica's loss/grads are means over ITS examples;
                # the global mean is their pmean
                loss = lax.pmean(loss, dp_axis)
                grads = jax.tree_util.tree_map(
                    lambda g: lax.pmean(g, dp_axis), grads)
            return loss, grads

        # microbatch CONTENTS shard over dp (axis 1 of [M, mb, ...]);
        # the per-microbatch PRNG keys and params replicate
        feed_specs = {n: P(None, dp_axis) if dp_axis else P()
                      for n in feeds}
        feed_specs['__rng__'] = P()
        pipe = collective.shard_map(
            pipe_body, mesh=mesh, in_specs=(P(), feed_specs),
            out_specs=(P(), P()), check_vma=False)

        def step(state, feeds, key0):
            # per-microbatch keys stream with the feeds so the stage
            # bodies (fwd AND 1F1B recompute) draw identical randomness
            feeds = dict(feeds)
            feeds['__rng__'] = jax.vmap(
                lambda m: jax.random.fold_in(key0, m))(jnp.arange(M))
            params_tuple = tuple(
                {n: state[n] for n in self.stage_params[s]}
                for s in range(S))
            loss, grads = pipe(params_tuple, feeds)
            env = dict(state)
            env[loss_name] = loss
            # a param shared by several stages contributes one partial
            # gradient per stage — SUM them (overwriting would train on
            # the last stage's share only)
            gsum = {}
            for s in range(S):
                for pn, g in grads[s].items():
                    if pn in param_names:
                        g32 = g.astype(jnp.float32)
                        gsum[pn] = gsum.get(pn, 0.0) + g32
            for pn, g in gsum.items():
                gn = grad_names[param_names.index(pn)]
                env[gn] = g.astype(state[pn].dtype)
            ctx = ExecutionContext(prog, prog.global_block(), key0)
            for i, op in enumerate(post_ops):
                _run_one(op, env, ctx, i)
            new_state = {n: env[n] for n in persist_names}
            return loss, new_state

        return jax.jit(step, donate_argnums=(0,))

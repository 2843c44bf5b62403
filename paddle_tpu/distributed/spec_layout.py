"""SpecLayout: the ONE canonical role -> PartitionSpec table.

Reference parity: the Fluid distribute_transpiler hard-coded WHERE each
var lives (trainer vs pserver); the TPU-native question is HOW each var
is partitioned over the device mesh.  This module is the single source
of that answer, the ``SpecLayout`` pattern from SNIPPETS.md [1]
(canonical PartitionSpecs over data/fsdp/tp axes) merged with [3]'s
``batch x model`` mesh setup:

- ``parse_mesh_spec`` normalizes the ``PADDLE_TPU_MESH`` vocabulary
  (``dp=4,tp=2`` / ``fsdp=8``) into an ordered axes tuple — the same
  tuple the pass-manager plan key, the sharding pass, and the executor
  all consume.
- ``SpecLayout`` maps roles to per-dim specs: activations batch-shard
  over ``dp`` (or ``fsdp`` when no dp axis exists — fsdp IS the data
  axis in a pure-ZeRO mesh), parameters shard their largest divisible
  dim over ``fsdp`` (trailing/output dims preferred, the Megatron
  convention ``parallel/api.param_sharding`` already uses), embedding
  tables row-shard over ``(fsdp, tp)`` when both divide.
- ``build_param_specs`` walks a program's persistables into a
  ``{name: spec}`` plan, folding in the TensorParallelTranspiler's
  per-parameter plan (``program._tp_shard_plan``) so tensor-parallel
  heads keep their column split and everything else falls to the fsdp
  rule — ONE spec source, where PR 4's transpiler and the generic fsdp
  heuristic used to disagree.
- ``extend_to_accumulators`` extends any param plan to the optimizer
  accumulators of every sharded param (``<param>_<stem>_<n>`` naming +
  exact shape match — the PR-4 rule, now shared by the tp transpiler
  and the sharding pass): fsdp that shards params but replicates their
  Adam moments saves nothing.

Specs here are plain hashable tuples (one entry per dim: an axis name,
a tuple of axis names, or None) so they can ride op attrs through the
verifier and the infer-cache; ``distributed/mesh_flag.named_sharding``
turns them into jax NamedShardings at jit time.
"""
import re

__all__ = ['parse_mesh_spec', 'SpecLayout', 'build_param_specs',
           'extend_to_accumulators', 'spec_divisor', 'normalize_spec',
           'ACC_SUFFIX', 'AXIS_ALIASES']

# canonical axis vocabulary; aliases normalize on parse so one spelling
# reaches every consumer (plan keys compare strings)
AXIS_ALIASES = {'dp': 'dp', 'data': 'dp',
                'fsdp': 'fsdp', 'zero': 'fsdp',
                'tp': 'tp', 'mp': 'tp', 'model': 'tp',
                'pp': 'pp', 'pipe': 'pp'}

# compact mesh piece: axis name immediately followed by its size
# ('pp2', 'fsdp4') — sugar for the canonical 'axis=size' form
_COMPACT_PIECE = re.compile(r'^([a-z]+?)(\d+)$')

# optimizer accumulator naming: _add_accumulator creates
# unique_name('<param>_<stem>') = '<param>_<stem>_<n>' with the PARAM's
# shape; the stems are the literal _add_accumulator first arguments in
# optimizer.py (ftrl's are plain 'squared'/'linear').  Beta-pow scalars
# are shape [1] and never pass the shape match.
ACC_SUFFIX = re.compile(
    r'(moment\d?|velocity|inf_norm|mean_square|momentum|'
    r'squared|linear|avg_squared_grad|avg_squared_update)_\d+$')


def parse_mesh_spec(s):
    """``'dp=4,tp=2'`` -> ``(('dp', 4), ('tp', 2))`` (ordered, axis
    names canonicalized).  Raises ValueError with the offending piece
    on malformed input — the flag fails loudly, never half-parses."""
    axes = []
    seen = set()
    for piece in str(s).split(','):
        piece = piece.strip()
        if not piece:
            continue
        if '=' not in piece:
            m = _COMPACT_PIECE.match(piece.strip().lower())
            if m is None:
                raise ValueError(
                    "PADDLE_TPU_MESH piece %r is not axis=size "
                    "(or compact axisN, e.g. pp2)" % piece)
            piece = '%s=%s' % (m.group(1), m.group(2))
        name, _, size = piece.partition('=')
        name = AXIS_ALIASES.get(name.strip().lower())
        if name is None:
            raise ValueError(
                "PADDLE_TPU_MESH axis %r is not one of %s"
                % (piece.split('=')[0],
                   sorted(set(AXIS_ALIASES))))
        try:
            size = int(size)
        except ValueError:
            raise ValueError(
                "PADDLE_TPU_MESH size in %r is not an integer" % piece)
        if size < 1:
            raise ValueError(
                "PADDLE_TPU_MESH size in %r must be >= 1" % piece)
        if name in seen:
            raise ValueError(
                "PADDLE_TPU_MESH repeats axis %r" % name)
        seen.add(name)
        axes.append((name, size))
    if not axes:
        raise ValueError("PADDLE_TPU_MESH is set but names no axes")
    return tuple(axes)


def replicated(rank):
    return (None,) * int(rank)


def spec_divisor(spec, axes):
    """How many ways a spec splits one value: the product of the mesh
    sizes of every axis it names.  ``axes`` is {name: size}."""
    if not spec:
        return 1
    d = 1
    for entry in spec:
        if entry is None:
            continue
        for ax in (entry if isinstance(entry, tuple) else (entry,)):
            d *= int(axes.get(ax, 1))
    return d


def normalize_spec(spec, rank, axes):
    """Any PartitionSpec-like (jax P, list, tuple) -> the canonical
    per-dim tuple, padded to ``rank`` and with axes the mesh doesn't
    carry dropped (a tp plan on a dp-only mesh degrades to replication,
    mirroring how vocab_parallel_ce degrades with no tp axis bound)."""
    entries = list(spec or ())
    out = []
    for i in range(int(rank)):
        e = entries[i] if i < len(entries) else None
        if isinstance(e, (list, tuple)):
            kept = tuple(a for a in e if a in axes)
            e = (kept if len(kept) > 1
                 else (kept[0] if kept else None))
        elif e is not None and e not in axes:
            e = None
        out.append(e)
    return tuple(out)


class SpecLayout(object):
    """Role -> spec table over whatever axes the mesh actually has.

    Methods return the canonical tuple spec, or None when the role
    cannot shard on this mesh/shape (caller treats None as replicated).
    """

    def __init__(self, axes, data_axis='dp', fsdp_axis='fsdp',
                 tp_axis='tp', embed_pad=True, pp_axis='pp'):
        self.axes = dict(axes)
        self.data_axis = data_axis if data_axis in self.axes else None
        self.fsdp_axis = fsdp_axis if fsdp_axis in self.axes else None
        self.tp_axis = tp_axis if tp_axis in self.axes else None
        # pp shards TIME (pipeline stages), never tensors: no role
        # below ever names it, so batch/param/embeddings specs are
        # identical with or without a pp axis in the mesh
        self.pp_axis = pp_axis if pp_axis in self.axes else None
        # embed_pad: row-shard lookup tables whose height does NOT
        # divide, relying on the embedding engine's sentinel-row
        # padding (distributed/embedding_engine.pad_height).  The
        # sharding pass pins it to the PADDLE_TPU_EMBED_SHARD mode so
        # an un-padded consumer never sees an indivisible split.
        self.embed_pad = bool(embed_pad)

    @property
    def batch_axis(self):
        """The axis activations batch-shard over: dp when present,
        else fsdp (a pure-fsdp mesh is ZeRO — data-parallel compute
        with sharded state), else nothing."""
        return self.data_axis or self.fsdp_axis

    def axis_size(self, name):
        return int(self.axes.get(name, 1))

    def batch(self, ndim, batch_size=None):
        """Activations/feeds: dim0 over the batch axis when divisible
        (GSPMD handles ragged shards, but an indivisible batch is a
        load imbalance the table should refuse, not paper over)."""
        ax = self.batch_axis
        if ax is None or ndim < 1:
            return None
        if batch_size is not None and batch_size % self.axis_size(ax):
            return None
        return (ax,) + (None,) * (int(ndim) - 1)

    def param(self, shape):
        """fsdp parameters: largest divisible dim over the fsdp axis,
        trailing (output) dims preferred — the Megatron convention
        parallel/api.param_sharding uses, restated over tuple specs."""
        ax = self.fsdp_axis
        if ax is None:
            return None
        size = self.axis_size(ax)
        if size <= 1:
            return None
        shape = tuple(int(d) for d in shape)
        for d in range(len(shape) - 1, -1, -1):
            if shape[d] > 0 and shape[d] % size == 0 and \
                    shape[d] >= 2 * size:
                spec = [None] * len(shape)
                spec[d] = ax
                return tuple(spec)
        return None

    def embeddings(self, shape, allow_pad=True):
        """Embedding tables: ROWS over the model-state axes — SNIPPETS
        [1] ``embeddings(): PS((fsdp, tp), None)`` when both exist,
        degrading to whichever of fsdp/tp the mesh has (a lookup
        table's natural split is its vocab dim: row ownership is what
        makes the all-to-all lookup and the per-shard apply local).
        Non-divisible heights still row-shard when ``embed_pad`` AND
        ``allow_pad`` hold (the engine sentinel-pads the table to the
        next divisible height; callers clear ``allow_pad`` for tables
        with DENSE-grad lookups, whose [V, D] grad would carry the
        indivisible split the verifier rightly rejects); otherwise —
        and for heights too small to matter — falls back to the plain
        param rule."""
        row_axes = tuple(a for a in (self.fsdp_axis, self.tp_axis)
                         if a)
        if row_axes and shape:
            div = 1
            for a in row_axes:
                div *= self.axis_size(a)
            height = int(shape[0])
            if div > 1 and height >= 2 * div and \
                    (height % div == 0 or
                     (self.embed_pad and allow_pad)):
                entry = row_axes if len(row_axes) > 1 else row_axes[0]
                return (entry,) + (None,) * (len(shape) - 1)
        return self.param(shape)


def build_param_specs(program, axes, layout=None):
    """{persistable name: spec} plan for one program on one mesh: the
    tensor-parallel transpiler's plan wins per name (normalized to the
    mesh's axes), the fsdp rule covers the rest, and the whole plan
    extends to optimizer accumulators.  Replicated names are absent."""
    layout = layout or SpecLayout(axes)
    axes_d = layout.axes
    plan = {}
    tp_plan = getattr(program, '_tp_shard_plan', None) or {}
    emb_tables = _embedding_tables(program)
    emb_names = set(emb_tables)
    for var in program.list_vars():
        if not getattr(var, 'persistable', False) or not var.shape:
            continue
        if any(int(d) < 0 for d in var.shape):
            continue  # batch-shaped persistable: not a parameter
        if _accumulator_of(var.name, emb_names):
            # an embedding table's optimizer accumulator must follow
            # the TABLE's row spec (extend_to_accumulators copies it
            # below), never the generic param rule — a moment sharded
            # on D under a row-sharded table could not be sliced in
            # lockstep by the per-shard apply
            continue
        spec = None
        if var.name in tp_plan:
            spec = normalize_spec(tp_plan[var.name], len(var.shape),
                                  axes_d)
            if not any(e is not None for e in spec):
                spec = None  # degraded entirely: fall to the fsdp rule
        if spec is None and var.name in emb_names:
            spec = layout.embeddings(var.shape,
                                     allow_pad=emb_tables[var.name])
        if spec is None:
            spec = layout.param(var.shape)
        if spec is not None:
            plan[var.name] = spec
    return extend_to_accumulators(program, plan)


def _accumulator_of(name, param_names):
    """True when ``name`` is an optimizer-accumulator var of one of
    ``param_names`` (the ``<param>_<stem>_<n>`` naming rule)."""
    for pname in param_names:
        if name.startswith(pname + '_') and \
                ACC_SUFFIX.fullmatch(name[len(pname) + 1:]):
            return True
    return False


def _embedding_param_names(program):
    """Names of lookup-table weights — the params the ``embeddings``
    role ((fsdp, tp) row split) applies to when no explicit tp plan
    claims them."""
    return set(_embedding_tables(program))


def _embedding_tables(program):
    """{lookup-table weight name: every lookup of it is sparse-grad}.
    The bool gates sentinel-padding: a dense-grad lookup (the
    layers.embedding default) autodiffs to a full [V, D] grad that
    would carry the table's indivisible row split — only tables whose
    grads stay SelectedRows (routed through the per-shard apply) may
    pad a non-divisible height."""
    tables = {}
    for block in program.blocks:
        for op in block.ops:
            if op.type != 'lookup_table':
                continue
            sparse = bool(op.attrs.get('is_sparse', False))
            for w in op.inputs.get('W') or ():
                tables[w] = tables.get(w, True) and sparse
    return tables


def extend_to_accumulators(program, plan):
    """Extend a param plan to the optimizer accumulator vars of every
    planned param: a moment/velocity buffer has the param's shape and
    would otherwise replicate — each device holding a full moment per
    sharded param undoes the memory win the plan exists for.  Matched
    by the ``<param>_<stem>_<n>`` accumulator naming plus an exact
    shape match; anything else (beta-pow scalars, unrelated vars)
    keeps its own spec.  Spec-representation agnostic: works for the
    tp transpiler's jax PartitionSpecs and the sharding pass's tuple
    specs alike (values are copied, never inspected)."""
    out = dict(plan)
    if program is None:
        return out
    gb = program.global_block()
    for var in program.list_vars():
        name = var.name
        if not getattr(var, 'persistable', False) or name in out:
            continue
        for pname, spec in plan.items():
            if not name.startswith(pname + '_'):
                continue
            if not ACC_SUFFIX.fullmatch(name[len(pname) + 1:]):
                continue
            try:
                pvar = gb.var_recursive(pname)
            except KeyError:
                continue
            if tuple(var.shape) != tuple(pvar.shape):
                continue
            out[name] = spec
            break
    return out

"""Autoregressive decode engine: continuous batching over a
device-resident paged KV cache (ISSUE 19 tentpole).

The serving stack's generation path used to be the O(T^2) one: re-run
the full context for every emitted token.  This module is the standard
inference-throughput fix for decoder-only LMs, TPU-native:

- **prefill/decode split** — a prompt runs ONCE through a full-context
  forward (per-bucket AOT-compiled, page-size-multiple bucket ladder so
  only ~log2 prefill shapes ever compile), its per-layer rows land in
  claimed cache pages, and its last-position logits yield the first
  token (the TTFT moment).  Every later token is one batched decode
  step: embed S current tokens, append their rows to the cache, and
  attend over pages (ops/attention.py ``paged_attention``, or the
  block's own).
- **paged cache** — what a position caches is the block's to describe
  (``cache_rows``: a K and a V row of heads x head_dim for full
  multi-head attention, one latent row shared by all heads for latent
  attention).  One page pool PER ROW PER LAYER, each its own device
  buffer ``[num_pages + 1, page_size, width]`` (lane-dense: a cached
  position is one contiguous row), with a HOST-side page table and
  free list.  Streams claim
  ceil(span/page_size) pages at admission and free them the step they
  finish; a stream's pages need not be contiguous, so the pool packs
  mixed-length streams without fragmentation-driven copies.  The pools
  are **donated chunk→chunk** through every compiled prefill-pack and
  decode step (``donate_argnums``), every buffer aliased to its own
  output: a write scatters rows on the page axis of its layer's buffer
  and a read gathers pages straight from it, so no program copies,
  slices or re-lays-out a pool — the cache never round-trips to host
  and never double-buffers.
- **continuous batching** — admission happens at STEP granularity: a
  queued stream joins the running batch the moment a slot and pages
  free up, and a finished stream's slot is reusable the very next step.
  Throughput is work-conserving instead of generation-batch-barriered;
  ``static_batching=True`` on the server reproduces the barriered
  baseline for the A/B the decode bench reports.
- **chunked prefill** (on with ``prefill_chunk_tokens`` or the prefix
  cache) — a prompt runs as chunks on a grid anchored at position 0,
  at most ``chunk_tokens`` of them a tick, and the tick's last chunk
  carries the running streams' decode rows through the same pass: a
  tick with a prompt pending is ONE program that reads the weights
  once (``chunk``), a tick without one is ``step``.  Rows never mix in
  a layer, so each stream gets what the two programs run one after the
  other would give it.

There is ONE definition of a decoder's serving path: which decoder is
served is a block description (inference/blocks.py: ``OptBlock``, the
default, ``OlmoeBlock``, ``DotsVlmBlock``, ``LagunaBlock``,
``OuroBlock``, ``JambaBlock``) that supplies the layer's equations, what a position
caches and how to attend over it, and the engine's ``prefill``,
``chunk`` and ``step`` are one loop over layers around them.  No code
here names a model or a parameter.

Where a block runs its layers SEVERAL times a token over the one set of
weights (it has ``between`` and says ``ut_steps``), weight layers and
cache layers are two counts: the weights are ``n_layers`` layers, held
and passed once, and a position is cached once a layer a recurrence
(``PagedKVCache.slots``).  Slot (t, l) is a range of pages of layer l's
own buffers, reached through the stream's one page table shifted by t
ranges, so the allocator, the page tables and the kernels know nothing
of recurrences; the recurrences are one traced body under ``lax.scan``
in each of the three programs (``_layers``).

Where a block's layers differ in what they KEEP (``layer_kinds``: some
every position, some a window of the newest), the cache holds a page
group a kind, sized apart.  A stream holds pages of its whole context in
the one and a RING in the other (``ring_pages``: the window's positions
before a call's first row, the call's own rows, a page to spare;
logical page j in column ``j % ring_pages``), claimed together at
admission and given back together; the page table a call takes is the
stream's pages and then its ring (``table_row``), and each layer's
attend is handed its own group's part.  A block with one kind sees none
of this: one group, one table, as before.

Where some layers keep a STATE A STREAM and nothing a position
(``layer_kinds``: ``'state'``; a state-space mixer), the cache holds a
third group that does not page: pools ``[layers of a run, max_streams +
1, ...]`` a state row a run of such layers (``PagedKVCache.state``),
indexed by the server's own batch slot, donated and updated in place as
the page pools are.  A run is walked under one ``lax.scan``
(``_state_run``); a step updates row r's state for slot r, a chunk those
and then its own stream's (``_advance_rows``, ``_advance_chunk``), told
which by the slot that comes with the stream's pages (``_slot_of``); a
chunk at position 0 starts from zeros (``_from_zero``), so no slot is
ever reset and a preempted stream recomputes its state with its pages.

Everything device-facing is AOT-compiled at ``warmup()`` via
``jit(...).lower(...).compile()`` — the serving loop only ever calls
precompiled executables, and ``stats()['compiles_after_warmup']``
counts any miss instead of hiding a multi-second stall.
"""
import itertools
import threading
import time
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..analysis import lockdebug as _lkd
from ..compile_cache import enable_compile_cache
from ..transpiler.memory_model import page_pool_bytes
from .blocks import OptBlock

__all__ = ['DecodeEngine', 'DecodeServer', 'DecodeStream',
           'extract_params', 'decode_buckets', 'PrefixCache',
           'PromptTooLongError']

_server_seq = itertools.count()


class PromptTooLongError(ValueError):
    """A submitted prompt cannot be served: longer than the top prefill
    bucket (monolithic prefill), or prompt+max_new exceeds the model
    context.  Subclasses ValueError so pre-existing callers' handlers
    keep working; raised in the SUBMITTING thread, never the worker."""


def extract_params(scope, n_layers, block=None):
    """Pull a model's fixed-name parameters out of a scope as a plain
    {name: jax.Array} dict — the engine's weights.  The manifest is the
    block description's ``names`` (inference/blocks.py; ``OptBlock``'s
    when none is given)."""
    names = (block or OptBlock).names(n_layers)
    return {n: jnp.asarray(scope.get(n)) for n in names}


def decode_buckets(page_size, top):
    """The prefill bucket ladder: page-size multiples doubling up to
    ``top`` (inclusive) — [P, 2P, 4P, ...].  Prompts pad to the next
    bucket so only ~log2 prefill shapes ever compile."""
    page_size, top = int(page_size), int(top)
    if top < page_size or top % page_size:
        raise ValueError(
            "prefill bucket top %d must be a multiple of page_size %d"
            % (top, page_size))
    sizes = [page_size]
    while sizes[-1] < top:
        sizes.append(min(sizes[-1] * 2, top))
    return sizes


_ROOMY = []    # the function ``_in_a_roomy_frame`` calls through, once built


def _in_a_roomy_frame(f):
    """``f()``, called from a frame with 65,536 unused local slots.

    CPython (3.11 and later) keeps a thread's frames in chunks of 16 KB
    and unmaps a chunk the moment the first frame in it returns, so a
    call path that happens to start on a chunk's edge maps and unmaps
    16 KB at every call.  Tracing and lowering an engine program with a
    Pallas kernel in it makes a few hundred thousand calls a couple of
    hundred frames deep: what that costs followed the DEPTH it was
    started from, a frame more or fewer in the caller moving it by a
    third, and on the chip's host, where an unmap is dear, it was most
    of set-up (a cell's seven programs, all from the compile cache: 72 s
    from a plain frame, 15 s from this one; PERF.md section 6, PR 56).
    A frame this large gets a 1 MB chunk to itself, and all that ``f``
    calls lives in the half of it the frame leaves free, wherever the
    caller stood."""
    if not _ROOMY:
        scope = {}
        exec('def roomy(f):\n    return f()\n    %s = None'
             % ' = '.join('_%d' % i for i in range(1 << 16)), scope)
        _ROOMY.append(scope['roomy'])
    return _ROOMY[0](f)


# what jax says of one compile and its persistent cache: every look-up
# records the first, a look-up that found the executable the second
# (jax 0.9.0 ``compiler.compile_or_get_cached``; ``cache_misses`` is
# recorded only where the new entry passes the thresholds and is written)
_CACHE_ASKED = '/jax/compilation_cache/compile_requests_use_cache'
_CACHE_HIT = '/jax/compilation_cache/cache_hits'
_heard = threading.local()  # .events: of the compile open on this thread
_listening = []             # non-empty once ``_hear`` is registered


def _hear(event, **_kw):
    events = getattr(_heard, 'events', None)
    if events is not None:
        events.add(event)


def _staged(jitted, args):
    """``jitted.lower(*args).compile()`` a stage a span: Python to
    jaxpr, jaxpr to StableHLO, and the backend, whose span says what the
    persistent cache did (``cache``: ``'hit'``, a load; ``'miss'``, XLA
    compiled; ``'off'``, jax looked nothing up).  With metrics off the
    spans are the shared no-op, and nothing listens."""
    with _obs.span('decode.compile.trace'):
        traced = jitted.trace(*args)
    with _obs.span('decode.compile.lower'):
        lowered = traced.lower()
    if not _obs.enabled():
        return lowered.compile()
    if not _listening:
        jax.monitoring.register_event_listener(_hear)
        _listening.append(_hear)
    said = {}
    events = _heard.events = set()
    try:
        with _obs.span('decode.compile.backend', args=said):
            compiled = lowered.compile()
            said['cache'] = 'hit' if _CACHE_HIT in events else \
                'miss' if _CACHE_ASKED in events else 'off'
    finally:
        _heard.events = None
    return compiled


class _PageGroup(object):
    """A free list over ``num_pages`` pages and, one past them, the
    group's TRASH page (padded page-table entries and inactive slots
    direct their writes there, so a compiled program needs no masking
    on its scatter).  Host state of the server's worker thread."""

    def __init__(self, num_pages):
        self.num_pages = int(num_pages)
        self.trash = self.num_pages
        self._free = list(range(self.num_pages))

    def free_pages(self):
        return len(self._free)

    def alloc(self, n):
        """Claim ``n`` pages or None when the group can't supply them —
        the caller (admission) keeps the stream queued, never drops."""
        if n > len(self._free):
            return None
        pages, self._free = self._free[:n], self._free[n:]
        return pages

    def free(self, pages):
        self._free.extend(pages)


class PagedKVCache(_PageGroup):
    """Device page pools + host free list.  ``rows`` is what a position
    caches in every layer, ``((name, width), ...)`` as the block
    describes it (two rows ``k`` and ``v`` of ``n_heads * head_dim``
    when none is given); ``pools`` holds, in that order, one list a row
    of ``n_layers`` jax arrays ``[num_pages + 1, page_size, width]``,
    one buffer per layer, also reachable by the row's name
    (``cache.k``, ``cache.latent``).  The engine threads them through
    its donated compiled calls (each aliased to its own output, written
    by a scatter on its page axis).  The minor dimension is the whole
    row: a ``[..., n_heads, head_dim]`` pool with head_dim under 128
    lanes is held page-minor by the TPU and every program that touches
    it re-lays-out the whole pool at its edge (PERF.md section 6,
    PR 25).  The free list / page tables are host state (the server's
    worker thread owns them — no lock needed beyond the server's
    own).

    The cache itself is the group of the layers that keep every
    position (``alloc``, ``free``, ``trash``, ``free_pages``).  Layers
    that read a window of the newest positions (``window_layers``) are
    a second group, ``cache.window``, sized apart (``window_pages``)
    with a free list and a trash page of its own: their buffers are
    ``[window_pages + 1, page_size, width]``, and a stream holds a ring
    of them, not a page a page of its context.

    A block that runs its ``n_layers`` weight layers ``recurrences``
    times a token caches a position once a layer a RECURRENCE:
    ``slots`` = recurrences x n_layers cache slots.  Slot (t, l) is
    pages ``t * (pages + 1) ..`` of layer l's buffers, which are
    ``recurrences`` times as long: recurrence t writes and reads
    through the stream's page table shifted by that many pages
    (``shift``), so one table, one free list and one claim serve every
    slot, each recurrence has a trash page of its own, and a kernel
    that takes (pool, page table) takes a slot as it takes a layer."""

    def __init__(self, n_layers, num_pages, page_size, n_heads=None,
                 head_dim=None, dtype=jnp.float32, rows=None,
                 window_layers=(), window_pages=0, recurrences=1,
                 state_rows=(), state_runs=(), max_streams=0):
        _PageGroup.__init__(self, num_pages)
        self.n_layers = int(n_layers)
        self.recurrences = int(recurrences)
        self.page_size = int(page_size)
        if rows is None:
            rows = (('k', n_heads * head_dim), ('v', n_heads * head_dim))
        self.rows = tuple((str(n), int(w)) for n, w in rows)
        self.dtype = jnp.dtype(dtype)
        self.window_layers = frozenset(int(i) for i in window_layers)
        self.window = _PageGroup(window_pages) if self.window_layers \
            else None
        # layers that cache nothing a position and a state a STREAM:
        # no page buffer (None holds their place in a row's list), and a
        # row of ``state`` a RUN of them, [layers of the run, max_streams
        # + 1, *shape], indexed by the server's slot (the last row is
        # the idle rows' trash)
        self.state_rows = tuple((str(n), tuple(int(d) for d in shape),
                                 jnp.dtype(dt))
                                for n, shape, dt in state_rows)
        self.state_runs = tuple((int(f), int(n)) for f, n in state_runs)
        self.state_layers = frozenset(
            i for f, n in self.state_runs for i in range(f, f + n))
        self.state_slots = int(max_streams)
        self.state = [
            [jnp.zeros((n, self.state_slots + 1) + shape, dt)
             for _f, n in self.state_runs]
            for _name, shape, dt in self.state_rows]
        self.pools = [
            [None if i in self.state_layers else
             jnp.zeros((self.recurrences
                        * (self.group_of(i).num_pages + 1),
                        self.page_size, w), dtype)
             for i in range(self.n_layers)] for _n, w in self.rows]

    @property
    def slots(self):
        """Cache layers: what a position is cached in, a weight layer a
        recurrence (the layers that keep a state a stream cache no
        position and are not counted)."""
        return self.recurrences * (self.n_layers - len(self.state_layers))

    def state_bytes_per_stream(self):
        """What a stream holds in the state layers, all of them."""
        return len(self.state_layers) * sum(
            int(np.prod(shape)) * dt.itemsize
            for _n, shape, dt in self.state_rows)

    def shift(self, pages, t):
        """Page ids ``pages`` (one array a group, trash entries too) as
        recurrence ``t`` (a number or a traced scalar) finds them in the
        buffers; a cache of one recurrence hands them back as they
        are."""
        if self.recurrences == 1:
            return pages
        return [p + t * (g.num_pages + 1)
                for p, g in zip(pages, self.groups)]

    @property
    def groups(self):
        """The page groups: this one, then the window layers' if any."""
        return [self] if self.window is None else [self, self.window]

    def group_of(self, layer):
        """The page group that holds ``layer``'s rows."""
        return self.window if layer in self.window_layers else self

    def __getattr__(self, name):
        # a row's buffers by the row's name (only reached for names the
        # instance does not have)
        for j, (row, _w) in enumerate(self.__dict__.get('rows', ())):
            if row == name:
                return self.pools[j]
        # (a state row's buffers, a run each, likewise)
        for j, row in enumerate(self.__dict__.get('state_rows', ())):
            if row[0] == name:
                return self.state[j]
        raise AttributeError(name)

    def row_widths(self):
        return [w for _n, w in self.rows]

    def group_bytes(self):
        """Resident bytes a group: cache slots x pages x page_size x
        the rows' widths x dtype (trash pages included — they are
        resident)."""
        n_window, T = len(self.window_layers), self.recurrences
        out = {'full': page_pool_bytes(
            self.num_pages + 1, self.page_size, dtype=self.dtype,
            n_layers=T * (self.n_layers - n_window
                          - len(self.state_layers)),
            row_widths=self.row_widths())}
        if self.state_layers:
            # a state a slot a state layer, the trash row too
            out['state'] = (self.state_slots + 1) \
                * self.state_bytes_per_stream()
        if self.window is not None:
            out['window'] = page_pool_bytes(
                self.window.num_pages + 1, self.page_size,
                dtype=self.dtype, n_layers=T * n_window,
                row_widths=self.row_widths())
        return out

    def resident_bytes(self):
        """Golden closed form, every group's."""
        return sum(self.group_bytes().values())


class _PrefixNode(object):
    """One cached page: the KV of ``key`` (a page_size token tuple)
    computed under the prefix its trie path spells."""
    __slots__ = ('key', 'page', 'parent', 'children', 'refs',
                 'last_use')

    def __init__(self, key, page, parent):
        self.key = key
        self.page = page
        self.parent = parent
        self.children = {}
        self.refs = 0
        self.last_use = 0


class PrefixCache(object):
    """Radix trie over token sequences mapping page-aligned prefixes to
    ref-counted KV pages (RadixAttention-style reuse over this engine's
    page-table indirection).

    Host state owned by the decode worker thread, like the pool free
    list — no lock of its own.  A node's page holds the KV a prefill
    computed for ``key`` under the node's path; because chunked prefill
    runs on an absolute position grid, that KV is BITWISE identical for
    every stream sharing the prefix, so a hit claims the pages by
    reference and reproduces the cold logits exactly.  Ownership rules:

    - ``match`` acquires a ref per matched node; the stream holds it
      until retire (or preemption) and ``release``s it.
    - ``insert`` ADOPTS the caller's page for any prefix page not yet
      cached (ownership moves to the trie); an already-cached page is
      skipped — the caller keeps its private copy and frees it itself.
    - ``evict`` only ever frees unreferenced LEAF pages, LRU-first; a
      referenced page (refs > 0) or an interior node is untouchable.
    """

    def __init__(self, page_size):
        self.page_size = int(page_size)
        self._root = _PrefixNode(None, None, None)
        self._clock = 0
        self.cached_pages = 0

    def _tick(self):
        self._clock += 1
        return self._clock

    def match(self, tokens):
        """Longest cached page-aligned prefix of ``tokens``: returns
        (pages, nodes) root-first, one ref acquired per node."""
        P = self.page_size
        node, pages, nodes = self._root, [], []
        t = len(tokens)
        i = 0
        while i + P <= t:
            child = node.children.get(
                tuple(int(x) for x in tokens[i:i + P]))
            if child is None:
                break
            child.refs += 1
            child.last_use = self._tick()
            nodes.append(child)
            pages.append(child.page)
            node = child
            i += P
        return pages, nodes

    def release(self, nodes):
        for n in nodes:
            n.refs -= 1
            n.last_use = self._tick()

    def insert(self, tokens, pages, acquire=False):
        """Walk the full pages of ``tokens`` (pages[i] backs page i),
        creating nodes for uncached pages.  Returns (nodes,
        adopted_indices): the caller no longer owns pages at adopted
        indices.  With ``acquire`` every node on the path gains a ref
        (the caller must later ``release`` the returned nodes)."""
        P = self.page_size
        node, nodes, adopted = self._root, [], []
        n_full = min(len(tokens) // P, len(pages))
        for i in range(n_full):
            key = tuple(int(x) for x in tokens[i * P:(i + 1) * P])
            child = node.children.get(key)
            if child is None:
                child = _PrefixNode(key, int(pages[i]), node)
                node.children[key] = child
                adopted.append(i)
                self.cached_pages += 1
            if acquire:
                child.refs += 1
            child.last_use = self._tick()
            nodes.append(child)
            node = child
        return nodes, adopted

    def evict(self, want):
        """Free up to ``want`` pages from unreferenced leaves,
        least-recently-used first.  Returns the freed page ids (the
        caller hands them back to the pool free list).  Referenced
        pages are never candidates — pool pressure can starve a new
        admission, but never corrupt a live stream's context."""
        freed = []
        while len(freed) < int(want):
            best, stack = None, list(self._root.children.values())
            while stack:
                n = stack.pop()
                if n.children:
                    stack.extend(n.children.values())
                elif n.refs == 0 and (best is None
                                      or n.last_use < best.last_use):
                    best = n
            if best is None:
                break  # every leaf referenced: nothing evictable
            del best.parent.children[best.key]
            freed.append(best.page)
            self.cached_pages -= 1
        return freed


class DecodeEngine(object):
    """Compiled prefill/pack/decode executables over one weight set.

    The three programs that write the page pools — ``pack``, ``chunk``
    and ``step`` — take ``cache.pools`` (a list of per-layer buffers for
    each row the block caches: ``cache.k`` and ``cache.v``, or
    ``cache.latent``) as donated arguments, one argument a row, and
    return them: a layer's new rows are scattered on the page axis of
    that layer's own buffers and attention reads pages from the same
    buffers, so the pools are updated in place.  The ``decode.compile``
    span of every program records ``alias_bytes``, ``temp_bytes`` and
    ``pool_bytes``: in place means the first equals the last and the
    scratch stays under what the program gathers.

    Which decoder is served is the ``block`` handed in (a description
    from inference/blocks.py; ``OptBlock``, the layer of
    models/transformer.py, when none is).  Prefill, chunk and step are
    ONE loop over layers (``_layers``) that differs only in where a
    position's rows are written and which of the block's three attends
    reads them.  Every program
    takes the weights as its first operand (``self.params``, placed
    once): no executable holds a copy, each can be kept in the
    persistent compile cache, and the ``argument_bytes`` of its
    ``decode.compile`` span count them.

    An engine call hands its executable the host's numpy arrays as they
    are, waits for the program, and copies back in ONE transfer what its
    caller reads (``_fetch``: ids, routing counts, a prompt's last row;
    the span's ``fetched_bytes``).  The decode rows' ``[S, V]`` logits
    stay on the device for whoever indexes them.  So do the decode
    rows' inputs of the next call (``_next_rows``): a call passes each
    of a step's three arrays from the host only where the device does
    not already hold that array's content (``_decode_operands``; the
    span's ``host_operands``, the totals in ``calls``).

    Not thread-safe by design: exactly one caller (the DecodeServer
    worker) drives it, and the page pools move through donated
    arguments — concurrent calls would use donated buffers.
    """

    def __init__(self, params, n_layers, n_heads, page_size=None,
                 num_pages=None, max_streams=None, prefill_bucket=None,
                 prefix_cache=None, prefill_chunk_tokens=None,
                 dtype=jnp.float32, max_seq=None, block=None,
                 window_pages=None):
        from ..flags import FLAGS
        enable_compile_cache()
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.block = block = block or OptBlock(self.n_heads)
        # (a block that says its heads layer by layer has no one count)
        if block.n_heads is not None and block.n_heads != self.n_heads:
            raise ValueError("block has %d heads, engine %d"
                             % (block.n_heads, self.n_heads))
        self.params = self._place(params)
        self.sizes = sizes = block.sizes(self.params)
        # a block that says ``between`` runs its layers ``ut_steps``
        # times a token over the one set of weights, each recurrence
        # into cache slots of its own (``_layers``)
        self.looped = hasattr(block, 'between')
        self.ut_steps = int(block.ut_steps) if self.looped else 1
        self.d_model = sizes['d_model']
        self.vocab_size = sizes['vocab_size']
        table = sizes.get('positions')
        if not (max_seq or table):
            raise ValueError("a block without a position table "
                             "needs max_seq")
        self.max_seq = int(max_seq or table)
        if table and self.max_seq > table:
            raise ValueError("max_seq %d exceeds the position "
                             "table's %d rows" % (self.max_seq, table))
        # routed-expert totals over the engine's life (``_routing``);
        # all zero for a block without experts
        self.routing = {'assignments': 0, 'all_assignments': 0,
                        'max_load': 0, 'touched': 0.0, 'steps': 0}
        # KV pages over the engine's decode steps (``_kv_pages``): the
        # running slots' live ones, and the page tables' entries; where
        # there are rings, their live pages and the columns that took a
        # newer page of their stream's (``_recycled``)
        self.kv_pages = {'live': 0, 'table': 0, 'window_live': 0,
                         'window_recycled': 0}
        # the calls that ran decode rows, and how many of their three
        # arrays went in from the host (``_decode_operands``)
        self.calls = {'step_calls': 0, 'step_host_operands': 0}
        # passes over a weight layer that the decode rows' calls ran,
        # as their programs' fetches count them (``_fetch``)
        self.loop_passes = 0
        # what the last such call left on the device for the next one,
        # and the host's copies of it: (ids, page tables, context
        # lengths) twice, or None before the first
        self._held = None
        self.page_size = int(page_size or FLAGS.decode_page_size)
        self.max_streams = int(max_streams or FLAGS.decode_max_streams)
        if self.max_seq % self.page_size:
            raise ValueError("max_seq %d not a page_size %d multiple"
                             % (self.max_seq, self.page_size))
        self.pages_per_stream = self.max_seq // self.page_size
        if num_pages is None:
            num_pages = self.max_streams * self.pages_per_stream
        top = int(prefill_bucket or FLAGS.decode_prefill_bucket)
        self.buckets = decode_buckets(self.page_size,
                                      min(top, self.max_seq))
        self.prefix_enabled = bool(FLAGS.decode_prefix_cache
                                   if prefix_cache is None
                                   else prefix_cache)
        self.chunk_tokens = int(FLAGS.decode_prefill_chunk_tokens
                                if prefill_chunk_tokens is None
                                else prefill_chunk_tokens)
        # chunked prefill path: active when either feature is on.  The
        # chunk GRID is anchored at absolute position 0, so a prefix
        # hit's tail chunks are an exact suffix of the cold chunk list
        # — the foundation of bitwise hit-vs-cold parity.  Both off ->
        # the monolithic bucket prefill, verbatim.
        self.chunked = self.prefix_enabled or self.chunk_tokens > 0
        if self.chunked:
            g = max(self.page_size,
                    (self.chunk_tokens // self.page_size)
                    * self.page_size)
            self.chunk_grid = min(g, self.buckets[-1])
            top = next(b for b in self.buckets
                       if b >= self.chunk_grid)
            self.chunk_buckets = [b for b in self.buckets if b <= top]
        else:
            self.chunk_grid = None
            self.chunk_buckets = []
        # the kinds of the block's layers: those that read a window of
        # the newest positions are a page group of their own, where a
        # stream holds a RING (logical page j in column j % ring_pages):
        # the window's positions before a call's first row and the
        # call's own rows, and a page to spare for a window that starts
        # inside one
        kinds = block.layer_kinds(self.n_layers) \
            if hasattr(block, 'layer_kinds') else ()
        window_layers = [i for i, k in enumerate(kinds) if k == 'window']
        # layers that keep a state a STREAM and nothing a position: runs
        # of them, each walked under one ``lax.scan`` (``_layers``)
        self.state_runs = list(block.state_runs(self.n_layers)) \
            if 'state' in kinds else []
        if self.state_runs and self.prefix_enabled:
            raise ValueError(
                "prefix_cache=True with layers that keep a state a "
                "stream: what a shared prefix leaves there is the state "
                "at its last token, not pages another stream can claim "
                "(no state is kept at page boundaries); serve this "
                "block with the prefix cache off")
        if self.state_runs and self.looped:
            raise ValueError("state layers under a block that loops over "
                             "its layers are not built")
        self.ring_pages = 0
        if window_layers:
            if self.prefix_enabled:
                raise ValueError(
                    "prefix_cache=True with layers that read a window: "
                    "what a shared prefix leaves in a window layer is "
                    "the ring of whoever computed it, not pages another "
                    "stream can claim; serve this block with the prefix "
                    "cache off")
            rows = self.chunk_grid if self.chunked else 1
            self.ring_pages = min(
                -(-(block.window - 1 + rows) // self.page_size) + 1,
                self.pages_per_stream)
        self.cache = PagedKVCache(
            self.n_layers, num_pages, self.page_size, dtype=dtype,
            rows=block.cache_rows(sizes), window_layers=window_layers,
            window_pages=self.max_streams * self.ring_pages
            if window_pages is None else window_pages,
            recurrences=self.ut_steps,
            state_rows=block.state_rows(sizes) if self.state_runs else (),
            state_runs=self.state_runs, max_streams=self.max_streams)
        # what ``_layers`` walks: a layer, or (run, first layer, layers)
        # where a run of state layers starts
        at = {f: (r, f, n) for r, (f, n) in enumerate(self.state_runs)}
        self._walk = [at.get(i, i) for i in range(self.n_layers)
                      if i in at or i not in self.cache.state_layers]
        # the layers that cache K/V, in order (``pack``)
        self._kv_layers = [i for i in self._walk if isinstance(i, int)]
        # a layer's group: 0 the pages of the whole context, 1 the rings
        self._group = [int(i in self.cache.window_layers)
                       for i in range(self.n_layers)]
        if window_layers:
            sizes['table_pages'] = {'full': self.pages_per_stream,
                                    'window': self.ring_pages}
        self.prefix = PrefixCache(self.page_size) \
            if self.prefix_enabled else None
        self.compiles_total = 0
        self._compiles_at_warmup = None
        self._prefill = {}   # bucket -> compiled (params, tokens, last)
        self._pack = {}      # bucket -> compiled (*pools, *rows, pages)
        self._chunk = {}     # bucket -> compiled chunked-prefill fn
        self._step = None
        # a decode step's operands with every slot idle (all-trash page
        # tables): what warm-up runs, and what a chunk carries when it
        # carries no decode rows
        S = self.max_streams
        self.idle_row = self.table_row(())   # a slot that holds nothing
        self._idle_step = (
            jnp.zeros((S,), jnp.int32),
            jnp.asarray(np.tile(self.idle_row, (S, 1))),
            jnp.zeros((S,), jnp.int32))
        # where there are state layers, a chunk's last operand: the row
        # of the state pools its stream holds; warm-up's is the trash row
        self._idle_slot = (jnp.int32(S),) if self.state_runs else ()

    # -- compiled function builders ------------------------------------

    def _compile(self, fn, *args, donate=(), bucket=None, **span_args):
        span_args.update(program=fn.__name__, bucket=bucket)
        # the block's part in the program: which attention it takes
        span_args.update(self.block.describe(
            fn.__name__, self.sizes, jax.default_backend(),
            self.page_size, self.cache.dtype))
        if self.looped:
            span_args.update(
                ut_steps=self.ut_steps, cache_slots=self.cache.slots,
                weight_layers=self.n_layers,
                cache_bytes_per_position=self.cache.slots * sum(
                    self.cache.row_widths()) * self.cache.dtype.itemsize)
        if self.state_runs:
            cache = self.cache
            kinds = self.block.layer_kinds(self.n_layers)
            span_args.update(
                layer_kinds={k: kinds.count(k) for k in sorted(set(kinds))},
                state_rows={n: list(shape)
                            for n, shape, _dt in cache.state_rows},
                state_bytes_per_stream=cache.state_bytes_per_stream(),
                state_pool_bytes=cache.group_bytes()['state'],
                # the form the state layers' scan takes, and in how many
                # layers: a step has none (its rows are one token each)
                ssm={self.block.scan_path(
                    self.sizes, jax.default_backend(), bucket):
                    len(cache.state_layers)}
                if fn.__name__ in ('prefill', 'chunk') else {})
        with _obs.span('decode.compile', args=span_args):
            compiled = _in_a_roomy_frame(lambda: _staged(
                jax.jit(fn, donate_argnums=donate), args))
            # whether the pools are updated in place, as the compiler
            # declares it: the donated bytes it aliased to outputs and
            # the scratch the program needs beside its arguments
            mem = compiled.memory_analysis()
            span_args.update(
                temp_bytes=int(mem.temp_size_in_bytes),
                alias_bytes=int(mem.alias_size_in_bytes),
                argument_bytes=int(mem.argument_size_in_bytes),
                pool_bytes=self.cache.group_bytes()
                if self.ring_pages or self.state_runs
                else self.cache.resident_bytes())
        self.compiles_total += 1
        return compiled

    # -- a stream's pages, group by group -------------------------------

    def table_row(self, pages):
        """A stream's row of a page table, int32 [MPP (+ ring_pages)]:
        its pages of the whole context, then (where there are window
        layers) its ring, each padded with its own group's trash page.
        ``pages`` is the stream's page list, or with a ring the pair
        (pages, ring)."""
        mpp, ring = self.pages_per_stream, ()
        if self.ring_pages:
            pages, ring = pages or ((), ())
        row = np.full((mpp + self.ring_pages,), self.cache.trash, np.int32)
        n = min(len(pages), mpp)
        row[:n] = pages[:n]
        if self.ring_pages:
            row[mpp:] = self.cache.window.trash
            row[mpp:mpp + len(ring)] = ring
        return row

    def ring_for(self, span):
        """The ring pages a stream of ``span`` positions claims."""
        return min(self.ring_pages, -(-int(span) // self.page_size))

    def _tables(self, pt):
        """A page table [..., MPP (+ ring_pages)] -> one table a group."""
        if not self.ring_pages:
            return (pt,)
        mpp = self.pages_per_stream
        return pt[..., :mpp], pt[..., mpp:]

    def _trashes(self):
        return [g.trash for g in self.cache.groups]

    def _layers(self, params, x, positions, active, attend, pools=(),
                pages=(), decoding=None, advance=None):
        """A block's layers over x [rows, D], as many times as the block
        runs them (``ut_steps`` recurrences over the one set of weights,
        recurrence t into cache slots of its own).  ``attend(i, q, rows,
        pools, *pages)`` is where prefill, chunk and step differ: it
        writes the rows layer i caches for these positions into
        ``pools`` (lists of the program's own, one a cache row) and
        returns the attention output and what a whole-prompt prefill
        keeps for ``pack`` (a tuple, one array a cache row, or ``()``).
        ``pages`` is what the program reaches the pools through (page
        ids and page tables, each one array a group): a recurrence
        hands ``attend`` each as that recurrence finds it
        (``PagedKVCache.shift``), so ``attend`` knows nothing of
        recurrences.  Where a recurrence ends the block's ``between``
        closes it (the stream the next one starts from, and the exit
        gate of every row).

        One recurrence is the loop over layers as it stands; several
        are ONE traced body under ``lax.scan`` with the pools as its
        carry, whatever the block (on the chip, 12 layers four times:
        the six programs compile in 41 s where 48 unrolled passes took
        148, and a step is 9.8 ms on the device where theirs was 10.9:
        PERF.md section 6, PR 56).

        A RUN of state layers (``_walk``) is one ``lax.scan`` over the
        run's stacked weights (``_state_run``), whatever its length, the
        run's state pools its carry: ``pools`` holds them after the page
        pools, a state row each, and ``advance`` is where the three
        programs differ there.

        Returns (x, pools, kept, extra): ``kept`` what a prefill keeps,
        a list a cache row of one array a layer, [rows, width] or under
        the scan [ut_steps, rows, width] (``_by_slot`` stacks them for
        ``pack``), ``extra`` what the program
        returns beside its usual outputs: the routing counts [layers
        that route x recurrences, n] where the block routes, then for a
        block that loops the exit distribution's mean over the
        ``active`` rows among the first ``decoding`` (the decode rows
        lead a chunk's; None: every row), [ut_steps] float32."""
        blk, T = self.block, self.ut_steps

        def recurrence(t, x, pools):
            pools = [list(pool) for pool in pools]
            # the page pools, a cache row each, then the state pools
            paged = pools[:len(self.cache.rows)]
            at = [self.cache.shift(p, t) for p in pages]
            counts, kept, out = [], [], {}
            for i in self._walk:
                if not isinstance(i, int):      # a run of state layers
                    x, keep = self._state_run(
                        params, i[0], x, pools[len(paged):], advance)
                    out.setdefault('state', []).append(keep)
                    continue
                q, *rows = blk.qkv(params, x, i, positions)
                ctx, keep = attend(
                    i, q, [r.astype(self.cache.dtype) for r in rows],
                    paged, *at)
                kept.append(keep)
                x, c = blk.after_attention(params, x, ctx, i, active)
                if c is not None:
                    counts.append(c)
            if counts:
                out['counts'] = jnp.stack(counts)
            if kept and kept[0]:
                out['kept'] = [list(r) for r in zip(*kept)]
            if self.looped:
                x, out['gate'] = blk.between(params, x, t)
            return x, pools, out

        if T == 1:
            x, pools, out = recurrence(0, x, pools)
        else:
            def body(carry, t):
                x, pools, out = recurrence(t, *carry)
                return (x, pools), out

            (x, pools), out = jax.lax.scan(
                body, (x, [list(pool) for pool in pools]), jnp.arange(T))
            if 'counts' in out:     # [T, layers, n] -> [T x layers, n]
                out['counts'] = out['counts'].reshape(
                    (-1,) + out['counts'].shape[2:])
        extra = (out['counts'],) if 'counts' in out else ()
        if self.looped:
            w = active[:decoding].astype(jnp.float32)
            gates = out['gate'].reshape(T, -1)[:, :decoding]
            extra += (blk.exit_distribution(gates) @ w
                      / jnp.maximum(jnp.sum(w), 1.0),)
        if 'state' in out and out['state'][0]:
            # what a whole-prompt prefill keeps of the states: a state
            # row's runs, each [layers of the run, *shape]
            out['kept'] = out.get('kept', []) + [
                list(run) for run in zip(*out['state'])]
        return x, pools, out.get('kept', ()), extra

    def _state_run(self, params, r, x, state, advance):
        """Run ``r`` of state layers over x: ONE traced body under
        ``lax.scan`` over the run's stacked weights
        (``block.state_weights``), the run's buffers of ``state`` (a list
        a state row of a list a run; written back here) its carry.
        ``advance(w, x, bufs, j)`` -> (x, bufs, keep) is the program's
        own: layer j of the run, weights ``w``, on buffers ``[layers,
        slots + 1, ...]`` a state row.  Returns (x, what the layers
        kept, stacked a layer: a whole-prompt prefill's final states,
        else ``()``)."""
        n = self.state_runs[r][1]

        def body(carry, xs):
            x, bufs, keep = advance(xs[0], *carry, xs[1])
            return (x, bufs), keep

        (x, bufs), kept = jax.lax.scan(
            body, (x, [row[r] for row in state]),
            (self.block.state_weights(params, r), jnp.arange(n)))
        for row, buf in zip(state, bufs):
            row[r] = buf
        return x, kept

    def _from_zero(self, pos0):
        """Whether a chunk at ``pos0`` starts its stream's state from
        zeros, whatever the slot holds: a reused slot needs no reset,
        and a preempted stream recomputes its state with its pages."""
        return pos0 == 0

    @staticmethod
    def _state_rows(bufs, j, row0, n):
        """Rows ``row0 .. row0 + n`` of layer ``j`` of a run's buffers
        (``[layers, slots + 1, ...]`` a state row)."""
        return [jax.lax.dynamic_slice(
            b, (j, row0) + (0,) * (b.ndim - 2), (1, n) + b.shape[2:])[0]
            for b in bufs]

    @staticmethod
    def _state_put(bufs, j, row0, rows):
        return [jax.lax.dynamic_update_slice(
            b, r[None].astype(b.dtype), (j, row0) + (0,) * (b.ndim - 2))
            for b, r in zip(bufs, rows)]

    def _advance_rows(self, live):
        """``advance`` of step: row r is a token of slot r, on state
        row r; a row that is not ``live`` leaves its slot's state as it
        is (the slot may hold a stream whose prompt is still going in)."""
        S = self.max_streams

        def advance(w, x, bufs, j):
            x, rows, _ = self.block.state_layer(
                w, x, rows=(*self._state_rows(bufs, j, 0, S), live))
            return x, self._state_put(bufs, j, 0, rows), ()
        return advance

    def _advance_chunk(self, live, slot, pos0, n_valid):
        """``advance`` of chunk: the first ``max_streams`` rows are the
        carried decode rows, as step's; the others one stream's chunk,
        which continues the state in row ``slot`` (from zeros at
        ``_from_zero``) for ``n_valid`` tokens."""
        S, zero = self.max_streams, self._from_zero(pos0)

        def advance(w, x, bufs, j):
            mine = [jnp.where(zero, 0.0, s[0])
                    for s in self._state_rows(bufs, j, slot, 1)]
            x, rows, seq = self.block.state_layer(
                w, x, rows=(*self._state_rows(bufs, j, 0, S), live),
                seq=(*mine, n_valid))
            bufs = self._state_put(bufs, j, 0, rows)
            return x, self._state_put(bufs, j, slot,
                                      [s[None] for s in seq]), ()
        return advance

    def _advance_prompt(self, n_valid):
        """``advance`` of a whole-prompt prefill: one sequence from
        zeros; what it keeps is the final state (``pack`` writes it)."""
        def advance(w, x, bufs, j):
            zeros = [jnp.zeros(shape, dt)
                     for _n, shape, dt in self.cache.state_rows]
            x, _, seq = self.block.state_layer(w, x, seq=(*zeros, n_valid))
            return x, bufs, tuple(seq)
        return advance

    def _by_slot(self, layers):
        """What ``_layers`` kept of one cache row, an array a layer ->
        [cache slots, rows, width], slot t x n_layers + l."""
        if self.ut_steps == 1:
            return jnp.stack(layers)
        both = jnp.stack(layers, axis=1)        # [T, L, rows, width]
        return both.reshape((-1,) + both.shape[2:])

    @staticmethod
    def _place(params):
        """The weights onto the device, once, under a set-up span that
        waits for them."""
        placed = {}
        with _obs.span('decode.weights', args=placed):
            out = jax.block_until_ready(
                {n: jnp.asarray(v) for n, v in params.items()})
            placed.update(
                bytes=sum(v.nbytes for v in out.values()),
                tensors=len(out), dtype=str(max(
                    out.values(), key=lambda v: v.nbytes).dtype))
        return out

    def _decode_operands(self, tokens, page_tables, ctx_lens, span_args):
        """A decode step's three arrays as the executables take them.
        Each goes in as the device array the last call left (``_held``)
        where the host's array equals, on every row, what that array
        holds: between two plain steps the ids are the last call's own
        output, the context lengths its own plus one on the running
        rows, the page tables unchanged, and nothing is handed over from
        the host (a numpy array costs the executable's call 0.15 ms:
        PERF.md section 6, PR 52).  An array that differs anywhere (a
        stream admitted or retired, a page claimed, another caller's
        rows) goes in from the host: ids and context lengths as numpy,
        as they are (where uploads are rare that is the cheapest way in:
        a ``jax.device_put`` that runs once in 40 calls costs 0.3 ms, a
        ``jnp.asarray`` is an upload of its own and a ``jnp`` scalar a
        device program), the page tables put on the device once, here,
        so that the calls after this one find them there.  Either way
        the program sees bit for bit what the host holds, idle rows
        included.  Returns (the operands, the host's arrays as int32);
        the span gets ``host_operands``, how many of the three came from
        the host."""
        host = [np.asarray(a, dtype=np.int32)
                for a in (tokens, page_tables, ctx_lens)]
        ops, sent = list(host), 3
        if self._held is not None:
            for k, (kept, handle) in enumerate(zip(*self._held)):
                if np.array_equal(host[k], kept):
                    host[k], ops[k], sent = kept, handle, sent - 1
        if ops[1] is host[1]:
            # a copy of its own: the caller may write into its array,
            # and a backend may share the host's memory with the handle
            host[1] = host[1].copy()
            ops[1] = jax.device_put(host[1])
        span_args['host_operands'] = sent
        self.calls['step_calls'] += 1
        self.calls['step_host_operands'] += sent
        return ops, host

    def _hold(self, ops, host, nxt, next_ids, next_ctx):
        """After a call that ran decode rows: keep what it left on the
        device for the next call (``next_ids``, the page tables it was
        called with, ``next_ctx``: ``_next_rows``) and what those arrays
        hold, worked out on the host from the ids the call fetched
        anyway (``nxt``)."""
        running = host[1][:, 0] != self.cache.trash
        self._held = (
            (np.where(running, nxt, 0).astype(np.int32), host[1],
             host[2] + running.astype(np.int32)),
            (next_ids, ops[1], next_ctx))

    def _fetch(self, arrays, extra, span_args, step=False, decoded=True):
        """An engine call's one copy to the host: ``arrays`` (what the
        caller reads) and what the program returned beside them
        (``extra``, as ``_layers`` orders it: the routing counts where
        the block routes, the exit distribution's mean where it loops)
        come back in one transfer, which waits for the program.  The
        span gets ``fetched_bytes``, what ``_routing`` makes of the
        counts and, where the call ran decode rows (``decoded``),
        ``loop_exit_mass`` and ``loop_passes`` (its length, the
        recurrences the program ran, times the weight layers); the
        arrays are returned as numpy."""
        got = jax.device_get(tuple(arrays) + tuple(extra))
        span_args['fetched_bytes'] = sum(a.nbytes for a in got)
        extra = list(got[len(arrays):])
        if self.looped:
            mass = extra.pop()
            if decoded:
                # one mean a recurrence the program ran, each a pass
                # over every weight layer
                span_args['loop_exit_mass'] = mass.tolist()
                span_args['loop_passes'] = len(mass) * self.n_layers
                self.loop_passes += span_args['loop_passes']
        if extra:
            self._routing(extra[0], span_args, step=step)
        return got[:len(arrays)]

    def _routing(self, c, span_args, step=False):
        """The routing counts ``c`` [L, E] a call returned beside its
        usual outputs (``_layers``), on the host (``_fetch``) -> the
        span's arguments and the engine's totals: assignments (top_k x
        tokens x layers), experts with a token (mean over layers;
        totalled over decode steps only), most tokens on one expert.
        Where the block holds a share of the experts, the counts' last
        column is the assignments to experts held elsewhere:
        ``moe_all_assignments`` counts them too, the other three
        (``moe_held_assignments``, ``moe_held_touched``,
        ``moe_max_load``) the held experts alone."""
        held = 'held_' if self.block.experts_share else ''
        tot = self.routing
        if held:
            span_args['moe_all_assignments'] = int(c.sum())
            tot['all_assignments'] += int(c.sum())
            c = c[:, :-1]
        touched = float(np.mean(np.sum(c > 0, axis=1)))
        span_args.update({'moe_%sassignments' % held: int(c.sum()),
                          'moe_%stouched' % held: touched,
                          'moe_max_load': int(c.max())})
        tot['assignments'] += int(c.sum())
        tot['max_load'] = max(tot['max_load'], span_args['moe_max_load'])
        if step:
            tot['touched'] += touched
            tot['steps'] += 1

    def _kv_pages(self, page_tables, ctx_lens, span_args):
        """A decode step's KV pages, from the host's arrays alone ->
        the span's arguments and the engine's totals: ``kv_live_pages``,
        what attention has to read (each running slot's pages up to and
        with the position written this step), and ``kv_table_pages``,
        what a gather of whole page tables reads (S x MPP).  Returns
        the number of running slots."""
        pts = np.asarray(page_tables)
        running = pts[:, 0] != self.cache.trash
        ctx = np.asarray(ctx_lens)[running]
        live = int(np.sum(ctx // self.page_size + 1))
        span_args.update(kv_live_pages=live, kv_table_pages=pts.size)
        if self.block.live_positions_arg:
            # the positions a step's attention reads, the new one too
            span_args[self.block.live_positions_arg] = int(np.sum(ctx + 1))
        if self.looped:
            # every recurrence reads the running slots' positions in a
            # slot of its own
            T = self.ut_steps
            span_args.update(
                ut_steps=T,
                kv_loop_live_positions=T * int(np.sum(ctx + 1)))
        if self.ring_pages:
            # by group: the pages above, and the rings' pages that hold
            # a window's positions (the new one too)
            P, w = self.page_size, self.block.window
            ring = int(np.sum(ctx // P - np.maximum(ctx + 1 - w, 0) // P
                              + 1))
            span_args.update(
                kv_full_live_pages=live, kv_window_live_pages=ring,
                # and the positions in them that attention reads
                kv_full_live_positions=int(np.sum(ctx + 1)),
                kv_window_live_positions=int(np.sum(
                    np.minimum(ctx + 1, w))))
            self.kv_pages['window_live'] += ring
            self._recycled(ctx, ctx + 1)
        self.kv_pages['live'] += live
        self.kv_pages['table'] += pts.size
        n = int(np.sum(running))
        if self.state_runs:
            # the running slots' states, every state layer's: what the
            # rows' update reads and writes
            span_args.update(
                ssm_live_slots=n,
                ssm_state_bytes=n * self.cache.state_bytes_per_stream())
            self.ssm_state_bytes += span_args['ssm_state_bytes']
        return n

    def _recycled(self, lo, hi):
        """Count the ring columns that positions [lo, hi) (arrays or
        numbers, a stream each) begin a page in when the page is not the
        column's first: a page given back to its own stream."""
        P, R = self.page_size, self.ring_pages
        first = np.maximum(-(-np.asarray(lo) // P), R)
        self.kv_pages['window_recycled'] += int(np.sum(np.maximum(
            -(-np.asarray(hi) // P) - first, 0)))

    # -- the three programs: one loop, three ways to attend -------------

    def _write_then(self, offset, read):
        """``attend`` of step (a chunk's: ``_pages_then``): a row a slot
        lands at (page, offset) of layer i's own buffers (``at``: the
        pages, one array a group, as the recurrence finds them), then
        ``read(i, q, pools, *tables)`` attends over what was written."""
        def attend(i, q, rows, pools, at, *tables):
            for pool, r in zip(pools, rows):
                pool[i] = pool[i].at[at[self._group[i]], offset].set(r)
            return read(i, q, pools, *tables), ()
        return attend

    def _prefill_fn(self, bucket):
        blk = self.block

        def prefill(params, tokens, last):
            # ``last`` (the prompt's final position) is a traced
            # operand, NOT python int: slicing the returned logits on
            # the host would dispatch an op-by-op gather whose hidden
            # per-shape compile (~25-40ms) lands on the first stream
            # of every bucket — invisible to compiles_total
            pos = jnp.arange(bucket)

            def attend(i, q, rows, pools):
                # attention reads the rows as the cache will hold them
                # (already in the pools' dtype); pack writes them later
                return blk.attend_prefill(params, i, q, rows)

            x, _pools, kept, extra = self._layers(
                params, blk.embed(params, tokens, pos), pos, pos <= last,
                attend, advance=self._advance_prompt(last + 1))
            n = len(self.cache.rows)
            # (then the final states, a state row's runs one after another)
            return (blk.head(params, x[last][None])[0],) + tuple(
                self._by_slot(layers) for layers in kept[:n]) + tuple(
                run for row in kept[n:] for run in row) + extra
        return prefill

    def _chunk_rows(self, bucket, pt, pos0, n_valid):
        """A chunk's rows: positions, which of them hold a prompt token,
        and the pages they are cached in, an id a PAGE (``_pages_then``)."""
        P, mpp = self.page_size, self.pages_per_stream
        # pos0 and n_valid are traced (host slicing would hide per-shape
        # gather compiles, the prefill lesson); a page that holds no
        # prompt token is written to the trash page
        pos = pos0 + jnp.arange(bucket)
        valid = jnp.arange(bucket) < n_valid
        j = jnp.arange(bucket // P)
        page, live = pos0 // P + j, j * P < n_valid
        tables = self._tables(pt)
        page_ids = [jnp.where(live, tables[0][jnp.clip(page, 0, mpp - 1)],
                              self.cache.trash)]
        if self.ring_pages:
            page_ids.append(jnp.where(live, tables[1][page % self.ring_pages],
                                      self.cache.window.trash))
        return pos, valid, page_ids

    def _step_rows(self, pt, ctx_len):
        """A decode step's rows, one a slot: positions and where their
        cached rows land."""
        P = self.page_size
        # ctx_len counts CACHED positions per slot; the incoming
        # token sits at position ctx_len and is cached this step
        pos = jnp.clip(ctx_len, 0, self.max_seq - 1)
        tables = self._tables(pt)
        page_idx = [jnp.take_along_axis(
            tables[0], (pos // P)[:, None], axis=1)[:, 0]]
        if self.ring_pages:
            page_idx.append(jnp.take_along_axis(
                tables[1], ((pos // P) % self.ring_pages)[:, None],
                axis=1)[:, 0])
        return pos, page_idx, pos % P

    def _chunk_read(self, params, pos0):
        def read(i, q, pools, tables):
            return self.block.attend_chunk(
                params, i, q, [pool[i] for pool in pools],
                tables[self._group[i]], pos0)
        return read

    def _step_read(self, params, pos, running):
        # a slot that is not running attends over nothing: a context of
        # 0 is the idle slot the step kernels skip (its table is all
        # trash, and its row is not counted)
        ctx_len = jnp.where(running, pos + 1, 0)

        def read(i, q, pools, tables):
            return self.block.attend_step(
                params, i, q, [pool[i] for pool in pools],
                tables[self._group[i]], ctx_len)
        return read

    def _next_rows(self, pt, ctx_len, nxt):
        """What a program returns beside the decode rows' ids: those
        rows' ids and context lengths as the NEXT call takes them if
        nothing else happens, an idle row 0 in both (``_hold`` works
        out the same on the host).  They stay on the device."""
        running = pt[:, 0] != self.cache.trash
        return (jnp.where(running, nxt, 0).astype(jnp.int32),
                ctx_len + running.astype(jnp.int32))

    def _chunk_fn(self, bucket):
        blk, S, trash = self.block, self.max_streams, self.cache.trash
        n = self._n_pools

        def chunk(params, *args):
            # one pass over S + bucket rows: the tick's decode rows (as
            # ``step`` takes them; all-trash page tables carry none),
            # then the chunk's.  Rows never mix in a layer, so each
            # group comes out as its own program would give it
            tokens, pt, pos0, n_valid, step_tokens, step_pt, ctx_len = \
                args[n:n + 7]
            spos, spage, soffset = self._step_rows(step_pt, ctx_len)
            pos, valid, page_ids = self._chunk_rows(
                bucket, pt, pos0, n_valid)
            read_step = self._step_read(params, spos, step_pt[:, 0] != trash)
            read_chunk = self._chunk_read(params, pos0)
            step_tables, tables = self._tables(step_pt), self._tables(pt)

            def read(i, q, pools, step_tables, tables):
                step_rows = read_step(i, q[:S], pools, step_tables)
                rows = read_chunk(i, q[S:], pools, tables)
                if self.ring_pages:
                    # a padded row reads positions nobody wrote, in a
                    # ring whatever the page's last holder left: it
                    # attends to nothing, so that what it writes to the
                    # trash pages stays finite for whoever gathers them
                    rows = jnp.where(valid[:, None, None], rows, 0.0)
                return jnp.concatenate([step_rows, rows])

            # a last chunk's padded rows point past the prompt: ``embed``
            # (which may index a position table) gets them inside max_seq
            # (the decode rows are cached a row at a time, as ``step``
            # caches them, and the chunk's a page at a time: each group
            # of rows has page ids of its own, which a recurrence
            # shifts alike)
            x, pools, _kept, extra = self._layers(
                params,
                blk.embed(params, jnp.concatenate([step_tokens, tokens]),
                          jnp.concatenate(
                              [spos, jnp.clip(pos, 0, self.max_seq - 1)])),
                jnp.concatenate([spos, pos]),
                jnp.concatenate([step_pt[:, 0] != trash, valid]),
                pages=(spage, page_ids, step_tables, tables),
                attend=self._pages_then(S, self._write_then(soffset, read)),
                pools=args[:n], decoding=S,
                # (the chunk's stream's slot is the last operand, where
                # there are state layers)
                advance=self._advance_chunk(
                    step_pt[:, 0] != trash, args[-1], pos0, n_valid)
                if self.state_runs else None)
            # the head on the decode rows and the chunk's last valid row
            last = S + jnp.clip(n_valid - 1, 0, bucket - 1)
            logits = blk.head(params,
                              jnp.concatenate([x[:S], x[last][None]]))
            nxt = jnp.argmax(logits[:S], axis=-1)
            return tuple(pools) + (logits[S], nxt, logits[:S]) \
                + self._next_rows(step_pt, ctx_len, nxt) + extra
        return chunk

    def _step_fn(self):
        blk, trash = self.block, self.cache.trash
        n = self._n_pools

        def step(params, *args):
            tokens, pt, ctx_len = args[n:]
            pos, page_idx, offset = self._step_rows(pt, ctx_len)
            # an inactive slot's page table is all trash: it runs (its
            # rows never meet another slot's) and is not counted
            x, pools, _kept, extra = self._layers(
                params, blk.embed(params, tokens, pos), pos,
                pt[:, 0] != trash,
                self._write_then(offset, self._step_read(
                    params, pos, pt[:, 0] != trash)),
                pools=args[:n], pages=(page_idx, self._tables(pt)),
                advance=self._advance_rows(pt[:, 0] != trash)
                if self.state_runs else None)
            logits = blk.head(params, x)
            nxt = jnp.argmax(logits, axis=-1)
            return tuple(pools) + (logits, nxt) \
                + self._next_rows(pt, ctx_len, nxt) + extra
        return step

    def _pools_out(self, out):
        """A pool program's outputs: the pools go back into the cache,
        the rest is returned."""
        n = len(self.cache.rows)
        self.cache.pools = [list(pool) for pool in out[:n]]
        self.cache.state = [list(row) for row in out[n:self._n_pools]]
        return out[self._n_pools:]

    @property
    def _n_pools(self):
        """The pool operands of a program that writes them: the page
        pools, a cache row each, then the state pools, a state row
        each."""
        return len(self.cache.rows) + len(self.cache.state_rows)

    def _all_pools(self):
        return self.cache.pools + self.cache.state

    def _slot_of(self, pages):
        """Where there are state layers an engine call is handed the
        pair (a stream's pages as ``table_row`` takes them, the stream's
        slot): -> (pages, the slot as the programs take it, or ())."""
        if not self.state_runs:
            return pages, ()
        pages, slot = pages
        return pages, (np.int32(slot),)

    def _ensure_prefill(self, bucket):
        if bucket in self._prefill:
            return
        L, P, n = len(self._kv_layers), self.page_size, len(self.cache.rows)
        n_pages = bucket // P

        def pack(*args):
            # scatter the rows the prefill kept into the claimed pages:
            # [slots, T, ...] -> [slots, n_pages, P, width], slot (t, i)
            # written at its group's ``pages``, where recurrence t finds
            # them, of layer i's own buffer (padded entries, and in a
            # ring the pages behind the newest, point at the trash page)
            pools, kept, pages = args[:n], args[n:2 * n], args[2 * n:]
            out = [list(row_pools) for row_pools in pools]
            for row_pools, rows in zip(out, kept):
                paged = rows.reshape(self.cache.slots, n_pages, P, -1)
                for t in range(self.ut_steps):
                    at = self.cache.shift(pages, t)
                    for k, i in enumerate(self._kv_layers):
                        row_pools[i] = row_pools[i].at[
                            at[self._group[i]]].set(paged[t * L + k])
            return tuple(out)

        def pack_states(*args):
            # ``pack``, and the final states the prefill kept into row
            # ``slot`` of the state pools: (*page pools, *state pools,
            # *kept rows, *kept states a run, *pages, slot)
            m, runs = self._n_pools, len(self.state_runs)
            states = m - n
            rest = args[m + n + states * runs:]
            out = pack(*args[:n], *args[m:m + n], *rest[:-1])
            kept = args[m + n:m + n + states * runs]
            return out + tuple(
                [jax.lax.dynamic_update_slice(
                    buf, kept[k * runs + r][:, None].astype(buf.dtype),
                    (0, rest[-1]) + (0,) * (buf.ndim - 2))
                 for r, buf in enumerate(row)]
                for k, row in enumerate(args[n:m]))

        toks = jnp.zeros((bucket,), jnp.int32)
        prefill = self._prefill_fn(bucket)
        self._prefill[bucket] = self._compile(
            prefill, self.params, toks, jnp.int32(0), bucket=bucket)
        states = len(self.cache.state_rows) * len(self.state_runs)
        # what the prefill keeps, as the compiled program declares it:
        # shapes and types only, as a second trace would give them
        kept = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in
                self._prefill[bucket].out_info[1:1 + n + states]]
        pages = [jnp.zeros((n_pages,), jnp.int32) for _ in self._trashes()]
        packer = pack
        if self.state_runs:
            packer = pack_states
            packer.__name__ = 'pack'
            pages.append(jnp.int32(0))      # the slot
        self._pack[bucket] = self._compile(
            packer, *self._all_pools(), *kept, *pages,
            donate=tuple(range(self._n_pools)), bucket=bucket)

    def _ensure_chunk(self, bucket):
        """Chunked-prefill executable for one chunk bucket: a SINGLE
        stream's prompt chunk of up to ``bucket`` tokens at absolute
        positions pos0.. (on the page grid), written a page at a time
        into the stream's pages (``_pages_then``) and attending over
        chunks 0..N via the page table (the KV-carry is the donated
        pool itself), and in the same pass the decode rows of one
        ``step`` (tokens [S], page tables [S, MPP], context lengths
        [S]; all-trash page tables carry none), each attending over its
        own pages as in ``step``: one read of the weights for both.
        The program returns, all on the device, the chunk's last VALID
        row's logits [V], so intermediate chunks pay one row of the
        head, not [C, V], then the decode rows' next tokens [S] and
        logits [S, V] (and the routing counts, where the block routes);
        ``prefill_chunk`` copies the row, the ids and the counts to the
        host and leaves [S, V] where it is.  The weights are the
        program's first operand, as every program's."""
        if bucket in self._chunk:
            return
        self._chunk[bucket] = self._compile(
            self._chunk_fn(bucket), self.params, *self._all_pools(),
            jnp.zeros((bucket,), jnp.int32),
            jnp.asarray(self.idle_row),
            jnp.int32(0), jnp.int32(1), *self._idle_step,
            *self._idle_slot,
            donate=tuple(range(1, 1 + self._n_pools)), bucket=bucket)

    def _ensure_step(self):
        if self._step is not None:
            return
        self._step = self._compile(
            self._step_fn(), self.params, *self._all_pools(),
            *self._idle_step,
            donate=tuple(range(1, 1 + self._n_pools)))

    def warmup(self):
        """AOT-compile every prefill bucket, its pack, and the decode
        step, then EXECUTE each once: the first invocation of a fresh
        executable pays one-time runtime setup (buffer finalization —
        measured 25-85ms per executable on the CPU backend) that must
        never land on a live stream's latency.  The dummy executions
        route every write to the trash page, so pool contents survive
        bit-for-bit even on a re-warm with streams resident.
        Afterwards the serving loop calls only precompiled, pre-run
        executables (compiles_after_warmup counts any miss).

        One ``decode.warmup`` span encloses it (``programs``: the
        executables this call built, ``runs``: the executions it made),
        a ``decode.compile`` each program and a ``decode.warmup.run``
        each execution and its wait beneath it."""
        if self._compiles_at_warmup == self.compiles_total:
            return  # already compiled AND warm-executed, nothing new
        args, built = {'runs': 0}, self.compiles_total
        with _obs.span('decode.warmup', args=args):
            self._warm(args)
            args['programs'] = self.compiles_total - built
        self._compiles_at_warmup = self.compiles_total

    def _warm(self, said):
        """Build what is missing, then run every executable once
        (counted in ``said['runs']``)."""
        def first_run(program, bucket, run):
            said['runs'] += 1
            with _obs.span('decode.warmup.run',
                           args={'program': program, 'bucket': bucket}):
                return jax.block_until_ready(run())

        def pack(b, kept):
            all_trash = [jnp.full((b // self.page_size,), t, jnp.int32)
                         for t in self._trashes()]
            self._pools_out(self._pack[b](
                *self._all_pools(), *kept, *all_trash, *self._idle_slot))
            return self._all_pools()

        if self.chunked:
            # chunked path: all prefill (cold included) runs the chunk
            # executables — the monolithic prefill/pack pair is never
            # dispatched, so warmup neither compiles nor warms it
            for b in self.chunk_buckets:
                self._ensure_chunk(b)
            self._ensure_step()
            for b in self.chunk_buckets:
                first_run('chunk', b, lambda: self._pools_out(
                    self._chunk[b](
                        self.params, *self._all_pools(),
                        jnp.zeros((b,), jnp.int32),
                        jnp.asarray(self.idle_row),
                        jnp.int32(0), jnp.int32(b), *self._idle_step,
                        *self._idle_slot))[0])
        else:
            for b in self.buckets:
                self._ensure_prefill(b)
            self._ensure_step()
            for b in self.buckets:
                _logits, *kept = first_run(
                    'prefill', b, lambda: self._prefill[b](
                        self.params, jnp.zeros((b,), jnp.int32),
                        jnp.int32(0))[:1 + len(self.cache.rows)
                                      + len(self.cache.state_rows)
                                      * len(self.state_runs)])
                first_run('pack', b, lambda: pack(b, kept))
        first_run('step', None, lambda: self._pools_out(self._step(
            self.params, *self._all_pools(), *self._idle_step))[0])

    @property
    def compiles_after_warmup(self):
        if self._compiles_at_warmup is None:
            return self.compiles_total
        return self.compiles_total - self._compiles_at_warmup

    # -- serving-loop entry points -------------------------------------

    def bucket_for(self, prompt_len):
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise PromptTooLongError(
            "prompt length %d exceeds top prefill bucket %d"
            % (prompt_len, self.buckets[-1]))

    def prefill_into(self, prompt, pages):
        """Run one prompt's prefill and pack its rows into ``pages``
        (the stream's claimed pages, page 0 of the stream first; with
        window layers the pair (pages, ring), as ``table_row`` takes it).
        Returns the last-position logits as numpy [V] — the first
        generated token's distribution, i.e. the TTFT payload."""
        prompt = np.asarray(prompt, dtype=np.int32)
        t = int(prompt.shape[0])
        bucket = self.bucket_for(t)
        args = {'tokens': t, 'bucket': bucket}
        with _obs.span('decode.prefill_into', args=args):
            self._ensure_prefill(bucket)
            toks = np.zeros((bucket,), np.int32)
            toks[:t] = prompt
            n = len(self.cache.rows) + len(self.cache.state_rows) \
                * len(self.state_runs)
            n_pages = bucket // self.page_size
            pages, slot = self._slot_of(pages)
            ring = ()
            if self.ring_pages:
                pages, ring = pages
            page_ids = [np.full((n_pages,), self.cache.trash, np.int32)]
            n_real = min(len(pages), n_pages)
            page_ids[0][:n_real] = pages[:n_real]
            if self.ring_pages:
                # the prompt's newest pages into their ring columns,
                # the pages behind them nowhere
                R, last = self.ring_pages, (t - 1) // self.page_size
                ids = np.full((n_pages,), self.cache.window.trash, np.int32)
                j = np.arange(max(last - len(ring) + 1, 0), last + 1)
                ids[j] = np.asarray(ring, np.int32)[j % R]
                page_ids.append(ids)
                self._recycled(0, t)
            # numpy in: ``jnp`` scalars would be device programs
            logits, *rest = self._prefill[bucket](
                self.params, toks, np.int32(t - 1))
            self._pools_out(self._pack[bucket](
                *self._all_pools(), *rest[:n], *page_ids, *slot))
            return self._fetch((logits,), rest[n:], args,
                               decoded=False)[0]

    def chunk_spans(self, prompt_len, start=0):
        """The grid-aligned chunk decomposition of positions
        [start, prompt_len): full ``chunk_grid`` chunks plus one ragged
        remainder.  ``start`` must sit ON the grid — a prefix hit's
        tail spans are then an exact suffix of the cold (start=0)
        spans, which is what makes hit and cold prefill bitwise
        identical executions."""
        g = self.chunk_grid
        if start % g:
            raise ValueError("chunk start %d off the %d-token grid"
                             % (start, g))
        spans, lo = [], int(start)
        while lo < prompt_len:
            hi = min(lo + g, int(prompt_len))
            spans.append((lo, hi))
            lo = hi
        return spans

    def prefill_chunk(self, tokens, pages, pos0, step_tokens=None,
                      page_tables=None, ctx_lens=None):
        """Run ONE prefill chunk for a single stream: ``tokens`` [c]
        (c <= chunk_grid) land at absolute positions pos0..pos0+c-1 in
        the pages named by ``pages`` (the stream's page table; entries
        past it route to trash; with window layers the pair (pages,
        ring)).  ``pos0`` is a multiple of the page size (``chunk_spans``
        keeps it on the chunk grid, which is one; anything else is a
        ``ValueError``): the chunk's rows are then whole pages in
        order, and are written a page at a time (``_pages_then``).
        Returns the chunk's last-row logits
        as numpy [V] — only the final chunk's matter (the TTFT
        payload), earlier chunks' are a one-row head by-product.

        Handed a decode step's operands as well (``step``'s three, of
        streams other than the chunk's), the same call runs that step's
        rows beside the chunk's, in place of a ``step`` after it: it
        then returns (last-row logits, next tokens [S] as numpy, the
        decode rows' logits [S, V] left on the device for whoever asks).
        The span's ``tokens`` and ``bucket`` stay the chunk's;
        ``step_rows`` counts the running slots carried, and
        ``fetched_bytes`` what came back to the host: the last row, and
        with rows carried their ids, beside the routing counts; the
        carried three go in as ``step``'s do, and ``host_operands``
        counts those that came from the host (a chunk that carries no
        rows has none, and leaves what the last step left alone).  The
        call's two halves are the spans ``.dispatch`` and ``.fetch``,
        as ``step``'s."""
        tokens = np.asarray(tokens, dtype=np.int32)
        c = int(tokens.shape[0])
        if pos0 % self.page_size:
            raise ValueError("chunk start %d off the %d-token page grid"
                             % (pos0, self.page_size))
        bucket = self.bucket_for(c)
        args = {'tokens': c, 'bucket': bucket, 'step_rows': 0}
        with _obs.span('decode.prefill_chunk', args=args):
            self._ensure_chunk(bucket)
            with _obs.span('decode.prefill_chunk.dispatch'):
                toks = np.zeros((bucket,), np.int32)
                toks[:c] = tokens
                pages, slot = self._slot_of(pages)
                pt = self.table_row(pages)
                if self.ring_pages:
                    self._recycled(pos0, pos0 + c)
                if slot:
                    args.update(ssm_scan_tokens=c, ssm_from_zero=pos0 == 0)
                carried, host = (self._idle_step, None) \
                    if step_tokens is None else self._decode_operands(
                        step_tokens, page_tables, ctx_lens, args)
                logits, nxt, step_logits, ids, ctx, *extra = \
                    self._pools_out(self._chunk[bucket](
                        self.params, *self._all_pools(), toks, pt,
                        np.int32(pos0), np.int32(c), *carried, *slot))
            with _obs.span('decode.prefill_chunk.fetch'):
                if step_tokens is not None:
                    args['step_rows'] = self._kv_pages(page_tables,
                                                       ctx_lens, args)
                if step_tokens is None:
                    # no decode rows ran: what is held stays as it is
                    return self._fetch((logits,), extra, args,
                                       decoded=False)[0]
                logits, nxt = self._fetch((logits, nxt), extra, args)
                self._hold(carried, host, nxt, ids, ctx)
                return logits, nxt, step_logits

    def step(self, tokens, page_tables, ctx_lens):
        """One batched decode step over all ``max_streams`` slots.
        Inactive slots pass token 0 with an all-trash page-table row —
        their writes land in the trash page and their outputs are
        ignored.  Returns (next tokens [S] as numpy, the rows' logits
        [S, V] left on the device for whoever indexes them): the ids and
        the routing counts are all that is copied to the host
        (``fetched_bytes`` on the span).  Of the three arrays, those
        that equal what the last call left on the device are not
        uploaded again (``_decode_operands``): the span's
        ``host_operands`` counts the ones that were, 0 to 3, and
        ``calls`` totals them (``step_calls``,
        ``step_host_operands``)."""
        self._ensure_step()
        # the two halves of the host's part: everything up to the call
        # into the executable returning, then the wait for the device
        # and the copy back of the ids (and routing counts)
        args = {}   # the step's KV pages, routing counts, fetched bytes
        with _obs.span('decode.step', args=args):
            with _obs.span('decode.step.dispatch'):
                ops, host = self._decode_operands(
                    tokens, page_tables, ctx_lens, args)
                logits, nxt, ids, ctx, *extra = self._pools_out(
                    self._step(self.params, *self._all_pools(), *ops))
            with _obs.span('decode.step.fetch'):
                self._kv_pages(page_tables, ctx_lens, args)
                nxt, = self._fetch((nxt,), extra, args, step=True)
                self._hold(ops, host, nxt, ids, ctx)
                return nxt, logits

    def resident_bytes(self):
        return self.cache.resident_bytes()

    # -- a chunk's rows into the pools, a page at a time -----------------

    ssm_state_bytes = 0         # live state bytes, over the decode rows' calls

    def _pages_then(self, n, attend):
        """``attend`` of chunk: of the rows layer i caches, the first
        ``n`` are the carried decode rows, one a slot, and ``attend``
        (``_write_then``) caches them a row at a time as ``step`` does
        and reads; the rows after them are the chunk's.  Those are
        ``bucket // P`` WHOLE pages in order (the chunk grid and every
        bucket are page multiples, and ``prefill_chunk`` holds ``pos0``
        to the page grid), so they are written as ``pack`` writes a
        monolithic prefill's: ``[bucket // P, P, width]`` at
        ``page_at`` (``_chunk_rows``: a page id a PAGE, one array a
        group, as the recurrence finds them): ``bucket // P`` updates a
        scatter where a row at a time took ``bucket`` (18 scatters of
        544 rows were 1.31 of a Laguna chunk's 16.4 ms on the chip:
        PERF.md section 6, PR 58).

        What a page holds then differs from a row at a time in ONE
        place: a prompt's ragged last page is written whole, so its
        rows past the prompt hold the padded rows' K/V, which used to go
        to the trash page.  They are positions >= the stream's context
        length: every reader leaves them out (the kernels' and the
        math's ``pos < ctx`` masks), the stream's own decode steps
        overwrite them in order before any read, and they are finite (a
        padded row's K/V is token 0's through finite weights; under a
        window it attends to nothing, ``_chunk_fn``).  In a ring that
        page's tail held positions ``ring_pages * P`` back, outside
        every window that can still read (``ring_pages`` covers
        ``window - 1 + chunk_grid`` and a page).  The prefix trie only
        ever holds whole valid pages, so nothing shared changes."""
        P = self.page_size

        def by_page(i, q, rows, pools, at, page_at, *tables):
            ids = page_at[self._group[i]]
            for pool, r in zip(pools, rows):
                pool[i] = pool[i].at[ids].set(
                    r[n:].reshape(-1, P, r.shape[-1]))
            return attend(i, q, [r[:n] for r in rows], pools, at, *tables)
        return by_page


class _DecodeMetrics(object):
    """Per-server decode metrics, labeled ``server="d<N>"`` (the
    _ServingMetrics pattern: global registry when observability is
    enabled, else a private one so stats() keeps working)."""

    def __init__(self, reg, sid):
        L = ('server',)
        self._sid = sid
        self._families = []

        def child(metric):
            self._families.append(metric)
            return metric.labels(server=sid)

        self.streams_active = child(reg.gauge(
            'paddle_tpu_decode_streams_active',
            'streams currently holding a decode batch slot', L))
        self.queue_depth = child(reg.gauge(
            'paddle_tpu_decode_queue_depth',
            'streams waiting for a slot or pages', L))
        self.ttft = child(reg.histogram(
            'paddle_tpu_decode_ttft_seconds',
            'submit-to-first-token latency per stream (prefill path)',
            L, buckets=_obs.DEFAULT_LATENCY_BUCKETS))
        self.pages_allocated = child(reg.counter(
            'paddle_tpu_decode_pages_allocated_total',
            'KV-cache pages claimed at stream admission', L))
        self.pages_freed = child(reg.counter(
            'paddle_tpu_decode_pages_freed_total',
            'KV-cache pages returned by finished streams', L))
        self.tokens = child(reg.counter(
            'paddle_tpu_decode_tokens_generated_total',
            'tokens emitted across all streams (prefill + decode)', L))
        self.steps = child(reg.counter(
            'paddle_tpu_decode_steps_total',
            'batched decode steps executed', L))
        self.prefix_hits = child(reg.counter(
            'paddle_tpu_decode_prefix_hit_tokens_total',
            'prompt tokens served from cached prefix pages (prefill '
            'MACs skipped)', L))
        self.prefix_misses = child(reg.counter(
            'paddle_tpu_decode_prefix_miss_tokens_total',
            'prompt tokens the prefill actually computed', L))
        self.prefix_evicted = child(reg.counter(
            'paddle_tpu_decode_prefix_evicted_tokens_total',
            'cached tokens LRU-evicted from the prefix trie under '
            'pool pressure', L))
        self.prefill_chunks = child(reg.counter(
            'paddle_tpu_decode_prefill_chunks_total',
            'chunked-prefill dispatches', L))
        self.prefill_chunks_carrying = child(reg.counter(
            'paddle_tpu_decode_prefill_chunks_carrying_total',
            'chunked-prefill dispatches that carried a decode step of '
            'at least one running stream', L))
        self.carried_rows = child(reg.counter(
            'paddle_tpu_decode_carried_rows_total',
            'decode rows of running streams that a prefill chunk '
            'carried', L))
        self.preempted = child(reg.counter(
            'paddle_tpu_decode_preempted_streams_total',
            'streams requeued on page-pool exhaustion mid-decode '
            '(recompute on readmission)', L))
        self.cached_pages = child(reg.gauge(
            'paddle_tpu_decode_prefix_cached_pages',
            'KV pages currently held by the prefix trie', L))
        self.state_recomputed = child(reg.counter(
            'paddle_tpu_decode_state_recomputed_total',
            'preemptions that threw a stream\'s per-slot state away '
            '(recomputed from position 0 at readmission)', L))

    def close(self):
        for m in self._families:
            m.remove(server=self._sid)


class DecodeStream(object):
    """Submit handle: resolves to the generated token ids."""

    def __init__(self, rid, prompt, max_new_tokens):
        self.request_id = rid
        self.prompt = np.asarray(prompt, dtype=np.int32)
        self.max_new_tokens = int(max_new_tokens)
        self.tokens = []          # generated ids, worker-appended
        self.token_times = []     # perf_counter per emitted token
        self.submitted_t = time.perf_counter()
        self.admitted_t = None    # when its batch slot was reserved
        self.first_token_t = None
        self.done_t = None
        self.error = None
        self._done = threading.Event()
        # worker-side state
        self._slot = None
        self._pages = None
        self._ring = []           # its pages of the window group, a ring
        self._ctx_len = 0         # cached positions
        # chunked-path worker state
        self._prefill_pos = None  # next uncomputed position, else None
        self._prompt_eff = None   # prompt (+ generated, post-preempt)
        self._owned = []          # pages the stream must free/donate
        self._ref_nodes = []      # trie nodes held by reference

    @property
    def ttft_s(self):
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submitted_t

    def per_token_s(self):
        """Inter-token gaps (decode-step latency as a client sees it)."""
        ts = self.token_times
        return [b - a for a, b in zip(ts, ts[1:])]

    def result(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError("stream %s still decoding"
                               % self.request_id)
        if self.error is not None:
            raise self.error
        return list(self.tokens)


class DecodeServer(object):
    """Continuous-batching decode worker over one DecodeEngine.

    ``submit`` queues a prompt; the worker admits it the moment a batch
    slot and enough cache pages free up (claiming
    ceil((prompt+max_new)/page_size) pages so a stream never stalls
    mid-decode), runs its prefill, and folds it into the running
    batched decode step.  Finished streams free their pages and slot
    immediately — the next step can admit a queued stream into them.

    ``static_batching=True`` is the baseline for the A/B: admission
    waits until the WHOLE batch finished, i.e. generation-batch
    barriers (every stream in a generation must finish before any new
    one starts).
    """

    def __init__(self, engine, static_batching=False, greedy=True,
                 warmup=True):
        from ..flags import FLAGS
        self.engine = engine
        self.static = bool(static_batching)
        self.greedy = bool(greedy)
        self._reserve = max(0, int(FLAGS.decode_page_reserve))
        self._preempted = 0       # lock: guarded_by(_cv)
        self._chunk_rr = 0        # round-robin cursor, worker-owned
        lock = threading.Lock()
        # one lock, one wait-set: submit/close wake the worker
        self._cv = _lkd.make_condition('DecodeServer._cv', lock)
        self._queue = deque()     # guarded by _cv
        self._slots = [None] * engine.max_streams  # worker-owned
        self._stopping = False    # guarded by _cv
        self._submitted = 0
        self._completed = 0
        sid = 'd%d' % next(_server_seq)
        reg = _obs.registry() if _obs.enabled() \
            else _obs.MetricsRegistry()
        self._m = _DecodeMetrics(reg, sid)
        if _obs.enabled():
            _obs.maybe_serve_from_env()
        if warmup:
            engine.warmup()
        self._worker = threading.Thread(target=self._loop,
                                        name='decode-worker-%s' % sid,
                                        daemon=True)
        self._worker.start()

    # -- client side ---------------------------------------------------

    def submit(self, prompt, max_new_tokens=16, request_id=None):
        prompt = np.asarray(prompt, dtype=np.int32)
        span = int(prompt.shape[0]) + int(max_new_tokens)
        if span > self.engine.max_seq:
            raise PromptTooLongError(
                "prompt+max_new %d exceeds max_seq %d"
                % (span, self.engine.max_seq))
        if not self.engine.chunked:
            # monolithic prefill: a prompt above the top bucket would
            # only surface as a worker-thread error mid-serve — fail
            # fast HERE, in the submitting thread, typed.  The chunked
            # path has no bucket ceiling (chunks cover any prompt up
            # to max_seq, already checked above).
            self.engine.bucket_for(len(prompt))
        with self._cv:
            if self._stopping:
                raise RuntimeError("DecodeServer is closed")
            rid = request_id if request_id is not None \
                else 'r%d' % self._submitted
            st = DecodeStream(rid, prompt, max_new_tokens)
            self._queue.append(st)
            self._submitted += 1
            self._m.queue_depth.set(len(self._queue))
            self._cv.notify()
        return st

    def drain(self, timeout=60.0):
        """Block until every submitted stream finished."""
        deadline = time.perf_counter() + timeout
        with self._cv:
            while self._queue or any(s is not None
                                     for s in self._slots):
                left = deadline - time.perf_counter()
                if left <= 0:
                    return False
                self._cv.wait(min(left, 0.1))
        return True

    def close(self):
        with self._cv:
            if self._stopping:
                return
            self._stopping = True
            self._cv.notify_all()
        self._worker.join(timeout=30.0)
        self._m.close()

    def stats(self):
        from ..transpiler.memory_model import prefix_cached_bytes
        eng = self.engine
        prefix = eng.prefix
        cached = prefix.cached_pages if prefix is not None else 0
        with self._cv:
            active = sum(1 for s in self._slots if s is not None)
            return {
                'prefix_cache': prefix is not None,
                'chunked_prefill': eng.chunked,
                'prefix_hit_tokens': int(self._m.prefix_hits.value),
                'prefix_miss_tokens':
                    int(self._m.prefix_misses.value),
                'prefix_evicted_tokens':
                    int(self._m.prefix_evicted.value),
                'prefill_chunks': int(self._m.prefill_chunks.value),
                # chunks that carried the tick's decode step (>= 1
                # running row), and the rows they carried
                'prefill_chunks_carrying':
                    int(self._m.prefill_chunks_carrying.value),
                'carried_rows': int(self._m.carried_rows.value),
                'preempted': self._preempted,
                'cached_pages': cached,
                # shared pages are counted ONCE: they live inside the
                # pool resident_bytes already reports — this is the
                # trie-held subset an eviction sweep could reclaim
                'prefix_cached_bytes': prefix_cached_bytes(
                    cached, eng.page_size, dtype=eng.cache.dtype,
                    n_layers=eng.cache.slots,
                    row_widths=eng.cache.row_widths()),
                # where some layers keep a state a stream: the slots
                # that hold one now, and the preemptions that threw one
                # away (recomputed with the stream's pages)
                'state_slots_live': active if eng.state_runs else 0,
                'state_recomputed': int(self._m.state_recomputed.value),
                'submitted': self._submitted,
                'completed': self._completed,
                'dropped': 0,  # admission queues, never sheds
                'active_streams': active,
                'queued': len(self._queue),
                'free_pages': self.engine.cache.free_pages(),
                # where some layers read a window: their group's free
                # pages, its rings' live pages over the decode steps,
                # and the ring columns that took a newer page of their
                # own stream's
                'window_free_pages': eng.cache.window.free_pages()
                if eng.cache.window is not None else 0,
                'kv_window_live_pages': eng.kv_pages['window_live'],
                'window_pages_recycled': eng.kv_pages['window_recycled'],
                'generated_tokens': int(self._m.tokens.value),
                'decode_steps': int(self._m.steps.value),
                'compiles_total': self.engine.compiles_total,
                'compiles_after_warmup':
                    self.engine.compiles_after_warmup,
                'resident_bytes': self.engine.resident_bytes(),
                'static_batching': self.static,
                # routed experts, over the engine's life: assignments
                # of every call, the most tokens one expert got in a
                # call, and the experts a decode step touched (mean over
                # layers and steps)
                'moe_assignments': self.engine.routing['assignments'],
                # where the engine holds a share of the experts, those
                # are the held experts' and these count the rest too
                'moe_all_assignments':
                    self.engine.routing['all_assignments'],
                'moe_max_load': self.engine.routing['max_load'],
                'moe_touched_mean': self.engine.routing['touched']
                / max(self.engine.routing['steps'], 1),
                # KV pages over the engine's decode steps: live ones
                # of the running slots, page-table entries
                'kv_live_pages': self.engine.kv_pages['live'],
                'kv_table_pages': self.engine.kv_pages['table'],
                # engine calls that ran decode rows (steps and carrying
                # chunks), and how many of their three arrays (ids, page
                # tables, context lengths) went in from the host and not
                # from what the call before left on the device
                'step_calls': self.engine.calls['step_calls'],
                'step_host_operands':
                    self.engine.calls['step_host_operands'],
                # where the block runs its layers several times a token:
                # the passes over a weight layer those calls ran
                'loop_passes': self.engine.loop_passes,
            }

    # -- worker side ---------------------------------------------------

    def _pages_needed(self, st):
        # the stream's whole span, claimed at admission so decode never
        # stalls on a mid-stream page fault (prefill's bucket padding
        # needs no extra pages — pack routes pad pages to trash)
        span = len(st.prompt) + st.max_new_tokens
        return -(-span // self.engine.page_size)

    def _claim(self, st, n, span):
        """``n`` pages of the whole context and, where some layers read
        a window, the stream's ring with them: both or neither (the
        stream then stays queued)."""
        cache = self.engine.cache
        pages = cache.alloc(n)
        if pages is not None and cache.window is not None:
            st._ring = cache.window.alloc(self.engine.ring_for(span))
            if st._ring is None:
                cache.free(pages)
                st._ring, pages = [], None
        return pages

    def _stream_pages(self, st):
        """A stream's pages as the engine's calls take them, and where
        some layers keep a state a stream, its slot with them: the
        stream's row of the state pools is the batch slot it holds from
        admission to retirement or preemption (no second allocator)."""
        pages = (st._pages, st._ring) if self.engine.ring_pages \
            else st._pages
        return (pages, st._slot) if self.engine.state_runs else pages

    def _free_ring(self, st):
        if st._ring:
            self.engine.cache.window.free(st._ring)
            st._ring = []

    def _admit(self, st):
        """Page claim + prefill for a slot-reserved stream.  Runs on
        the worker OUTSIDE the lock (device work); the slot itself was
        reserved under ``_cv`` by the loop."""
        eng = self.engine
        with _obs.span('server.admit', args={'rid': st.request_id}):
            if eng.chunked:
                return self._admit_chunked(st)
            pages = self._claim(st, self._pages_needed(st),
                                len(st.prompt) + st.max_new_tokens)
            if pages is None:
                return False
            st._pages = pages
            self._m.pages_allocated.inc(len(pages))
            logits = eng.prefill_into(st.prompt, self._stream_pages(st))
            first = int(np.argmax(logits))
            now = time.perf_counter()
            st.first_token_t = now
            st.tokens.append(first)
            st.token_times.append(now)
            st._ctx_len = len(st.prompt)
            self._m.ttft.observe(st.ttft_s)
            self._m.tokens.inc()
            return True

    def _evict(self, want):
        """LRU-evict up to ``want`` unreferenced trie pages back to the
        pool free list (counted; referenced pages are untouchable)."""
        eng = self.engine
        freed = eng.prefix.evict(want)
        if freed:
            eng.cache.free(freed)
            self._m.prefix_evicted.inc(len(freed) * eng.page_size)
        return len(freed)

    def _admit_chunked(self, st):
        """Incremental admission: match the prompt against the prefix
        trie (claiming cached pages by reference), then claim only the
        pages the computed TAIL needs — and only while the pool keeps
        ``reserve`` pages of headroom for running streams' growth.
        Prefill itself is scheduled chunk-by-chunk in the loop."""
        eng = self.engine
        P, G = eng.page_size, eng.chunk_grid
        if st._prompt_eff is None:
            # preemption resume: the prompt grows the tokens already
            # generated, so re-prefill recomputes the lost KV and its
            # final chunk emits the NEXT token (greedy is
            # deterministic — identical to the uninterrupted stream)
            st._prompt_eff = np.concatenate(
                [st.prompt, np.asarray(st.tokens, np.int32)]) \
                if st.tokens else st.prompt
        prompt = st._prompt_eff
        t = len(prompt)
        m, ref_pages, nodes = 0, [], []
        if eng.prefix is not None and t > 0:
            pages, nodes = eng.prefix.match(prompt)
            # usable cached span: whole grid multiples only (so tail
            # chunks are a suffix of the cold decomposition), capped
            # at t-1 so prefill always computes >= 1 token — the
            # last-position logits are the first generated token
            m = (min(len(pages) * P, t - 1) // G) * G
            keep = m // P
            if keep < len(nodes):
                eng.prefix.release(nodes[keep:])
                nodes = nodes[:keep]
            ref_pages = pages[:keep]
        n_tail = -(-t // P) - m // P
        short = n_tail + self._reserve - eng.cache.free_pages()
        if short > 0 and eng.prefix is not None:
            self._evict(short)
        owned = None
        if eng.cache.free_pages() >= n_tail + self._reserve:
            owned = self._claim(st, n_tail, t + st.max_new_tokens
                                - len(st.tokens))
        if owned is None:
            if nodes:
                eng.prefix.release(nodes)
            return False
        st._pages = list(ref_pages) + list(owned)
        st._owned = list(owned)
        st._ref_nodes = nodes
        st._prefill_pos = m
        self._m.pages_allocated.inc(len(owned))
        self._m.prefix_hits.inc(m)
        self._m.prefix_misses.inc(t - m)
        return True

    def _trie_insert(self, st, upto, acquire):
        """Insert the stream's full pages covering positions
        [0, upto) into the trie; adopted pages leave ``st._owned``
        (the trie owns them now).  With ``acquire`` the stream swaps
        its held refs for refs on the whole inserted path."""
        eng = self.engine
        seq = np.concatenate(
            [st._prompt_eff, np.asarray(st.tokens, np.int32)])[:upto] \
            if st.tokens else st._prompt_eff[:upto]
        if acquire and st._ref_nodes:
            eng.prefix.release(st._ref_nodes)
        nodes, adopted = eng.prefix.insert(seq, st._pages,
                                           acquire=acquire)
        for i in adopted:
            st._owned.remove(st._pages[i])
        if acquire:
            st._ref_nodes = nodes

    def _finish_prefill(self, st, logits):
        """The stream's final chunk ran: emit the first token and
        publish its full prompt pages to the trie, so a stream
        submitted RIGHT NOW — while this one decodes — already hits."""
        eng = self.engine
        first = int(np.argmax(logits))
        now = time.perf_counter()
        if st.first_token_t is None:
            st.first_token_t = now
            self._m.ttft.observe(st.ttft_s)
        st.tokens.append(first)
        st.token_times.append(now)
        st._ctx_len = len(st._prompt_eff)
        self._m.tokens.inc()
        if eng.prefix is not None:
            self._trie_insert(st, st._ctx_len, acquire=True)

    def _plan_prefill_chunks(self, active):
        """This tick's prefill chunks, [(stream, lo, hi)] in the order
        they run: AT MOST the per-tick token budget (a tick always runs
        one chunk; a further one only if it fits in what the first left,
        so a prompt's ragged last chunk is not followed by another
        prompt's whole one: every chunk is a pass over the weights, and
        the budget is what bounds a tick), round-robin across streams so
        one long prompt cannot starve another's TTFT.  Budget 0 =
        unlimited (whole prefill now)."""
        eng = self.engine
        budget = eng.chunk_tokens if eng.chunk_tokens > 0 else None
        pending = [st for st in active if st._prefill_pos is not None]
        if not pending:
            return []
        rr = self._chunk_rr % len(pending)
        self._chunk_rr += 1
        plan, used = [], 0
        for st in pending[rr:] + pending[:rr]:
            lo, t = st._prefill_pos, len(st._prompt_eff)
            while lo < t:
                hi = min(lo + eng.chunk_grid, t)
                if budget is not None and plan and used + hi - lo > budget:
                    return plan
                plan.append((st, lo, hi))
                used += hi - lo
                lo = hi
        return plan

    def _run_prefill_chunks(self, plan, step_operands, rows):
        """Run the planned chunks.  The LAST carries the tick's decode
        step (``step_operands``: ``step``'s three arrays, with ``rows``
        running slots), so the tick reads the weights once for both;
        returns those rows' next tokens (None when ``rows`` is 0: the
        chunk then runs alone)."""
        eng, nxt = self.engine, None
        for n, (st, lo, hi) in enumerate(plan):
            carry = step_operands if rows and n == len(plan) - 1 else ()
            with _obs.span('server.admit', args={'rid': st.request_id}):
                out = eng.prefill_chunk(st._prompt_eff[lo:hi],
                                        self._stream_pages(st), lo, *carry)
                logits, nxt = out[:2] if carry else (out, None)
                self._m.prefill_chunks.inc()
                if carry:
                    self._m.prefill_chunks_carrying.inc()
                    self._m.carried_rows.inc(rows)
                if hi >= len(st._prompt_eff):
                    st._prefill_pos = None
                    self._finish_prefill(st, logits)
                else:
                    st._prefill_pos = hi
        return nxt

    def _ensure_capacity(self, st):
        """Claim-as-context-grows: the next step writes position
        ``ctx_len`` — claim its page if the stream has outgrown its
        claim (evicting unreferenced cache pages first).  On true
        exhaustion preempt: free everything, requeue FRONT, recompute
        at readmission.  Returns False when preempted."""
        eng = self.engine
        if st._ctx_len // eng.page_size < len(st._pages):
            return True
        if eng.cache.free_pages() < 1 and eng.prefix is not None:
            self._evict(1)
        pages = eng.cache.alloc(1)
        if pages is not None:
            st._pages.extend(pages)
            st._owned.extend(pages)
            self._m.pages_allocated.inc(1)
            return True
        if st._ref_nodes:
            eng.prefix.release(st._ref_nodes)
            st._ref_nodes = []
        if st._owned:
            eng.cache.free(st._owned)
            self._m.pages_freed.inc(len(st._owned))
            st._owned = []
        self._free_ring(st)
        st._pages = None
        st._prompt_eff = None
        st._prefill_pos = None
        st._ctx_len = 0
        self._m.preempted.inc()
        if eng.state_runs:
            self._m.state_recomputed.inc()
        with self._cv:
            self._preempted += 1
            self._slots[st._slot] = None
            st._slot = None
            self._queue.appendleft(st)
            self._m.queue_depth.set(len(self._queue))
        return False

    def _retire(self, st):
        self._slots[st._slot] = None
        eng = self.engine
        if eng.chunked:
            if eng.prefix is not None and st._pages:
                # donate the completed stream's full pages — prompt
                # AND generated span — back to the trie (refs 0:
                # instantly reusable, instantly evictable)
                self._trie_insert(st, st._ctx_len, acquire=False)
            if st._ref_nodes:
                eng.prefix.release(st._ref_nodes)
                st._ref_nodes = []
            eng.cache.free(st._owned)
            self._m.pages_freed.inc(len(st._owned))
            st._owned = []
        else:
            eng.cache.free(st._pages)
            self._m.pages_freed.inc(len(st._pages))
        self._free_ring(st)
        st._pages = None
        st.done_t = time.perf_counter()
        # the request's three spans, from the stamps the stream kept
        args = {'rid': st.request_id, 'prompt_tokens': len(st.prompt),
                'new_tokens': len(st.tokens)}
        for name, t0, t1 in (
                ('queued', st.submitted_t, st.admitted_t),
                ('prefill', st.admitted_t, st.first_token_t),
                ('decode', st.first_token_t, st.done_t)):
            _obs.record_span('server.request.' + name, t0, t1, args)
        self._completed += 1
        st._done.set()

    def _loop(self):
        for n in itertools.count():
            with self._cv:
                while not self._stopping and not self._queue and \
                        all(s is None for s in self._slots):
                    self._cv.wait(0.5)
                if self._stopping and not self._queue and \
                        all(s is None for s in self._slots):
                    return
            # one tick: admit what fits, then one decode step (riding
            # a prefill chunk when a prompt is pending).  The idle wait
            # above is no part of it.
            args = {}
            with _obs.span('server.tick', step=n, args=args):
                self._tick(args)

    def _tick(self, args):
        """Admission, this tick's prefills and one batched decode step,
        in as few engine calls as they take: a monolithic prefill is a
        call a prompt at admission; with chunked prefill the tick's
        last chunk carries the decode step (one call for both, and a
        stream whose prompt ends in it decodes from the next tick); a
        tick with no prompt pending is one ``step``.  The token
        accounting after the call is the same whichever ran.  Fills
        ``args`` (the tick span's) with what the tick did."""
        eng = self.engine
        S = eng.max_streams
        with self._cv:
            # admission at step granularity: continuous mode fills
            # any free slot; static mode only starts a fresh
            # generation once the whole previous batch retired
            admissible = []
            if not self.static or \
                    all(s is None for s in self._slots):
                admissible = [i for i, s in enumerate(self._slots)
                              if s is None]
            pending = []
            now = time.perf_counter()
            while self._queue and admissible:
                st = self._queue.popleft()
                slot = admissible.pop(0)
                # reserve the slot under the lock so drain() never
                # sees the stream in neither queue nor slots
                st._slot = slot
                self._slots[slot] = st
                if st.admitted_t is None:
                    st.admitted_t = now
                pending.append(st)
            self._m.queue_depth.set(len(self._queue))
        requeue = [st for st in pending if not self._admit(st)]
        with self._cv:
            for st in requeue:
                self._slots[st._slot] = None
                st._slot = None
                if st.first_token_t is None:
                    st.admitted_t = None    # still waiting, for pages
            if requeue:
                self._queue.extendleft(reversed(requeue))
                self._m.queue_depth.set(len(self._queue))
            active = [s for s in self._slots if s is not None]
            self._m.streams_active.set(len(active))
            args.update(running=0, queued=len(self._queue),
                        admitted=len(pending) - len(requeue))
        if not active:
            return
        if eng.chunked:
            # interleave: up to chunk_tokens of prefill work a tick,
            # its last chunk carrying the decode step of every stream
            # that was decoding before it — a long prompt dents running
            # streams' inter-token latency by the chunk's own rows in
            # one pass over the weights, not by a second program.  A
            # stream whose prompt ends here decodes from the next tick
            decoding = [st for st in active
                        if st._prefill_pos is None]
            decoding = [st for st in decoding
                        if self._ensure_capacity(st)]
            chunks = self._plan_prefill_chunks(active)
        else:
            decoding, chunks = active, []
        if not (decoding or chunks):
            return
        args['running'] = len(decoding)
        # build the batched step inputs from host stream state
        tokens = np.zeros((S,), np.int32)
        pts = np.tile(eng.idle_row, (S, 1))
        ctx = np.zeros((S,), np.int32)
        mpp = eng.pages_per_stream
        for st in decoding:
            i = st._slot
            tokens[i] = st.tokens[-1]
            pts[i, :len(st._pages)] = st._pages
            pts[i, mpp:mpp + len(st._ring)] = st._ring
            ctx[i] = st._ctx_len
        if chunks:
            nxt = self._run_prefill_chunks(chunks, (tokens, pts, ctx),
                                           len(decoding))
        else:
            nxt, _ = eng.step(tokens, pts, ctx)
        if eng.prefix is not None:
            self._m.cached_pages.set(eng.prefix.cached_pages)
        if not decoding:
            return
        now = time.perf_counter()
        self._m.steps.inc()
        finished = []
        for st in decoding:
            i = st._slot
            st._ctx_len += 1
            if len(st.tokens) < st.max_new_tokens:
                st.tokens.append(int(nxt[i]))
                st.token_times.append(now)
                self._m.tokens.inc()
            if len(st.tokens) >= st.max_new_tokens:
                finished.append(st)
        with self._cv:
            for st in finished:
                self._retire(st)
            if finished:
                self._cv.notify_all()

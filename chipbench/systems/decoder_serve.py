"""The system under test for a ``decoder_serve`` configuration: the
program's transformer served by one ``DecodeServer`` on one
``DecodeEngine``, with the deployment's engine settings from the traffic
file.  Construction follows chip_smoke.py's ``phase_serve``.

The benchmark sees the server from outside: ``Tap`` wraps the calls the
server makes into the engine and stamps, on the benchmark's clock, every
token each request receives.
"""
import threading
import time

import numpy as np


class Request(object):
    __slots__ = ('prompt', 'n_out', 'due', 'sent', 'times', 'done',
                 'stream')

    def __init__(self, prompt, n_out, due=None):
        self.prompt = np.asarray(prompt, np.int32)
        self.n_out = int(n_out)
        self.due = due          # when the schedule wanted it sent
        self.sent = None        # when it was sent
        self.times = []         # the benchmark's clock at each token
        self.done = None
        self.stream = None

    def ttft(self):
        start = self.due if self.due is not None else self.sent
        return self.times[0] - start

    def gaps(self):
        return [b - a for a, b in zip(self.times, self.times[1:])]


class Tap(object):
    """Time-stamps tokens at the engine's boundary, on the benchmark's
    clock, whatever the engine's mode.  The server's one worker makes
    every call into the engine (``prefill_into``, ``prefill_chunk``,
    ``step``) and appends the tokens a call produced to its streams
    before it makes the next call.  So on entry to each call the tap
    looks at the live requests: a token a stream has gained since the
    last look came from the call before, and gets that call's exit
    time.  ``calls`` keeps (method, t0, t1, size) of every call: the
    prompt tokens of a prefill, the running slots of a step."""

    METHODS = ('prefill_into', 'prefill_chunk', 'step')

    def __init__(self, engine, spans):
        self.spans = spans
        self.trash = engine.cache.trash
        self.live = []       # requests sent and not yet complete
        self.calls = []      # (method, t0, t1, size)
        self.steps = []      # (t0, t1, running slots, cached tokens read)
        self._exit = None    # exit time of the last call
        self._lock = threading.Lock()   # guards ``live`` (clients add)
        for name in self.METHODS:
            setattr(engine, name, self._wrap(name, getattr(engine, name)))

    def look(self):
        """Stamp the tokens gained since the last look."""
        with self._lock:
            still = []
            for req in self.live:
                have = len(req.stream.tokens)
                while len(req.times) < min(have, req.n_out):
                    req.times.append(self._exit)
                if len(req.times) >= req.n_out:
                    req.done = req.times[-1]
                elif req.stream.error is None:
                    still.append(req)
            self.live[:] = still

    def add(self, req):
        with self._lock:
            self.live.append(req)

    def _wrap(self, name, call):
        span = 'bench.decode_step' if name == 'step' else 'bench.prefill'

        def wrapped(*args):
            self.look()
            t0 = time.perf_counter()
            with self.spans(span):
                out = call(*args)
            self._exit = t1 = time.perf_counter()
            if name == 'step':
                _tokens, page_tables, ctx_lens = args
                running = np.flatnonzero(page_tables[:, 0] != self.trash)
                self.steps.append((t0, t1, len(running),
                                   int(np.sum(ctx_lens[running]))))
                size = len(running)
            else:
                size = len(args[0])
            self.calls.append((name, t0, t1, size))
            return out
        return wrapped

    @property
    def prefills(self):
        """(t0, t1, prompt tokens) of every prefill call, whole or chunk."""
        return [c[1:] for c in self.calls if c[0] != 'step']


class Served(object):
    """Weights, engine and (after ``start``) server and tap."""

    def __init__(self, run, buckets):
        import paddle_tpu as fluid
        from paddle_tpu.inference.decode import DecodeEngine, extract_params
        from paddle_tpu.models import transformer
        c, e = run.config, run.traffic['engine']
        self.run = run
        self.layers, self.heads = c['num_hidden_layers'], \
            c['num_attention_heads']
        with run.phases('startup_program'):
            scope = fluid.Scope()
            main_p, startup = fluid.Program(), fluid.Program()
            main_p.random_seed = startup.random_seed = \
                run.seed % (2 ** 31 - 1) + 1
            with fluid.program_guard(main_p, startup):
                transformer.build(
                    vocab_size=c['vocab_size'],
                    seq_len=c['max_position_embeddings'],
                    n_layers=self.layers, d_model=c['hidden_size'],
                    n_heads=self.heads, d_ff=c['ffn_dim'])
            place = fluid.CPUPlace() if run.rehearse else fluid.TPUPlace(0)
            fluid.Executor(place).run(startup, scope=scope)
            self.params = extract_params(scope, self.layers)
        with run.phases('pool_allocation'):
            # the deployment's engine settings, all of them, are the
            # traffic file's ("arithmetic" is its note on the sizing)
            self.engine = DecodeEngine(
                self.params, n_layers=self.layers, n_heads=self.heads,
                prefill_bucket=max(buckets),
                **{k: v for k, v in e.items() if k != 'arithmetic'})
            # the deployment serves these prompt lengths and no others:
            # warm their buckets only (warmup() walks engine.buckets)
            self.engine.buckets = [b for b in self.engine.buckets
                                   if b in buckets]
        with run.phases('compile_and_warm_execution'):
            self.engine.warmup()
        self.server = self.tap = None

    def start(self):
        from paddle_tpu.inference.decode import DecodeServer
        with self.run.phases('server_start'):
            self.tap = Tap(self.engine, self.run.spans)
            self.server = DecodeServer(self.engine, warmup=False)

    def submit(self, req):
        with self.run.spans('bench.submit'):
            req.sent = time.perf_counter()
            req.stream = self.server.submit(req.prompt,
                                            max_new_tokens=req.n_out)
        self.tap.add(req)
        return req.stream

    def close(self):
        """Stop the server (its worker has then made its last call) and
        stamp what that call produced."""
        if self.server is not None:
            self.server.close()
            self.tap.look()

    def scratch_bytes(self):
        """The most scratch any of the engine's compiled programs
        declares (``memory_analysis().temp_size_in_bytes``, read from
        the executables the engine already holds): the whole-pool copies
        of the step and of pack live there, and ``peak_bytes_in_use``
        does not count them."""
        eng = self.engine
        programs = [eng._step] + [x for d in (eng._prefill, eng._pack,
                                              eng._chunk)
                                  for x in d.values()]
        return max(int(x.memory_analysis().temp_size_in_bytes)
                   for x in programs if x is not None)

    def replay(self, prompt, n_new):
        """Prefill then decode through the pages by hand, greedy: the
        logits of every position the engine produced ([n_new, V]) and
        the tokens it chose (chip_smoke.py's replay)."""
        eng = self.engine
        pages = eng.cache.alloc(-(-(len(prompt) + n_new) // eng.page_size))
        if eng.chunked:     # the way this deployment's server prefills
            for lo, hi in eng.chunk_spans(len(prompt)):
                first = eng.prefill_chunk(prompt[lo:hi], pages, lo)
        else:
            first = eng.prefill_into(np.asarray(prompt), pages)
        rows = [first]
        toks = [int(np.argmax(rows[0]))]
        for j in range(n_new - 1):
            pt = np.full((eng.max_streams, eng.pages_per_stream),
                         eng.cache.trash, np.int32)
            pt[0, :len(pages)] = pages
            t_in = np.zeros((eng.max_streams,), np.int64)
            t_in[0] = toks[-1]
            ctx = np.zeros((eng.max_streams,), np.int32)
            ctx[0] = len(prompt) + j
            rows.append(eng.step(t_in, pt, ctx)[1][0])
            toks.append(int(np.argmax(rows[-1])))
        eng.cache.free(pages)
        return np.stack(rows), toks


def buckets_for(page_size, lengths):
    """The prefill buckets (page-size multiples doubling) that prompts of
    these lengths fall into."""
    out = set()
    for n in lengths:
        b = page_size
        while b < n:
            b *= 2
        out.add(b)
    return sorted(out)

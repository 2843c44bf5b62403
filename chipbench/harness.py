"""What every cell's run shares: the clock, set-up phases, host spans,
the compile count, the traced seconds, and the one result line."""
import contextlib
import gc
import glob
import gzip
import importlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

from . import peaks, xplane

T0 = time.perf_counter()    # process start, as near as Python lets us see
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def info(tag, obj):
    """An earlier line of the run: a tag and one JSON object."""
    print('%s %s' % (tag, json.dumps(obj)), flush=True)


class Phases(object):
    """Set-up by phase: ``with phases('compile'):`` adds the seconds."""

    def __init__(self):
        self.seconds = {}

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = round(
                self.seconds.get(name, 0.0) + time.perf_counter() - t0, 3)


class Spans(object):
    """The benchmark's own spans around its calls into the program, on
    the host clock (a traced run lays them over the trace afterwards)."""

    def __init__(self):
        self.log = {}           # name -> [(t0, t1), ...]
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            with self._lock:
                self.log.setdefault(name, []).append((t0, t1))

    def between(self, name, lo, hi):
        """Durations (seconds) of the spans of ``name`` inside [lo, hi)."""
        return [b - a for a, b in self.log.get(name, ())
                if a >= lo and b < hi]


class CompileCounter(object):
    """Counts XLA compilations in this process, whoever asks for them:
    jax reports each backend compile (and each load from the persistent
    cache) as an event."""

    EVENTS = ('/jax/core/compile/backend_compile_duration',
              '/jax/compilation_cache/cache_retrieval_time_sec')

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self.log = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, seconds, **_kw):
        if name == self.EVENTS[0]:
            self.count += 1
        if name in self.EVENTS and seconds >= 0.5:
            self.log.append([name.rsplit('/', 1)[1], round(seconds, 2)])


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation."""
    v = sorted(values)
    if not v:
        return None
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def fifths(lo, hi):
    """The five equal parts of [lo, hi) as (lo, hi) pairs."""
    step = (hi - lo) / 5.0
    return [(lo + i * step, lo + (i + 1) * step) for i in range(5)]


def chipbench_marker(x):
    """The marker program: its name is how xplane finds the marks."""
    return x + 1


class Run(object):
    """One run of one cell: arguments, the cell's files, the device."""

    def __init__(self, args, bench, cell):
        self.args = args
        self.bench = bench
        self.cell = cell
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.rehearse = bool(args.rehearse)
        cfg_entry = next(c for c in bench['configs']
                         if c['name'] == cell['config'])
        with open(os.path.join(ROOT, cfg_entry['file'])) as f:
            self.config = json.load(f)
        self.traffic = load_json('traffic', cell['traffic'] + '.json')
        if self.rehearse:
            self.config = dict(self.config, **self.config.get('rehearse', {}))
            self.traffic = dict(self.traffic,
                                **self.traffic.get('rehearse', {}))
        self.phases = Phases()
        self.spans = Spans()
        self.compiles = None
        self.device = None
        self.peaks = None
        self.obs = {}       # what the readers of per-layer metrics see
        self.scratch_bytes = 0

    # -- device ----------------------------------------------------------

    def claim_device(self):
        """Stamp the device, or leave with a non-zero code and no result
        where jax finds no TPU or fewer chips than the cell asks for."""
        import jax
        devs = jax.devices()
        self.device = {'platform': devs[0].platform,
                       'kind': devs[0].device_kind, 'count': len(devs)}
        need = int(self.cell['chips'])
        if not self.rehearse:
            if devs[0].platform != 'tpu' or len(devs) < need:
                sys.stderr.write(
                    'chipbench: cell %s needs %d TPU chip(s); jax found %s\n'
                    % (self.cell['name'], need, json.dumps(self.device)))
                sys.exit(3)
            self.peaks = peaks.lookup(devs[0].device_kind)
        self.compiles = CompileCounter()
        self.devices = devs[:need]
        self._marker = jax.jit(chipbench_marker)
        self._marker_arg = jax.device_put(0, devs[0])
        self.mark()

    def memory_peak_bytes(self):
        """Peak on the fullest chip: ``peak_bytes_in_use`` (resident
        arrays) plus ``scratch_bytes``, the scratch of the window's
        largest program, which every kind sets from the compiled
        program's ``memory_analysis()`` (the counter does not see it);
        the MEMORY line gives the parts."""
        import jax
        peaks_ = [(d.memory_stats() or {}).get('peak_bytes_in_use', 0)
                  for d in jax.devices()[:int(self.cell['chips'])]]
        return int(max(peaks_)) + self.scratch_bytes

    # -- the window --------------------------------------------------------

    @staticmethod
    def quiet_gc():
        gc.collect()
        gc.freeze()

    def trace_seconds(self):
        return float(self.traffic.get('trace_seconds', 3.0))

    def mark(self):
        """Run the marker program and wait for it: the host clock at its
        completion.  Its executions are the anchors between the host's
        clock and the trace's (the end of the marker's device event is
        this moment, to a tenth of a millisecond)."""
        self._marker(self._marker_arg).block_until_ready()
        return time.perf_counter()

    @contextlib.contextmanager
    def traced(self):
        """The traced seconds, between two marks.  Only the device is
        traced: with the host tracer on, at any level, a 154 MB
        ``device_put`` took 544 ms in place of 36 (my chip run, PR 23),
        and the Python tracer starves the program's threads (PR 22 read
        an 80% idle share for a 30% one).  So the benchmark's host spans
        are kept on the host clock and laid over the trace afterwards.
        The trace goes to a temporary directory, is read and removed."""
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        log_dir = tempfile.mkdtemp(prefix='chipbench_trace_')
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            t_a = self.mark()
            yield
            t_b = self.mark()
        finally:
            jax.profiler.stop_trace()
            paths = glob.glob(os.path.join(log_dir, '**', '*.xplane.pb'),
                              recursive=True)
            if paths:
                path = max(paths, key=os.path.getmtime)
                self.obs['xplane_bytes'] = os.path.getsize(path)
                self.obs['trace'] = xplane.load(path)
            shutil.rmtree(log_dir, ignore_errors=True)
        self.obs['marks'] = (t_a, t_b)

    def lay_spans_over_trace(self):
        """Put the host spans of the traced seconds into the trace as a
        host plane, on the trace's clock (a line through the two marks)."""
        tr = self.obs.get('trace')
        win = tr and xplane.window(tr)
        if not win:
            return
        t_a, t_b = self.obs['marks']
        scale = (win[1] - win[0]) / (t_b - t_a)
        events = [[name, int(win[0] + (a - t_a) * scale),
                   int((b - a) * scale)]
                  for name, log in self.spans.log.items()
                  for a, b in log if a >= t_a and b <= t_b]
        tr['planes'].append({'name': '/host:bench', 'lines': [
            {'name': 'spans', 'events': sorted(events, key=lambda e: e[1])}]})
        keep = self.args.keep_trace
        if keep:
            os.makedirs(keep, exist_ok=True)
            with gzip.open(os.path.join(
                    keep, self.cell['name'] + '.trace.json.gz'), 'wt') as f:
                json.dump(tr, f)

    # -- results -----------------------------------------------------------

    def metric_entries(self, section):
        """The metrics of ``section`` that this cell reports."""
        return [m for m in self.bench[section]
                if self.cell['name'] in m.get('workloads',
                                              [self.cell['name']])]

    def per_layer(self):
        """Ask each per-layer metric's reader for its number."""
        out = {}
        for m in self.metric_entries('per_layer'):
            decl = load_json('metrics', m['name'] + '.json')
            reader = importlib.import_module(
                'chipbench.metrics.' + decl['reader'])
            value = reader.read(self, **decl.get('params', {}))
            if value is not None:
                out[m['name']] = {'value': float(value), 'unit': m['unit']}
        return out

    def emit(self, correct, attempted, failed, end_to_end, why=()):
        """Print the earlier lines and the one last line."""
        info('SETUP_PHASES', self.phases.seconds)
        info('COMPILES', {'total': self.compiles.count,
                          'over_half_a_second': self.compiles.log})
        for w in why:
            print('INCORRECT %s' % w, flush=True)
        device = dict(self.device,
                      memory_peak_bytes=self.memory_peak_bytes())
        info('MEMORY', {'peak_bytes_in_use': device['memory_peak_bytes']
                        - self.scratch_bytes,
                        'program_scratch_bytes': self.scratch_bytes})
        line = {'correct': bool(correct) and not why,
                'attempted': int(attempted), 'failed': int(failed)}
        if self.trace:
            self.lay_spans_over_trace()
            metrics = self.per_layer()
            tr = self.obs.get('trace')
            win = tr and xplane.window(tr)
            bi = tr and xplane.busy_and_idle(tr, win)
            if bi:
                device['busy_s'], device['window_s'] = bi[0], bi[1]
                for text in xplane.idle_lines(
                        bi[2], self.obs.get('host_idle_share', bi[2])):
                    print(text, flush=True)
                line['breakdown'] = {
                    'device_ops': xplane.top(xplane.op_seconds(tr, win)),
                    'idle_gaps': xplane.top(xplane.idle_gaps(tr, win))}
        else:
            units = {m['name']: m['unit']
                     for m in self.metric_entries('end_to_end')}
            metrics = {k: {'value': float(v), 'unit': units[k]}
                       for k, v in end_to_end.items() if k in units}
        if self.rehearse:
            # a CPU's times are not the chip's: counts and correctness only
            metrics = {}
            line['rehearsal'] = True
        line['metrics'] = metrics
        line['device'] = device
        print(json.dumps(line), flush=True)


def spread(values):
    """Distance between the quartiles as a share of the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)

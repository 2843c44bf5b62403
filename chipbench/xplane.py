"""From a profiler trace to numbers: the one reduction every PR shares.

A trace is held as plain data so that a recorded one can sit in
``tests/`` as JSON::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

``load`` makes that from an ``.xplane.pb``; everything else here is
arithmetic on intervals.  Times inside a trace are nanoseconds on the
profiler's clock; what leaves this module is seconds.

Device planes are those named ``/device:TPU:<n>``.  Their ``XLA Ops``
line holds one event per executed HLO op, their ``XLA Modules`` line one
per executed program (``jit_step_fn(...)``).  Host planes hold spans of
the host: ``/host:bench`` the benchmark's own.
"""
import bisect
import re

OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
COLLECTIVE = re.compile(
    r'all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all')
# an idle gap shorter than this is the device's own turn-around between
# two ops, not the host holding it back
MIN_GAP_NS = 20_000


def load(path):
    """Read the device planes of an ``.xplane.pb`` into the plain form
    (the benchmark traces the device alone and lays its own host spans
    over the trace afterwards: harness.Run.lay_spans_over_trace)."""
    import jax
    planes = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith('/device:'):
            continue
        lines = [{'name': line.name,
                  'events': [[short_name(ev.name), int(ev.start_ns),
                              int(ev.duration_ns)] for ev in line.events]}
                 for line in plane.lines]
        planes.append({'name': plane.name,
                       'lines': [ln for ln in lines if ln['events']]})
    return {'planes': planes}


_SHAPE = re.compile(r'\w+\[[\d,]*\]')


def short_name(text):
    """The trace names a device op by its whole HLO instruction
    (``%fusion.69 = (f32[256]{..}, bf16[256,56,56,256]{..}) fusion(...),
    kind=kOutput, calls=...``).  Keep the op's name, the fusion kind and
    the largest result shape: ``fusion.69:kOutput:bf16[256,56,56,256]``.
    Anything else (a program's name) is left as it is."""
    if not text.startswith('%') or ' = ' not in text:
        return text
    name, rest = text[1:].split(' = ', 1)
    result = rest.split(') ', 1)[0] if rest.startswith('(') \
        else rest.split(' ', 1)[0]
    shapes = _SHAPE.findall(result)
    kind = re.search(r'kind=(\w+)', rest)

    def elements(shape):
        dims = [int(d) for d in shape[shape.index('[') + 1:-1].split(',')
                if d]
        n = 1
        for d in dims:
            n *= d
        return n
    parts = [name] + ([kind.group(1)] if kind else []) \
        + ([max(shapes, key=elements)] if shapes else [])
    return ':'.join(parts)


# -- intervals ---------------------------------------------------------------

def union(intervals):
    """Sorted, disjoint cover of ``intervals`` ([lo, hi) pairs)."""
    out = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def clip(intervals, lo, hi):
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals):
    return sum(b - a for a, b in intervals)


def subtract(a, b):
    """Parts of the disjoint sorted cover ``a`` not covered by ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append([cur, hi])
    return out


# -- picking events ----------------------------------------------------------

def device_planes(trace):
    return [p for p in trace['planes']
            if re.match(r'/device:TPU:\d+$', p['name'])]


def line_events(plane, line_name):
    for line in plane['lines']:
        if line['name'] == line_name:
            return line['events']
    return []


def host_spans(trace):
    """Every kept host span as (name, lo, hi), all threads together."""
    out = []
    for p in trace['planes']:
        if p['name'].startswith('/host:'):
            for line in p['lines']:
                out.extend((n, s, s + d) for n, s, d in line['events'])
    return out


MARKER = 'jit_chipbench_marker'


def window(trace):
    """[lo, hi) of the traced window: from the end of the first
    execution of the benchmark's marker program to the end of the last
    (chip 0's module line).  None without two marks."""
    planes = device_planes(trace)
    ends = sorted(s + d for n, s, d in
                  (line_events(planes[0], MODULES_LINE) if planes else ())
                  if n.startswith(MARKER))
    if len(ends) < 2:
        return None
    return ends[0], ends[-1]


# -- reductions --------------------------------------------------------------

def busy(plane, win):
    """Disjoint intervals inside ``win`` in which an op ran on the plane."""
    ops = line_events(plane, OPS_LINE) or line_events(plane, MODULES_LINE)
    return clip(union([s, s + d] for _n, s, d in ops), *win)


def busy_and_idle(trace, win):
    """(busy_s, window_s, idle_share) averaged over the device planes."""
    planes = device_planes(trace)
    if not planes or win is None:
        return None
    span = win[1] - win[0]
    busy_s = sum(total(busy(p, win)) for p in planes) / len(planes) / 1e9
    return busy_s, span / 1e9, 1.0 - busy_s / (span / 1e9)


def module_calls(trace, win, prefix):
    """Device seconds of each execution of programs whose name starts
    with ``prefix`` (``jit_step_fn``), chip 0's view, inside the window."""
    planes = device_planes(trace)
    if not planes:
        return []
    return [d / 1e9 for n, s, d in line_events(planes[0], MODULES_LINE)
            if n.startswith(prefix) and s >= win[0] and s + d <= win[1]]


def module_of(plane):
    """A function from an op's start to the name of the program it ran
    in, from the plane's module line."""
    mods = sorted((s, s + d, n) for n, s, d
                  in line_events(plane, MODULES_LINE))
    starts = [m[0] for m in mods]

    def find(t):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < mods[i][1]:
            return re.sub(r'\(.*', '', mods[i][2])
        return ''
    return find


def op_seconds(trace, win):
    """{"<program>/<op>": device seconds inside the window}, chip 0."""
    planes = device_planes(trace)
    if not planes:
        return {}
    find = module_of(planes[0])
    out = {}
    for n, s, d in line_events(planes[0], OPS_LINE):
        if s >= win[0] and s + d <= win[1]:
            key = '%s/%s' % (find(s), n)
            out[key] = out.get(key, 0.0) + d / 1e9
    return out


def exposed_collective_s(trace, win):
    """Seconds, averaged over chips, in which a collective ran on a chip
    and no other op did."""
    planes = device_planes(trace)
    if not planes:
        return None
    acc = 0.0
    for p in planes:
        coll, comp = [], []
        for line in p['lines']:
            if line['name'] == MODULES_LINE:
                continue
            for n, s, d in line['events']:
                if COLLECTIVE.search(n):
                    coll.append([s, s + d])
                elif line['name'] == OPS_LINE:
                    comp.append([s, s + d])
        acc += total(subtract(clip(union(coll), *win),
                              clip(union(comp), *win)))
    return acc / len(planes) / 1e9


def idle_gaps(trace, win):
    """{span name: idle seconds on chip 0 while that span was the
    innermost one open on the host}; ``no_span`` where none was."""
    planes = device_planes(trace)
    if not planes:
        return {}
    gaps = [g for g in subtract([list(win)], busy(planes[0], win))
            if g[1] - g[0] >= MIN_GAP_NS]
    spans = sorted(host_spans(trace), key=lambda x: x[1])
    starts = [x[1] for x in spans]
    out = {}
    for lo, hi in gaps:
        # spans that can overlap the gap start before its end
        cand = [x for x in spans[:bisect.bisect_left(starts, hi)]
                if x[2] > lo]
        cuts = sorted({lo, hi} | {t for _n, a, b in cand for t in (a, b)
                                  if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            cover = [x for x in cand if x[1] <= a and x[2] >= b]
            name = min(cover, key=lambda x: x[2] - x[1])[0] \
                if cover else 'no_span'
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def top(d, n=10):
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


IDLE_MISMATCH_POINTS = 10.0


def idle_lines(trace_idle, host_idle):
    """The lines a traced run prints about its two idle shares (both as
    shares of 1).  The second way is the host clock outside the traced
    seconds; when the two differ by more than ten points of the window
    the tracer has changed what it measured."""
    lines = ['IDLE_SHARE trace=%.4f host_clock=%.4f'
             % (trace_idle, host_idle)]
    if abs(trace_idle - host_idle) * 100.0 > IDLE_MISMATCH_POINTS:
        lines.append('IDLE_MISMATCH trace=%.4f host_clock=%.4f: they '
                     'differ by more than %g points; lighten the tracing'
                     % (trace_idle, host_idle, IDLE_MISMATCH_POINTS))
    return lines

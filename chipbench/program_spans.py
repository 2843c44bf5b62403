"""The program's own spans, for the readers of per-layer metrics.

The program records its spans (``paddle_tpu/observability/tracing.py``)
into one bounded ring whose events carry a start relative to a public
clock origin, a duration, an ``id`` and the ``parent`` id of the span
that was open around them.  The ring's clock is ``time.perf_counter()``,
the clock of ``harness.Run.mark()``, so its events go onto the device
trace's clock by the same line through the two marks that
``Run.lay_spans_over_trace`` uses: no host tracer is involved.

``spans(run)`` is the one entry point: the events as ``Span`` tuples on
``perf_counter()``, read once a run and kept in ``run.obs``.  The first
call also lays the tick tree over the trace (``lay``) and prints the
``PROGRAM_SPANS`` line.  It returns ``None`` where the program has no
such ring (a parent commit from before the spans), where the ring holds
no span, and where the ring's ``dropped`` count shows that the window was
cut; a reader then returns ``None`` and the metric is left out.

``--keep-trace`` is written by ``lay_spans_over_trace`` before any reader
runs, so the file it leaves lacks the ``/host:program`` plane.
"""
import collections
import statistics

from . import harness, xplane

Span = collections.namedtuple('Span', 'name t0 t1 id parent args')

PLANE = '/host:program'
TICK = 'server.tick'
STEP = 'decode.step'
PREFILLS = ('decode.prefill_into', 'decode.prefill_chunk')
# how far the line through the two marks may put a host stamp from the
# device's clock (harness.Run.mark: a tenth of a millisecond)
CLOCK_SLACK_NS = 100_000


def ring_spans(since):
    """The ring's spans as ``Span`` tuples on ``perf_counter()``, oldest
    first; ``None`` without a ring that names its clock, and where the
    ring evicted events that ended after ``since``."""
    from paddle_tpu.observability import timeline
    # the ring's public clock origin and its ``dropped`` count came
    # with the spans: a program without the one has none of the three
    origin = getattr(timeline, 'CLOCK_ORIGIN', None)
    if origin is None:
        return None
    ring = timeline.ring()
    events = ring.events()
    if ring.dropped and events and \
            origin + events[0]['ts'] + events[0]['dur'] > since:
        return None     # what was evicted may have been in the window
    return [Span(e['name'], origin + e['ts'], origin + e['ts'] + e['dur'],
                 e['id'], e['parent'], e['args'] or {})
            for e in events if 'id' in e]


def spans(run):
    """The program's spans of this run (see the module's docstring)."""
    if 'program_spans' not in run.obs:
        got = ring_spans(run.obs.get('t_open', harness.T0))
        run.obs['program_spans'] = got or None
        if got:
            lay(run)
            harness.info('PROGRAM_SPANS', summary(run))
    return run.obs['program_spans']


def inside(events, lo, hi, name=None):
    return [s for s in events if s.t0 >= lo and s.t1 < hi
            and (name is None or s.name == name)]


def window(run):
    """[lo, hi) of the untraced window on the host clock."""
    return run.obs['t_open'], run.obs['t_host_end']


def children(events):
    """{span id: [its direct children]}."""
    out = {}
    for s in events:
        if s.parent is not None:
            out.setdefault(s.parent, []).append(s)
    return out


def self_seconds(span, kids):
    """A span's duration less its direct children's (they run on its
    thread, one after another)."""
    return (span.t1 - span.t0) - sum(k.t1 - k.t0
                                     for k in kids.get(span.id, ()))


def descendants(span, kids):
    out = []
    for k in kids.get(span.id, ()):
        out.append(k)
        out.extend(descendants(k, kids))
    return out


def prefill_stalls(events, lo, hi):
    """For every tick inside [lo, hi) that ran a decode step: the seconds
    the engine's prefill calls took in that tick before the step."""
    kids = children(events)
    out = []
    for tick in inside(events, lo, hi, TICK):
        below = descendants(tick, kids)
        step = next((s for s in below if s.name == STEP), None)
        if step is not None:
            out.append(sum(s.t1 - s.t0 for s in below
                           if s.name in PREFILLS and s.t1 <= step.t0))
    return out


# -- onto the trace's clock --------------------------------------------------

def to_trace(run):
    """A function from a ``perf_counter()`` reading inside the traced
    seconds to nanoseconds on the trace's clock, or None."""
    tr = run.obs.get('trace')
    win = tr and xplane.window(tr)
    if not win or 'marks' not in run.obs:
        return None
    t_a, t_b = run.obs['marks']
    scale = (win[1] - win[0]) / (t_b - t_a)
    return lambda t: win[0] + (t - t_a) * scale


def lay(run):
    """Append the tick tree of the traced seconds (``server.tick`` and
    what it encloses; not the seconds-long ``server.request.*`` spans,
    which would swallow every ``no_span`` gap) to the trace as plane
    ``/host:program``, once."""
    events, clock = run.obs.get('program_spans'), to_trace(run)
    tr = run.obs.get('trace')
    if not events or clock is None or \
            any(p['name'] == PLANE for p in tr['planes']):
        return
    t_a, t_b = run.obs['marks']
    kids = children(events)
    rows = []
    for tick in inside(events, t_a, t_b, TICK):
        for s in [tick] + descendants(tick, kids):
            rows.append([s.name, int(clock(s.t0)),
                         int(clock(s.t1)) - int(clock(s.t0))])
    tr['planes'].append({'name': PLANE, 'lines': [
        {'name': 'spans', 'events': sorted(rows, key=lambda e: e[1])}]})


def step_gaps(run, events):
    """[(launch gap, return gap)] in seconds for every ``decode.step``
    span of the traced seconds: from the span's start to the start of
    the step program on the device, and from the program's end on the
    device to the span's end.  A step whose span holds no execution of
    the program, or more than one, is left out."""
    clock = to_trace(run)
    if not events or clock is None:
        return []
    tr = run.obs['trace']
    prog = run.config['device_programs']['step']
    mods = sorted((s, s + d) for n, s, d in xplane.line_events(
        xplane.device_planes(tr)[0], xplane.MODULES_LINE)
        if n.startswith(prog))
    out = []
    for sp in inside(events, *run.obs['marks'], name=STEP):
        lo, hi = clock(sp.t0), clock(sp.t1)
        mine = [m for m in mods if lo - CLOCK_SLACK_NS <= m[0] < hi]
        if len(mine) == 1:
            out.append(((mine[0][0] - lo) / 1e9, (hi - mine[0][1]) / 1e9))
    return out


# -- the PROGRAM_SPANS line --------------------------------------------------

def _ms(values):
    v = sorted(values)
    return {'n': len(v), 'min': 1e3 * v[0],
            'median': 1e3 * statistics.median(v),
            'p95': 1e3 * harness.percentile(v, 95), 'max': 1e3 * v[-1]}


def summary(run):
    """What the program's spans say about this run, for PERF.md: per
    span name over the untraced window its count, mean and mean self
    time; over the traced seconds the two gaps of every decode step, in
    the order the steps ran."""
    events = run.obs['program_spans']
    out = {'events': len(events)}
    if 't_open' in run.obs:
        kids = children(events)
        by_name = {}
        for s in inside(events, *window(run)):
            by_name.setdefault(s.name, []).append(s)
        out['window'] = {
            name: {'n': len(group),
                   'median_ms': 1e3 * statistics.median(
                       s.t1 - s.t0 for s in group),
                   'mean_ms': 1e3 * statistics.mean(
                       s.t1 - s.t0 for s in group),
                   'self_mean_ms': 1e3 * statistics.mean(
                       self_seconds(s, kids) for s in group)}
            for name, group in sorted(by_name.items())}
    gaps = step_gaps(run, events)
    if gaps:
        out['traced_steps'] = {
            'launch_gap_ms': _ms([g[0] for g in gaps]),
            'return_gap_ms': _ms([g[1] for g in gaps]),
            'each_ms': [[round(1e3 * x, 3) for x in g] for g in gaps]}
    return out

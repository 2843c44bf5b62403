"""Set-up by the program's own spans: over the spans of the ring that
ENDED in set-up, ``[harness.T0, t_open)``, the seconds of one span name
(``decode.warmup`` and, beneath it, ``decode.compile.trace``,
``.lower``, ``.backend`` and ``decode.warmup.run``), or with ``arg`` and
``equals`` the count of those whose ``args[arg]`` is that value
(``decode.compile.backend`` says ``cache``: ``'hit'``, ``'miss'``,
``'off'``).

``None``, and the metric is left out, where the program has no such span
(a parent commit from before them) and where the ring's ``dropped`` is
not zero: the ring evicts its oldest events first and set-up's are the
oldest, so a cut ring gives no number, never a part of a sum.

The first call of a run prints the ``SETUP_SPANS`` line: a row a
``decode.compile`` span (``program``, ``bucket``, ``trace_s``,
``lower_s``, ``backend_s``, ``cache``, and ``warm_run_s`` of that
executable's ``decode.warmup.run``), ``warmup_self_s`` (``decode.warmup``
less its direct children: what no span under it names), and the ring's
``events`` and ``dropped`` as the run ends.
"""
from .. import harness
from .. import program_spans as ps

WARMUP = 'decode.warmup'
COMPILE = 'decode.compile'
RUN = 'decode.warmup.run'
STAGES = ('trace', 'lower', 'backend')
_STAGE_OF = {COMPILE + '.' + stage: stage for stage in STAGES}


def spans(run):
    """The spans that ended in set-up, oldest first, read once a run; or
    ``None`` (see the module's docstring)."""
    if 'setup_spans' not in run.obs:
        run.obs['setup_spans'] = _read(run)
    return run.obs['setup_spans']


def _read(run):
    from paddle_tpu.observability import timeline
    t_open = run.obs.get('t_open')
    # the ring's clock origin and its ``dropped`` count came with the
    # spans (program_spans.ring_spans): without the one, none of it
    if t_open is None or getattr(timeline, 'CLOCK_ORIGIN', None) is None:
        return None
    ring = timeline.ring()
    said = {'events': len(ring.events()), 'dropped': ring.dropped}
    got = None
    if not ring.dropped:
        # (every event of the ring ends after harness.T0, taken before
        # the program was imported)
        got = [s for s in ps.ring_spans(harness.T0) or () if s.t1 < t_open]
        said = dict(summary(got), **said)
    harness.info('SETUP_SPANS', said)
    return got


def summary(events):
    """The ``SETUP_SPANS`` line's rows and ``warmup_self_s``."""
    kids = ps.children(events)
    runs = {}
    for s in events:
        if s.name == RUN:
            key = (s.args.get('program'), s.args.get('bucket'))
            runs[key] = runs.get(key, 0.0) + s.t1 - s.t0
    rows = []
    for c in events:
        if c.name != COMPILE:
            continue
        row = {'program': c.args.get('program'),
               'bucket': c.args.get('bucket')}
        for k in kids.get(c.id, ()):
            stage = _STAGE_OF.get(k.name)
            if stage:
                row[stage + '_s'] = k.t1 - k.t0
                if 'cache' in k.args:
                    row['cache'] = k.args['cache']
        row['warm_run_s'] = runs.get((row['program'], row['bucket']))
        rows.append(row)
    return {'programs': rows,
            'warmup_self_s': sum(ps.self_seconds(s, kids)
                                 for s in events if s.name == WARMUP)}


def read(run, span, arg=None, equals=None):
    mine = [s for s in spans(run) or () if s.name == span]
    if not mine:
        return None
    if arg is not None:
        return sum(1 for s in mine if s.args.get(arg) == equals)
    return sum(s.t1 - s.t0 for s in mine)

"""Lightweight span tracing bridging the registry, the timeline ring and
XLA traces.

``span("executor.compile")`` is a context manager that does three things
at once:

- feeds the wall-clock duration into the registry histogram
  ``paddle_tpu_span_seconds{span="executor.compile"}`` (so /metrics
  carries per-region latency distributions with no profiler attached);
- records the region into the timeline ring (observability/timeline.py)
  with an ``id`` and the ``parent`` id of the span open around it on the
  same thread, so a reader has each region's start, end and cause on
  ``time.perf_counter()``'s clock, and its self time as its duration
  minus its children's — with no profiler attached either;
- annotates the XLA trace via ``jax.profiler.TraceAnnotation``, so when
  a host trace *is* being captured (profiler.py) the same region names
  show up on the TensorBoard/Perfetto timeline.

When metrics are disabled, ``span()`` returns one shared no-op object —
no allocation, no annotation, no clock read, no ring record — so
instrumented paths cost a single function call.
"""
import itertools
import threading
import time

from . import metrics as _metrics
from . import timeline as _timeline

__all__ = ['span', 'record_span']

_lock = threading.Lock()
_span_children = {}  # span name -> histogram child handle
_ids = itertools.count(1)       # next() is atomic under the GIL
_open = threading.local()       # .stack: ids of this thread's open spans


class _NullSpan(object):
    """Shared do-nothing span for the disabled path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _child(name):
    child = _span_children.get(name)
    if child is None:
        hist = _metrics.registry().histogram(
            'paddle_tpu_span_seconds',
            'wall-clock duration of named host-side spans',
            labelnames=('span',))
        child = hist.labels(span=name)
        with _lock:
            _span_children.setdefault(name, child)
    return child


class _Span(object):
    __slots__ = ('_child', '_ann', '_t0', '_name', '_step', '_args',
                 '_id', '_parent')

    def __init__(self, child, ann, name, step, args):
        self._child = child
        self._ann = ann
        self._name = name
        self._step = step
        self._args = args

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        stack = getattr(_open, 'stack', None)
        if stack is None:
            stack = _open.stack = []
        self._parent = stack[-1] if stack else None
        self._id = next(_ids)
        stack.append(self._id)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        _open.stack.pop()
        self._child.observe(dur)
        # one measurement, two sinks: the histogram and the ring
        _timeline.ring().record(
            self._name, cat='span', t0=self._t0, dur=dur, step=self._step,
            args=self._args, span_id=self._id, parent=self._parent)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def span(name, annotate=True, step=None, args=None):
    """Context manager timing a host-side region into the registry and
    the timeline ring.

    :param name: dotted region name (``"executor.run"``); becomes the
        ``span`` label on ``paddle_tpu_span_seconds`` and the ring
        event's name.
    :param annotate: also open a ``jax.profiler.TraceAnnotation`` so the
        region shows in captured XLA traces.  Pass False on regions hot
        enough that the annotation's C++ hop matters.
    :param step: the ring event's step (default: the ring's current one).
    :param args: a dict kept on the ring event as it is when the region
        ends, so the caller may fill it inside the region.
    :returns: the shared no-op span when metrics are disabled.
    """
    if not _metrics.enabled():
        return _NULL_SPAN
    ann = None
    if annotate:
        import jax
        ann = jax.profiler.TraceAnnotation(name)
    return _Span(_child(name), ann, name, step, args)


def record_span(name, t0, t1, args=None):
    """Put a region on the ring from two ``time.perf_counter()`` stamps
    the caller kept (a request's wait, which no one thread's ``with``
    encloses).  It has an id and no parent, and feeds no histogram."""
    if _metrics.enabled():
        _timeline.ring().record(name, cat='span', t0=t0, dur=t1 - t0,
                                args=args, span_id=next(_ids))

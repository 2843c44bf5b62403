"""``decode._in_a_roomy_frame``: an engine program is traced and lowered
from inside one large frame, so that what it costs does not follow the
depth of the caller's stack (CPython unmaps a 16 KB chunk of frames the
moment its first frame returns: PERF.md section 6, PR 56)."""
import resource

import pytest

from paddle_tpu.inference.decode import _in_a_roomy_frame

CALLS = 20000


def _leaf():
    # a frame wider than one ``_at_depth`` step, so that no chunk's edge
    # falls between two depths
    a = b = c = d = e = f = g = h = i = j = k = l = m = n = o = p = 1
    return a


def _hot():
    n = 0
    for _ in range(CALLS):
        n += _leaf()
    return n


def _at_depth(k, f):
    return _at_depth(k - 1, f) if k else f()


def _faults(f):
    before = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
    assert f() == CALLS
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt - before


def test_it_returns_what_the_function_returns_and_raises_what_it_raises():
    assert _in_a_roomy_frame(lambda: 41 + 1) == 42
    with pytest.raises(KeyError, match='gone'):
        _in_a_roomy_frame(lambda: {}['gone'])


@pytest.mark.parametrize('roomy', [False, True], ids=['plain', 'roomy'])
def test_a_hot_call_on_a_chunks_edge_maps_memory_only_without_it(roomy):
    """A loop of calls started one frame deeper each time: somewhere in
    240 depths (more than a chunk of them) a plain start puts the call on a chunk's edge, and every
    call then faults a fresh page in; from a roomy frame none does."""
    wrap = _in_a_roomy_frame if roomy else (lambda f: f())
    wrap(_leaf)     # the roomy function is built at its first call
    worst = max(_faults(lambda: _at_depth(k, lambda: wrap(_hot)))
                for k in range(240))
    if roomy:
        assert worst < CALLS // 20
    elif worst < CALLS // 2:
        pytest.skip('this interpreter keeps its emptied frame chunks: '
                    'worst depth faulted %d pages in %d calls'
                    % (worst, CALLS))

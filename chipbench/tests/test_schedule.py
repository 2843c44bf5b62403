"""What makes an open loop's schedule "kept": the pure rule on made-up
lateness (the runs that the old rule, p99 against one decode step,
failed; a starved sender; a long stall), and a whole rehearsal whose
sender is held back, which has to come out not correct."""
import inspect
import json
import subprocess
import sys

import pytest

from chipbench.kinds import serving
from chipbench.tests.test_rehearse import ROOT


def arrivals(n, normal_ms=1.0, **late_ms):
    """``n`` arrivals ``normal_ms`` late, but ``count`` of them ``ms``
    late for each ``late_ms`` entry name=(count, ms)."""
    late = [normal_ms / 1e3] * n
    i = 0
    for count, ms in late_ms.values():
        late[i:i + count] = [ms / 1e3] * count
        i += count
    return late


# (lateness, rate a second, kept, arrivals over the limit)
CASES = {
    # the two runs of PR 39 that the old rule failed: 25-40 ms at p99
    'three_of_154_in_a_pause': (arrivals(154, a=(3, 40.0)), 3.6, True, 3),
    'one_of_67_a_quarter_second': (arrivals(67, a=(1, 250.0)), 1.8, True, 1),
    'a_tenth_of_144_at_30ms': (arrivals(144, a=(14, 30.0)), 3.6, False, 14),
    # a starved sender is late on every wake-up
    'all_30ms_late_limit_27.8': (arrivals(144, 30.0), 3.6, False, 144),
    'all_30ms_late_limit_55.6': (arrivals(67, 30.0), 1.8, True, 0),
    # PR 32's one long stall: 8 of 144 arrivals 2.29 s late
    'eight_of_144_in_a_long_stall': (arrivals(144, a=(8, 2290.0)), 3.6,
                                     False, 8),
    'exactly_one_in_twenty': (arrivals(140, a=(7, 40.0)), 3.6, True, 7),
    'one_more_than_one_in_twenty': (arrivals(140, a=(8, 40.0)), 3.6,
                                    False, 8),
    'at_the_limit_is_not_over_it': ([0.1 / 2.2] * 86, 2.2, True, 0),
    'two_pauses_of_differing_length': (
        arrivals(86, a=(2, 107.0), b=(1, 64.0)), 2.2, True, 3),
}


@pytest.mark.parametrize('case', sorted(CASES))
def test_schedule_kept(case):
    late, rate, kept, over = CASES[case]
    got_kept, got_over, limit = serving.schedule_kept(late, rate)
    assert (got_kept, got_over) == (kept, over)
    assert limit == pytest.approx(0.1 / rate)


def test_the_rule_reads_nothing_the_program_sets():
    """No step time, span or duration of an engine call goes in: the
    arrivals' lateness and the traffic file's rate, and two literals."""
    assert list(inspect.signature(serving.schedule_kept).parameters) == \
        ['late', 'rate_per_s']
    assert (serving.LATE_LIMIT_IN_GAPS, serving.LATE_ARRIVALS_ONE_IN) == \
        (0.1, 20)


HELD_BACK = '''
import sys, time
from chipbench import run
from chipbench.kinds import serving
from chipbench.systems import decoder_serve
submit = decoder_serve.Served.submit
def late_submit(self, req):
    time.sleep(0.02)    # the sender thread, held back before it stamps
    return submit(self, req)
decoder_serve.Served.submit = late_submit
measure = serving.measure
def as_on_the_chip(run, *a, **k):
    # a rehearsal prints the line of a schedule not kept and is not
    # failed by it (its 4 s hold ~40 arrivals); a chip run is
    run.rehearse = False
    try:
        return measure(run, *a, **k)
    finally:
        run.rehearse = True
serving.measure = as_on_the_chip
sys.exit(run.main(['--workload', 'opt-1.3b_serve_chat', '--seed',
                   '3000000023', '--seconds', '4', '--trace', '0',
                   '--rehearse']))
'''


def held_back(script):
    p = subprocess.run([sys.executable, '-c', script], cwd=ROOT,
                       stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    window = json.loads(next(line for line in lines if line.startswith(
        'WINDOW ')).split(' ', 1)[1])
    return json.loads(lines[-1]), window, lines[:-1]


TEXT = ('arrivals left more than 8.3 ms late (a tenth of the gap between '
        'arrivals at 12/s)')


def test_a_sender_held_back_is_not_correct():
    """The rest of a run with the generator broken underneath: every
    arrival leaves 20 ms late at the rehearsal's 12 a second (limit 8.3
    ms), judged as a chip run is.  (The same run with nothing held back
    is test_rehearse.py's.)"""
    res, window, earlier = held_back(HELD_BACK)
    assert res['correct'] is False, earlier
    said = [line for line in earlier if line.startswith('INCORRECT ')]
    assert len(said) == 1 and said[0].endswith(TEXT)
    over = window['late_over_limit']
    assert over['count'] == res['attempted'] > 20
    assert over['limit_ms'] == pytest.approx(1e3 / 120)
    assert window['late_p50_ms'] > 20.0 > over['limit_ms']


def test_a_rehearsal_says_so_and_is_not_failed_by_it():
    """The same under the rehearsal's exemption: the line is printed,
    the count is in WINDOW, and ``correct`` is left to the rest."""
    res, window, earlier = held_back(HELD_BACK.replace(
        'serving.measure = as_on_the_chip', ''))
    assert res['correct'] is True, earlier
    assert not [line for line in earlier if line.startswith('INCORRECT ')]
    assert [line for line in earlier if line.endswith(TEXT)]
    assert window['late_over_limit']['count'] == res['attempted']

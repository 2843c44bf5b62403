"""What the two serving kinds (``open_loop``, ``closed_loop``) share: the
sizes a traffic file asks for, the comparison with the reference, the
window, and the numbers taken from it."""
import collections
import functools
import importlib
import math
import time

import numpy as np

from .. import harness, xplane


def sizes(spec, n):
    """``n`` whole numbers spread evenly over the quantiles of the
    distribution ``spec`` = {"dist": "uniform"|"log_uniform", "lo", "hi"}:
    every seed gets the same set, in another order."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = float(spec['lo']), float(spec['hi'])
    if spec['dist'] == 'log_uniform':
        v = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    elif spec['dist'] == 'uniform':
        v = lo + q * (hi - lo)
    else:
        raise ValueError('unknown distribution %r' % spec['dist'])
    return np.clip(np.rint(v), lo, hi).astype(int)


def requests(run, n, system):
    """``n`` requests from --seed: the fixed sets of prompt and output
    lengths, paired and ordered by the seed, with random tokens."""
    t, rng = run.traffic, np.random.default_rng(run.seed)
    plens = rng.permutation(sizes(t['prompt_tokens'], n))
    olens = rng.permutation(sizes(t['output_tokens'], n))
    vocab = run.config['vocab_size']
    return [system.Request(rng.integers(1, vocab, int(p)), o)
            for p, o in zip(plens, olens)]


def all_prompt_lengths(run):
    t = run.traffic
    return list(sizes(t['prompt_tokens'], 4096)) + \
        [c['prompt_tokens'] for c in t['check']]


# What makes an open loop's schedule "kept".  Every number is the
# traffic's, none is the program's: a limit made of a step's time shrank
# with every gain to the step, and failed sound runs (PERF.md section 6,
# PR 40).
# Poisson gaps have a coefficient of variation of 1: an arrival that
# leaves under a tenth of the mean gap late leaves the arrival process
# what the traffic file says.
LATE_LIMIT_IN_GAPS = 0.1
# A pause of the machine stops the sender and the server together.  The
# arrivals due inside it all leave at its end, so it is ONE stretch of
# consecutive arrivals over the limit, and it holds T/40 of a 40 s
# window's arrivals at any rate.  The sealed machines pause 0.7-2.3 s
# once in some tens of runs (6 of 84 arrivals 2.1 s late, 8 of 142 1.4
# s, 8 of 144 2.3 s: PERF.md section 6, PRs 32 and 49), and each stream
# then has one long gap, 16-64 of the 5,000-49,000 a p95 is taken over:
# a run stopped for 2 s in mid-window (2-5 of 86-91 arrivals, one
# stretch) read its seed's itl_p95_ms to 0.1 ms (PR 49).  One in ten is
# a pause of 4 s.  A stretch longer than that moves a p95 and is not set
# aside: stopped for 8 s (11-17 of 86-91) the same runs read 1.3-2.5%
# more, and 11 of 74 over 6 s were a machine running slowly (PR 41).
LATE_PAUSE_ONE_IN = 10
# Outside that one stretch the cells' lateness is ~1.1 ms at p99, 25-50
# times under the limit, and the machines' short pauses (70-110 ms every
# 5-25 s) hold 1-3 of 67-154 arrivals; a sender starved of the CPU
# passes one in twenty at once (4 spinning threads: 7.7-12.1% over, PR
# 40; 7.1-11.5% of those left, PR 49).
LATE_ARRIVALS_ONE_IN = 20
# A sender that is held back or starved is late on EVERY wake-up, a
# machine that pauses on one stretch: the median tells them apart.  Sound
# runs read 0.24-0.82 ms, and no more with a stop of 8 s in them; beside
# 4 spinning threads 11.3-17.1 ms (PR 40) and 13.8-15.9 (PR 49).  A
# hundredth of the gap (5.6 / 4.5 / 2.8 ms at 1.8 / 2.2 / 3.6 a second)
# lies 3.4-6.8 times over the largest of the one and 2-4 times under the
# smallest of the other.
LATE_MEDIAN_IN_GAPS = 0.01

Schedule = collections.namedtuple(
    'Schedule', 'kept over limit stretches set_aside median reasons')


def schedule_kept(late, rate_per_s):
    """Whether the generator kept the schedule of an open loop.  ``late``
    holds sent - due of the window's arrivals in seconds, IN THE ORDER
    THEY WERE DUE (``measure`` builds it so): a stretch is a maximal run
    of consecutive arrivals later than a tenth of the mean gap.  The
    longest stretch is set aside as a pause of the machine if it holds at
    most one arrival in ten; of the arrivals left at most one in twenty
    may be over that limit, and the median lateness of all is at most a
    hundredth of the mean gap.  Counted, not interpolated, so the answer
    does not turn on how many arrivals the window held.  Returns (kept,
    the count over the limit, the limit in seconds, the stretches as
    (first index, count, longest lateness in seconds), the count set
    aside, the median in seconds, a line for each term that failed)."""
    limit = LATE_LIMIT_IN_GAPS / rate_per_s
    stretches = []      # [first index, count, longest lateness]
    for i, x in enumerate(late):
        if x <= limit:
            continue
        if stretches and stretches[-1][0] + stretches[-1][1] == i:
            stretches[-1][1] += 1
            stretches[-1][2] = max(stretches[-1][2], x)
        else:
            stretches.append([i, 1, x])
    over = sum(s[1] for s in stretches)
    longest = max((s[1] for s in stretches), default=0)
    set_aside = longest if longest * LATE_PAUSE_ONE_IN <= len(late) else 0
    left = len(late) - set_aside
    median = harness.percentile(late, 50)
    median_limit = LATE_MEDIAN_IN_GAPS / rate_per_s
    reasons = []
    if (over - set_aside) * LATE_ARRIVALS_ONE_IN > left:
        tail = ('more than %.1f ms late (a tenth of the gap between '
                'arrivals at %g/s)' % (1e3 * limit, rate_per_s))
        if set_aside:
            reasons.append(
                '%d of %d arrivals outside the longest stretch left %s; '
                'that stretch holds %d and is set aside'
                % (over - set_aside, left, tail, longest))
        else:
            reasons.append(
                '%d of %d arrivals left %s; the longest stretch holds %d, '
                'over one in %d, and stays'
                % (over, left, tail, longest, LATE_PAUSE_ONE_IN))
    if median > median_limit:
        reasons.append(
            'the median arrival left %.2f ms late, over %.2f ms (a '
            'hundredth of the gap between arrivals at %g/s)'
            % (1e3 * median, 1e3 * median_limit, rate_per_s))
    return Schedule(not reasons, over, limit, stretches, set_aside, median,
                    reasons)


def build(run):
    """The served system, warmed, compared with the reference, started."""
    system = importlib.import_module('chipbench.systems.'
                                     + run.config['system'])
    reference = importlib.import_module('chipbench.reference.'
                                        + run.config['reference'])
    import jax
    import jax.numpy as jnp
    buckets = system.buckets_for(run.traffic['engine']['page_size'],
                                 all_prompt_lengths(run))
    served = system.Served(run, buckets)
    with run.phases('memory_analysis'):
        run.scratch_bytes = served.scratch_bytes()
    why = []
    with run.phases('reference_check'):
        rng = np.random.default_rng(run.seed + 1)
        checks = run.traffic['check']
        pad = max(buckets)
        ref = jax.jit(functools.partial(
            reference.logits, n_layers=served.layers, n_heads=served.heads))
        errs = []
        for c in checks:
            prompt = rng.integers(1, run.config['vocab_size'],
                                  int(c['prompt_tokens']))
            got, toks = served.replay(prompt, int(c['output_tokens']))
            seq = np.zeros((pad,), np.int32)
            n = len(prompt) + len(toks) - 1
            seq[:len(prompt)] = prompt
            seq[len(prompt):n] = toks[:-1]
            want = np.asarray(ref(served.params, jnp.asarray(seq)))[
                len(prompt) - 1:n]
            errs.append(float(np.max(np.abs(got - want))
                              / np.max(np.abs(want))))
            if not np.isfinite(got).all():
                why.append('the engine gave non-finite logits')
        harness.info('REFERENCE', {'logits_rel_err': errs,
                                   'tol': reference.LOGITS_TOL})
        if not max(errs) <= reference.LOGITS_TOL:
            why.append('paged logits are %r from the reference, over %g'
                       % (errs, reference.LOGITS_TOL))
    if served.engine.compiles_after_warmup:
        why.append('the check compiled: a bucket was not warmed')
    served.start()
    return served, why


def measure(run, served, why, reqs, t_open, window_s, stop):
    """After the window: wait for what is in flight, then reduce."""
    tap, t = served.tap, run.traffic
    t_host_end = t_open + window_s
    if run.trace:
        # the last seconds of the window are traced; the host-clock
        # numbers come from the seconds before them
        t_host_end = t_open + max(window_s - run.trace_seconds(),
                                  0.5 * window_s)
        time.sleep(max(t_host_end - time.perf_counter(), 0))
        with run.traced():
            time.sleep(max(t_open + window_s - time.perf_counter(), 0))
    else:
        time.sleep(max(t_host_end - time.perf_counter(), 0))
    compiled = run.compiles.count - run.obs['compiles_at_open']
    stop.set()
    stats = served.server.stats()
    sent = [r for r in list(reqs) if r.sent is not None]
    with run.phases('drain_after_window'):
        for r in sent:
            try:
                r.stream.result(timeout=120.0)
            except Exception as e:      # a failed request is counted
                harness.info('REQUEST_FAILED', {'error': repr(e)[:300]})
        served.close()

    done = sorted((r for r in sent if r.done is not None
                   and t_open <= r.done < t_host_end),
                  key=lambda r: r.done)
    attempted = [r for r in sent
                 if t_open <= (r.due if r.due is not None else r.sent)
                 < t_host_end]
    failed = sum(1 for r in attempted
                 if r.stream.error is not None or r.done is None)
    bad = [r for r in sent if r.done is not None
           and len(r.stream.tokens) != r.n_out]
    if bad:
        why.append('%d request(s) got another number of tokens than asked'
                   % len(bad))
    if compiled or stats['compiles_after_warmup']:
        why.append('%d compilation(s) inside the window (server counts %d)'
                   % (compiled, stats['compiles_after_warmup']))
    if len(done) < 20:
        why.append('only %d requests completed in the window' % len(done))
        done = done or sent[:1]

    gaps = [g for r in done for g in r.gaps()]
    ttfts = [r.ttft() for r in done]
    tokens = lambda rs: sum(len(r.prompt) + r.n_out for r in rs)
    span = done[-1].done - done[0].done
    rate = tokens(done[1:]) / span if span > 0 else 0.0
    steps = [s for s in tap.steps if t_open <= s[0] and s[1] < t_host_end]
    step_s = float(np.median([s[1] - s[0] for s in steps])) if steps else 0.0
    late = [r.sent - r.due for r in attempted if r.due is not None]
    late_p99 = harness.percentile(late, 99) if late else None
    late_over = late_p50 = late_stretches = late_set_aside = None
    if late:    # an open loop; a closed loop has no schedule to keep
        sched = schedule_kept(late, float(t['rate_per_s']))
        late_over = {'count': sched.over, 'limit_ms': 1e3 * sched.limit}
        late_p50, late_set_aside = 1e3 * sched.median, sched.set_aside
        # the longest first, and no more of them than a line holds
        late_stretches = [[i, n, 1e3 * x] for i, n, x in sorted(
            sched.stretches, key=lambda s: -s[1])[:16]]
        for reason in sched.reasons:
            # (a rehearsal's 4 s hold ~40 arrivals behind a busy CPU, so
            # two late ones are over one in twenty: it prints the lines
            # and is not failed by them)
            (print if run.rehearse else why.append)(reason)

    fifth_rows = []
    for lo, hi in harness.fifths(t_open, t_host_end):
        part = [r for r in done if lo <= r.done < hi]
        g = [x for r in part for x in r.gaps()]
        fifth_rows.append({
            'completed': len(part),
            'itl_p95_ms': 1e3 * harness.percentile(g, 95) if g else None,
            'ttft_p90_ms': 1e3 * harness.percentile(
                [r.ttft() for r in part], 90) if part else None,
            'serve_tokens_per_s': tokens(part) / (hi - lo)})
    harness.info('FIFTHS', fifth_rows)
    harness.info('WINDOW', {
        'completed': len(done), 'attempted': len(attempted),
        'gaps': len(gaps), 'decode_steps': len(steps),
        'decode_step_host_ms': 1e3 * step_s,
        'late_p99_ms': None if late_p99 is None else 1e3 * late_p99,
        'late_p50_ms': late_p50,
        'late_max_ms': 1e3 * max(late) if late else None,
        'late_over_limit': late_over,
        'late_stretches': late_stretches,
        'late_set_aside': late_set_aside,
        'queued_at_close': stats['queued'],
        'active_at_close': stats['active_streams'],
        'free_pages_at_close': stats['free_pages'],
        'prefill_chunks': stats['prefill_chunks']})

    # the tap's stamps beside the program's own (same clock, taken a few
    # lines after the engine call returns): a tap that lost track of a
    # request would show here
    harness.info('TAP_CHECK', {'max_diff_ms': 1e3 * max(
        [abs(a - b) for r in done
         for a, b in zip(r.times, r.stream.token_times)] or [0.0])})

    prefills = [p for p in tap.prefills
                if t_open <= p[0] and p[1] < t_host_end]
    run.obs.update(
        kind=t['kind'], t_open=t_open, t_host_end=t_host_end,
        done=done, steps=steps, prefills=prefills, tap=tap,
        late_p99_s=late_p99, slots=served.engine.max_streams,
        params=served.params, layers=served.layers,
        kv_bytes_per_token=2 * served.layers * run.config['hidden_size']
        * np.dtype(run.config['kv_dtype']).itemsize)
    host_idle(run)
    end = {'setup_s': t_open - harness.T0}
    if gaps:
        end['itl_p95_ms'] = 1e3 * harness.percentile(gaps, 95)
        end['ttft_p90_ms'] = 1e3 * harness.percentile(ttfts, 90)
    end['serve_tokens_per_s'] = rate
    run.emit(correct=True, attempted=len(attempted), failed=failed,
             end_to_end=end, why=why)


def program_seconds(run, tr, key):
    name = run.config['device_programs'][key]
    return xplane.module_calls(tr, xplane.window(tr), name)


def host_idle(run):
    """The idle share a second way: 1 - (device seconds of the engine's
    calls, each at its mean in the trace) / elapsed, over the seconds
    that were not traced.  Also records the server's time between engine
    calls as spans, for the attribution of gaps."""
    tr = run.obs.get('trace')
    if tr is None or xplane.window(tr) is None:
        return
    o = run.obs
    mean = {k: (sum(v) / len(v) if v else 0.0) for k, v in
            ((k, program_seconds(run, tr, k))
             for k in run.config['device_programs'])}
    # each engine call runs the programs the configuration lists for it
    per_call = {call: sum(mean[k] for k in programs)
                for call, programs in run.config['engine_calls'].items()}
    device_s = sum(per_call[c[0]] for c in o['tap'].calls
                   if o['t_open'] <= c[1] and c[2] < o['t_host_end'])
    o['host_idle_share'] = 1.0 - device_s / (o['t_host_end'] - o['t_open'])
    # the server's time between two engine calls, as spans of its own
    calls = sorted([(s[0], s[1], s[2]) for s in o['tap'].steps]
                   + [(p[0], p[1], 1) for p in o['tap'].prefills])
    for a, b in zip(calls, calls[1:]):
        if b[0] > a[1]:
            run.spans.log.setdefault(
                'bench.server_between_calls' if a[2] or b[2]
                else 'bench.server_no_request', []).append((a[1], b[0]))

"""Find the highest arrival rate an ``open_loop`` cell sustains: one
process, one set-up, one stretch of arrivals per rate.

    python3 -m chipbench.sweep --workload opt-1.3b_serve_chat \\
        --rates 1.6,2.0,2.3,2.6,2.9 --seconds 30

For each rate it prints the requests in the system (sent, not complete)
at the end of each fifth of the stretch: at a rate the server sustains
the count levels off, above it the count grows to the end.  The cell's
``rate_per_s`` is then fixed by hand at about four fifths of the highest
sustained rate; the benchmark never searches for it.
"""
import argparse
import importlib
import json
import os
import sys
import time

import numpy as np

from . import harness
from .kinds import open_loop, serving


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--rates', required=True)
    ap.add_argument('--seconds', type=float, default=30.0)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--rehearse', action='store_true')
    args = ap.parse_args(argv)
    args.trace, args.keep_trace = 0, None
    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    cell = next(w for w in bench['workloads'] if w['name'] == args.workload)
    if args.rehearse:
        os.environ['JAX_PLATFORMS'] = 'cpu'
    sys.path.insert(0, harness.ROOT)
    run = harness.Run(args, bench, cell)
    run.claim_device()
    served, why = serving.build(run)
    system = importlib.import_module(served.__class__.__module__)
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(',')):
            run.seed = args.seed + 100 * k
            n = int(np.ceil(rate * args.seconds))
            reqs = serving.requests(run, n, system)
            due = np.cumsum(open_loop.gaps(
                rate, n, np.random.default_rng(run.seed)))
            t0 = time.perf_counter()
            marks = [t0 + (i + 1) * args.seconds / 5 for i in range(5)]
            in_system, m = [], 0
            for r, d in zip(reqs, due):
                r.due = t0 + float(d)
                while time.perf_counter() < r.due:
                    time.sleep(max(min(r.due - time.perf_counter(), 0.01),
                                   0.0))
                    if m < 5 and time.perf_counter() >= marks[m]:
                        in_system.append(sum(
                            1 for x in reqs
                            if x.sent is not None and x.done is None))
                        m += 1
                served.submit(r)
            for r in reqs:
                r.stream.result(timeout=300.0)
            served.tap.look()
            drained = time.perf_counter() - t0
            done = [r for r in reqs if r.done is not None]
            gaps = [g for r in done for g in r.gaps()]
            harness.info('RATE', {
                'rate_per_s': rate, 'sent': n,
                'in_system_at_each_fifth': in_system,
                'seconds_to_drain_after_last_arrival':
                    drained - float(due[-1]),
                'itl_p95_ms': 1e3 * harness.percentile(gaps, 95),
                'ttft_p90_ms': 1e3 * harness.percentile(
                    [r.ttft() for r in done], 90),
                'ttft_p50_ms': 1e3 * harness.percentile(
                    [r.ttft() for r in done], 50),
                'late_p99_ms': 1e3 * harness.percentile(
                    [r.sent - r.due for r in done], 99),
                'decode_step_host_ms': 1e3 * float(np.median(
                    [s[1] - s[0] for s in served.tap.steps[-200:]])),
                'memory_peak_bytes': run.memory_peak_bytes()})
    finally:
        served.close()
    harness.info('SETUP_PHASES', run.phases.seconds)
    return 0


if __name__ == '__main__':
    sys.exit(main())

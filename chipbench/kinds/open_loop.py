"""Traffic of kind ``open_loop``: independent users.  Requests arrive on
a schedule fixed by --seed at the rate in the traffic file, whatever the
server does; each is timed from the moment it was due."""
import importlib
import threading
import time

import numpy as np

from . import serving


def gaps(rate, n, rng):
    """``n`` gaps between Poisson arrivals at ``rate`` a second: the
    quantiles of the exponential distribution (every seed the same set)
    in the seed's order.  (Arrival arithmetic after
    benchmarks/bench_serving.py's decode_scenario.)"""
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-q) / rate)


def run(run):
    t = run.traffic
    served, why = serving.build(run)
    system = importlib.import_module(served.__class__.__module__)
    settle = float(t['settle_seconds'])
    horizon = settle + run.seconds + 1.0
    n = int(np.ceil(float(t['rate_per_s']) * horizon))
    with run.phases('traffic'):
        reqs = serving.requests(run, n, system)
        due = np.cumsum(gaps(float(t['rate_per_s']), n,
                             np.random.default_rng(run.seed + 2)))
    run.quiet_gc()
    stop = threading.Event()
    t_start = time.perf_counter()
    for r, d in zip(reqs, due):
        r.due = t_start + float(d)

    def send():
        for r in reqs:
            wait = r.due - time.perf_counter()
            if wait > 0 and stop.wait(wait):
                return
            if stop.is_set():
                return
            served.submit(r)

    sender = threading.Thread(target=send, name='bench-sender', daemon=True)
    sender.start()
    with run.phases('settle'):
        time.sleep(settle)
    run.obs['compiles_at_open'] = run.compiles.count
    t_open = time.perf_counter()
    try:
        serving.measure(run, served, why, reqs, t_open, run.seconds, stop)
    finally:
        stop.set()
        sender.join(timeout=10.0)
        served.close()

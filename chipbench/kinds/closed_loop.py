"""Traffic of kind ``closed_loop``: callers that wait.  ``clients``
clients each send their next request when the last one completes."""
import importlib
import itertools
import threading
import time

import numpy as np

from . import serving


def run(run):
    t = run.traffic
    served, why = serving.build(run)
    system = importlib.import_module(served.__class__.__module__)
    clients = int(t['clients'])
    with run.phases('traffic'):
        reqs = serving.requests(run, int(t['requests']), system)
        rng = np.random.default_rng(run.seed + 3)
        streams = rng.integers(1, run.config['vocab_size'],
                               (clients, int(t['token_stream'])))
    run.quiet_gc()
    stop = threading.Event()

    sent = []

    def client(k):
        # each client goes round the sizes of its own share of the fixed
        # set, so any window of a few rounds serves the same sizes
        # whatever the seed; every send has token ids of its own (the
        # next stretch of the client's stream), so a repeat shares
        # nothing with the request whose size it has
        at = 0
        for r in itertools.cycle(reqs[k::clients]):
            if stop.is_set():
                return
            n = len(r.prompt)
            again = system.Request(
                np.take(streams[k], np.arange(at, at + n), mode='wrap'),
                r.n_out)
            at += n
            sent.append(again)
            served.submit(again).result(timeout=300.0)

    threads = [threading.Thread(target=client, args=(k,), daemon=True,
                                name='bench-client-%d' % k)
               for k in range(clients)]
    for th in threads:
        th.start()
    with run.phases('settle'):
        time.sleep(float(t['settle_seconds']))
    run.obs['compiles_at_open'] = run.compiles.count
    t_open = time.perf_counter()
    try:
        serving.measure(run, served, why, sent, t_open, run.seconds, stop)
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=10.0)
        served.close()

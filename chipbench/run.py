"""Run one cell once: ``python3 -m chipbench.run --workload <name>
--seed <n> --seconds <s> --trace <0|1>`` from the root of a checkout.

The last line of standard output is the result.  Without a TPU (or with
fewer chips than the cell asks for) the run leaves with code 3 and no
result; ``--rehearse`` runs the cell's toy shapes on the CPU instead and
prints no device metric.
"""
import argparse
import importlib
import json
import os
import sys

from . import harness


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--seconds', type=float, default=None)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--rehearse', action='store_true',
                    help='toy shapes on the CPU; prints no device metric')
    ap.add_argument('--keep-trace', metavar='DIR', default=None,
                    help='also write the reduced trace there as JSON')
    args = ap.parse_args(argv)

    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    cells = {w['name']: w for w in bench['workloads']}
    if args.workload not in cells:
        ap.error('no workload %r in BENCHMARK.json (has: %s)'
                 % (args.workload, ', '.join(cells)))
    cell = cells[args.workload]
    if args.seconds is None:
        args.seconds = float(bench['run_seconds'])

    if args.rehearse:
        os.environ['JAX_PLATFORMS'] = 'cpu'
        os.environ['XLA_FLAGS'] = (
            os.environ.get('XLA_FLAGS', '')
            + ' --xla_force_host_platform_device_count=%d'
            % int(cell['chips'])).strip()
    sys.path.insert(0, harness.ROOT)
    run = harness.Run(args, bench, cell)
    for key, value in run.traffic.get('env', {}).items():
        os.environ[key] = str(value)    # the deployment's own switches
    import jax
    # every program goes to the persistent cache, however quick its
    # compile: the second run of a cell finds them all
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    run.claim_device()
    kind = importlib.import_module('chipbench.kinds.' + run.traffic['kind'])
    kind.run(run)
    return 0


if __name__ == '__main__':
    sys.exit(main())

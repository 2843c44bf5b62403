"""Run cells several times, one new process a run, and say how the runs
spread: ``python3 -m chipbench.measure --out chiprun_out/x.jsonl
<workload>:<seconds>:<trace>:<seed>[,<seed>...] ...``.

This process never touches JAX: each run holds the chip alone.  Every
run's last line goes to ``--out`` with its arguments; the earlier lines
(set-up by phase, fifths, idle shares) go to ``<out>.log``.
"""
import argparse
import json
import os
import subprocess
import sys
import time

from .harness import spread


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--out', required=True)
    ap.add_argument('--extra', default='', help='more arguments to each run')
    ap.add_argument('specs', nargs='+')
    args = ap.parse_args(argv)
    os.makedirs(os.path.dirname(args.out) or '.', exist_ok=True)
    series = {}
    with open(args.out, 'a') as out, open(args.out + '.log', 'a') as log:
        for spec in args.specs:
            workload, seconds, trace, seeds = spec.split(':')
            for seed in seeds.split(','):
                cmd = [sys.executable, '-m', 'chipbench.run', '--workload',
                       workload, '--seed', seed, '--seconds', seconds,
                       '--trace', trace] + args.extra.split()
                t0 = time.time()
                p = subprocess.run(cmd, stdout=subprocess.PIPE,
                                   stderr=subprocess.PIPE, text=True)
                wall = time.time() - t0
                lines = p.stdout.strip().splitlines()
                log.write('### %s rc=%d wall=%.1f\n%s\n' % (
                    ' '.join(cmd[2:]), p.returncode, wall,
                    '\n'.join(lines[:-1])))
                if p.returncode != 0 or not lines:
                    log.write(p.stderr[-6000:] + '\n')
                    print('FAILED rc=%d %s\n%s' % (p.returncode, spec,
                                                   p.stderr[-3000:]))
                    continue
                res = json.loads(lines[-1])
                res.update(workload=workload, seed=int(seed),
                           seconds=float(seconds), trace=int(trace),
                           wall_s=round(wall, 1))
                out.write(json.dumps(res) + '\n')
                out.flush()
                log.flush()
                key = (workload, seconds, trace)
                for name, m in res['metrics'].items():
                    series.setdefault(key, {}).setdefault(
                        name, []).append(m['value'])
                short = {k: round(v['value'], 4)
                         for k, v in res['metrics'].items()}
                print('%s s=%s t=%s seed=%s correct=%s wall=%.0f %s' % (
                    workload, seconds, trace, seed, res['correct'], wall,
                    json.dumps(short)), flush=True)
    for key, metrics in series.items():
        for name, values in metrics.items():
            if len(values) >= 3:
                rest = values[1:] if name == 'setup_s' else values
                print('SPREAD %s %s n=%d median=%.6g spread=%.4f%%' % (
                    ':'.join(key), name, len(rest),
                    sorted(rest)[len(rest) // 2],
                    100 * spread(rest) if len(rest) >= 2 else 0.0))
    return 0


if __name__ == '__main__':
    sys.exit(main())

"""Run every secondary benchmark (SURVEY §5 / BASELINE configs 1-5) and
print one JSON line each.  The headline ResNet-50 bench lives in
../bench.py.

One process for each chip: every bench is a child of its own, run one
after another, and this parent never imports jax.  Keep it so — a parent
that touches jax holds the chip, and every child that needs it then
fails or hangs."""
import subprocess
import sys
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHES = ['bench_mnist.py', 'bench_vgg.py', 'bench_lstm_lm.py',
           'bench_seq2seq.py', 'bench_decode.py', 'bench_ctr.py',
           'bench_attention.py', 'bench_serving.py',
           'bench_feed.py']

if __name__ == '__main__':
    # forward the shared bench flags (--tune {off,cached,search},
    # --roofline, --tune-trace) to every child; benches parse them via
    # common.bench_cli (parse_known_args — unknown flags pass through)
    extra = sys.argv[1:]
    failed = []
    for b in BENCHES:
        r = subprocess.run([sys.executable, os.path.join(HERE, b)]
                           + extra, cwd=HERE)
        if r.returncode != 0:
            failed.append(b)
    if failed:
        print('FAILED: %s' % ', '.join(failed), file=sys.stderr)
        sys.exit(1)

"""BASELINE config 1: Fluid MNIST convnet — examples/s."""
import numpy as np

from common import bench_cli, run_bench, on_tpu


def main():
    opts = bench_cli()
    import paddle_tpu as fluid
    from paddle_tpu.models import mnist

    batch = 2048 if on_tpu() else 64

    def build():
        main_p, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup):
            img, label, pred, avg_cost, acc = mnist.build('conv')
            fluid.optimizer.AdamOptimizer(1e-3).minimize(avg_cost)
        return main_p, startup, avg_cost

    rng = np.random.default_rng(0)

    def feed():
        return {'img': rng.normal(size=(batch, 1, 28, 28)).astype(
                    np.float32),
                'label': rng.integers(0, 10, (batch, 1)).astype(np.int32)}

    # K=500 steps per chain: the device step is short (~1.6 ms in the
    # round-5 trace), so a short chain times dispatch.
    # amp_compare: two rows (amp=off / amp=bf16) — the f32-vs-bf16
    # step-time and activation-bytes columns PERF.md tracks
    # step_breakdown: feed_s/compute_s/update_s per step over REAL
    # per-step feeds, device-prefetch off vs on (the MFU story's
    # where-did-the-time-go table)
    run_bench('mnist_conv_examples_per_sec', batch, build, feed,
              steps=500 if on_tpu() else 5,
              note='batch=%d' % batch,
              compile_stats=True,
              amp_compare='bf16',
              step_breakdown=True,
              tune=opts.tune, roofline=opts.roofline)


if __name__ == '__main__':
    main()

"""The convolutions' share of their roofline, in percent.  Least time:
chipbench/flops.py's ``resnet_conv_roofline_s`` (for every convolution
and each of its passes the larger of FLOPs / bf16 peak and bytes / HBM
peak, from the configuration's shapes at one chip's batch).  Time taken:
the device time per step of the step's convolution fusions, which XLA:TPU
marks ``kind=kOutput`` or names after the convolution in them.  The
fusions also hold the batch-norm and ReLU work XLA fused into them, so
the share reads low, never high."""
from .. import flops, xplane


def read(run, program):
    tr = run.obs.get('trace')
    if tr is None or run.peaks is None:
        return None
    win = xplane.window(tr)
    prog = run.config['device_programs'][program]
    steps = len(xplane.module_calls(tr, win, prog))
    conv_s = sum(v for k, v in xplane.op_seconds(tr, win).items()
                 if k.startswith(prog + '/')
                 and (':kOutput' in k or 'convolution' in k))
    if not steps or not conv_s:
        return None
    least = flops.resnet_conv_roofline_s(
        run.config, run.config['per_chip_batch'], run.peaks)
    return 100.0 * least / (conv_s / steps)

"""The plain ResNet reference against the system at toy widths in f32 on
the CPU: the first step's loss, and the gradients of the last FC read
back from the first momentum update (velocity starts at 0, so the
update is -lr x gradient).  (The OPT reference is compared inside every
``--rehearse`` run: test_rehearse.py reads its REFERENCE line.)"""
import argparse
import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_resnet_loss_and_fc_gradients():
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    from chipbench import harness
    from chipbench.reference import resnet50 as reference
    from chipbench.systems import resnet_train
    # the training cells' entries are in pending.json (not cells yet)
    with open(os.path.join(ROOT, 'chipbench', 'pending.json')) as f:
        bench = json.load(f)
    cell = next(w for w in bench['workloads']
                if w['name'] == 'resnet50_train_fed')
    args = argparse.Namespace(seed=11, seconds=1, trace=0, rehearse=True,
                              keep_trace=None)
    run = harness.Run(args, bench, cell)
    assert run.config['dtype'] == 'float32'     # the toy is true f32
    run.claim_device()
    rig = resnet_train.Rig(run, run.devices)
    batch = resnet_train.host_batches(run, rig, 1)[0]
    before = {k: np.asarray(v).copy() for k, v in rig.weights().items()}
    want, gw, gb = reference.loss_and_fc_grads(
        before, batch['img'], batch['label'], run.config)
    got = rig.step(batch)
    assert abs(got - float(want)) <= 1e-4 * abs(float(want))
    after = rig.weights()
    lr = run.config['learning_rate']
    for name, g in (('fc_0.w_0', gw), ('fc_0.b_0', gb)):
        sys_g = (before[name] - np.asarray(after[name])) / lr
        scale = np.max(np.abs(np.asarray(g)))
        assert np.max(np.abs(sys_g - np.asarray(g))) <= 1e-3 * scale, name

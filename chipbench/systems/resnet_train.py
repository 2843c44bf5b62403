"""The system under test for a ``resnet_train`` configuration: the
program's ResNet through ``Executor.run``, as a user builds it (rig
construction copied from chip_smoke.py's ``TrainRig``)."""

import numpy as np


class Rig(object):
    def __init__(self, run, devices):
        import paddle_tpu as fluid
        from paddle_tpu.models import resnet
        c = run.config
        self.fluid = fluid
        self.chips = len(devices)
        self.batch = int(c['per_chip_batch']) * self.chips
        self.shape = (c['image_size'], c['image_size'], c['image_channels'])
        self.classes = int(c['num_classes'])
        self.main, self.startup = fluid.Program(), fluid.Program()
        self.main.random_seed = self.startup.random_seed = \
            run.seed % (2 ** 31 - 1) + 1
        with fluid.program_guard(self.main, self.startup):
            _img, _label, _pred, self.loss, _acc = resnet.build_imagenet(
                depth=c['depth'], num_classes=self.classes,
                image_shape=self.shape, dtype=c['dtype'],
                layout=c['layout'])
            fluid.optimizer.MomentumOptimizer(
                learning_rate=c['learning_rate'],
                momentum=c['momentum']).minimize(self.loss)
        self.scope = fluid.Scope()
        self.place = fluid.CPUPlace() if run.rehearse else fluid.TPUPlace(0)
        self.exe = fluid.Executor(self.place)
        self.exe.run(self.startup, scope=self.scope)

    def weights(self):
        """{name: array} of the parameters, as the startup program (or
        the last step) left them."""
        return {p.name: self.scope.get(p.name)
                for p in self.main.global_block().all_parameters()}

    def step(self, feed):
        """One synced step: the loss as a float."""
        out, = self.exe.run(self.main, feed=feed, fetch_list=[self.loss],
                            scope=self.scope)
        return float(np.asarray(out).ravel()[0])

    def scratch_bytes(self, feed):
        """Bytes of scratch the compiled step declares for one chip's
        batch (``memory_analysis().temp_size_in_bytes``):
        ``peak_bytes_in_use`` sees resident arrays only (0.64 GB here,
        beside GBs of activations).  ``Executor.compile`` gives the
        one-device step, so a mesh cell asks with one chip's share."""
        per = self.batch // self.chips
        fn, args = self.exe.compile(
            self.main, feed={k: v[:per] for k, v in feed.items()},
            fetch_list=[self.loss], scope=self.scope)
        return int(fn.lower(*args).compile().memory_analysis()
                   .temp_size_in_bytes)

    def pipeline(self, feed_cfg, fill):
        from paddle_tpu.runtime import FeedPipeline, native
        if not native.available():
            raise RuntimeError('the native runtime is not available')
        return FeedPipeline(
            {'img': ((self.batch,) + self.shape, np.float32),
             'label': ((self.batch, 1), np.int32)}, fill,
            depth=int(feed_cfg['depth']), workers=int(feed_cfg['workers']),
            stage=bool(feed_cfg['stage']),
            device=self.place.jax_device())


def host_batches(run, rig, n):
    """A pool of ``n`` global host batches from --seed: ``n`` blocks of
    one chip's batch are drawn, and each global batch is ``chips`` of
    them in an order of its own."""
    rng = np.random.default_rng(run.seed)
    per = rig.batch // rig.chips
    imgs = [rng.standard_normal((per,) + rig.shape, dtype=np.float32)
            for _ in range(n)]
    labels = [rng.integers(0, rig.classes, (per, 1)).astype(np.int32)
              for _ in range(n)]
    pool = []
    for j in range(n):
        pick = [(j + k * (k + 1) // 2) % n for k in range(rig.chips)]
        pool.append({
            'img': np.concatenate([imgs[i] for i in pick])
            if rig.chips > 1 else imgs[j],
            'label': np.concatenate([labels[i] for i in pick])
            if rig.chips > 1 else labels[j]})
    return pool

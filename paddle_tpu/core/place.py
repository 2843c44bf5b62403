"""Device places.

Reference parity: paddle/platform/place.h (CPUPlace / CUDAPlace).  The
TPU-native framework adds TPUPlace; every place resolves to a jax.Device.
"""
import jax


class Place(object):
    _platform = None

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def jax_device(self):
        """Resolve to a concrete LOCAL jax.Device.  A place that names a
        platform (CPUPlace, TPUPlace) raises when that backend is absent
        or holds fewer than ``device_id + 1`` local devices — asking for
        a chip and silently computing somewhere else is how a CPU run
        gets reported as a TPU one.  XLAPlace names no platform and
        means "whatever there is", ordinal wrapped.  Local devices only:
        in a multi-process (distributed.launch) run, jax.devices() leads
        with process 0's devices, which other processes cannot place
        data on."""
        if self._platform is None:
            devs = jax.local_devices()
            return devs[self.device_id % len(devs)]
        try:
            devs = jax.local_devices(backend=self._platform)
        except RuntimeError as e:
            raise RuntimeError(
                "%r: this process has no %r backend (jax's default "
                "backend is %r)" % (self, self._platform,
                                    jax.default_backend())) from e
        if self.device_id >= len(devs):
            raise RuntimeError("%r: only %d local %s device(s)"
                               % (self, len(devs), self._platform))
        return devs[self.device_id]


class CPUPlace(Place):
    _platform = 'cpu'

    def __init__(self):
        super(CPUPlace, self).__init__(0)


class TPUPlace(Place):
    """A single TPU chip.  Parity with the reference's CUDAPlace(id)."""
    _platform = 'tpu'


# CUDAPlace is accepted as an alias so reference scripts run unchanged: on a
# TPU host it resolves to the TPU chip with the same ordinal.
class CUDAPlace(TPUPlace):
    pass


class XLAPlace(Place):
    """Whatever jax's default backend is (tpu > gpu > cpu)."""
    _platform = None


def default_place():
    platform = jax.default_backend()
    if platform == 'cpu':
        return CPUPlace()
    return XLAPlace(0)

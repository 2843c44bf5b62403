"""Every public Pallas entry point lowers for the TPU at its bench shape.

The CPU suite runs the kernels with ``interpret=True``, which skips the
TPU lowering's block-shape rules — how `table_update`'s one-row blocks
passed every test and were refused by the chip's toolchain.  Here each
kernel is traced with ``interpret=False`` and lowered for the TPU
platform from this CPU: seconds, no chip, and a block the lowering
refuses fails here instead of in chip time.  The cases are the ones
chip_smoke.py's `kernels` phase then compiles and runs on the chip.

Lowering is the first gate only; Mosaic's own compile (VMEM limits,
vector layouts) runs inside XLA:TPU.  The `slow` test below runs that
too, against a compile-only v5e topology, where libtpu offers one.
"""
import functools
import os
import sys

import pytest

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402

CASES = chip_smoke.kernel_cases(chip_smoke.CHIP)


def _lowered(case, sharding=None):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in case.specs]
    fn = jax.jit(functools.partial(case.kernel, interpret=False))
    return fn.trace(*args).lower(lowering_platforms=('tpu',))


@pytest.mark.parametrize('case', CASES, ids=lambda c: c.name)
def test_lowers_for_tpu(case):
    assert 'tpu_custom_call' in _lowered(case).as_text()


@pytest.mark.slow
@pytest.mark.parametrize('case', CASES, ids=lambda c: c.name)
def test_mosaic_compiles_for_v5e(case):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # no libtpu here, or it cannot describe one
        pytest.skip('no compile-only TPU topology: %r' % (e,))
    _lowered(case, SingleDeviceSharding(topo.devices[0])).compile()

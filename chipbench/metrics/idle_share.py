"""The device's idle share of the traced window, in percent: 1 - the
union of the device's ops over the window, averaged over the chips."""
from .. import xplane


def read(run):
    tr = run.obs.get('trace')
    bi = tr and xplane.busy_and_idle(tr, xplane.window(tr))
    return 100.0 * bi[2] if bi else None

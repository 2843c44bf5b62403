"""The table of peaks, keyed by ``device_kind``.  An unknown device is an
error, never a default."""
import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'peaks.json')


def lookup(device_kind):
    with open(_PATH) as f:
        table = json.load(f)['devices']
    if device_kind not in table:
        raise KeyError('no peaks for device_kind %r in %s (known: %s)'
                       % (device_kind, _PATH, ', '.join(sorted(table))))
    return table[device_kind]

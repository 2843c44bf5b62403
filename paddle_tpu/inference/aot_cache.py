"""AOT-serialized compiled-executable cache: zero-compile cold start.

The persistent XLA compilation cache (compile_cache.py)
already makes a fresh process's warmup cheap — but not free: every
bucket still pays deserialize + trace + lower before the cache can even
be consulted.  This cache removes the whole pipeline from the serving
cold path by persisting the END PRODUCT: each bucket's compiled
executable is serialized with ``jax.experimental.serialize_executable``
and written as one file per key under ``<dir>/paddle_tpu_aot/`` where
``<dir>`` is PADDLE_TPU_AOT_CACHE_DIR (point it at the compilation
cache dir to keep the serialized executables next to the compiled-HLO
entries they duplicate at a higher level).  A fresh process's
``deploy()`` then deserializes straight into the bucket table:
serving-ready with zero warmup compiles — ``stats()['compiles']`` stays
pinned at 0 on a warm disk cache.

Keying mirrors the tuner winner cache (the stable cross-process key):
the bucket artifact's CONTENT digest stands in for the composite plan
key (the exported StableHLO already embeds the pass pipeline's output
and the baked params), combined with the bucket size, the device kind,
and the jax version — any drift in model bytes, shape, hardware, or
runtime produces a different key, i.e. a plain miss and a normal
compile, never a wrong executable.

File format: one JSON header line (schema-versioned, carries the
source-artifact path for the orphan sweep and the ids of the devices
the executable was compiled for) followed by the pickled
``(payload, in_tree, out_tree)`` triple.  A load hands the executable
back to exactly those devices: one compiled for one device is never
loaded for every local device, and one compiled for devices this
process does not have is a counted miss.  Writes are atomic
(``tmp.<pid>`` + ``os.replace``), so a shared directory behaves under
concurrent fleets the same way the XLA compilation cache does.

Corruption contract (the TuneCache pattern): a header that fails to
parse or a body that fails to deserialize is COUNTED
(``stats()['corrupt']`` / paddle_tpu_aot_cache_corrupt_total) and
treated as a miss — the caller falls back to the normal compile path,
nothing crashes.  A parseable header with the wrong schema / jax
version / device kind is a counted MISS (the entry is valid, just not
for this process).  ``sweep_orphans`` gives the cache dir the same
orphan-tombstone hygiene version GC has: crashed writers' ``.tmp.*``
leftovers and entries whose source artifact was GC'd are removed.
"""
import hashlib
import json
import os
import pickle

import jax

from .. import observability as _obs

try:  # the serving AOT path needs the executable serializer; absent
    # (older jax), the cache quietly disables and warmup compiles
    from jax.experimental import serialize_executable as _se
except Exception:  # pragma: no cover - container jax has it
    _se = None

__all__ = ['AotCache']

_SCHEMA = 2  # 2: the header carries the executable's device ids

# process-wide counters mirrored into the observability registry when
# metrics are enabled — tests read the plain dict, dashboards the
# exposition
_STATS = {'hits': 0, 'misses': 0, 'corrupt': 0, 'stores': 0,
          'orphans': 0}


def _count(which):
    _STATS[which] += 1
    if not _obs.enabled():
        return
    r = _obs.registry()
    name = {'hits': 'paddle_tpu_aot_cache_hits_total',
            'misses': 'paddle_tpu_aot_cache_misses_total',
            'corrupt': 'paddle_tpu_aot_cache_corrupt_total',
            'stores': 'paddle_tpu_aot_cache_stores_total',
            'orphans': 'paddle_tpu_aot_cache_orphans_total'}[which]
    r.counter(name, 'serving AOT executable cache %s' % which).inc()


def _device_kind():
    try:
        return jax.devices()[0].device_kind
    except Exception:  # pragma: no cover - no backend at all
        return 'unknown'


def artifact_digest(path, _bufsize=1 << 20):
    """sha1 of an exported bucket artifact's bytes — the content key
    component that stands in for the composite plan key (the StableHLO
    module embeds the pass pipeline's output and the baked params)."""
    h = hashlib.sha1()
    with open(path, 'rb') as f:
        for chunk in iter(lambda: f.read(_bufsize), b''):
            h.update(chunk)
    return h.hexdigest()


class AotCache(object):
    """Load/store serialized compiled executables keyed by
    (artifact digest, bucket, device kind, jax version).

    ``root=None`` resolves the directory from PADDLE_TPU_AOT_CACHE_DIR;
    an empty resolution disables persistence (``enabled()`` False,
    load always None, store a no-op) — serving still works, a fresh
    process just re-compiles per warmup."""

    def __init__(self, root=None):
        if root is None:
            from ..flags import FLAGS
            root = FLAGS.aot_cache_dir or ''
        self.root = os.path.join(root, 'paddle_tpu_aot') if root else ''

    def enabled(self):
        return bool(self.root) and _se is not None

    @staticmethod
    def key(artifact_sha1, bucket, device_kind=None):
        """Stable digest of the keying components (schema included, so
        a format bump re-keys the world instead of half-matching)."""
        if device_kind is None:
            device_kind = _device_kind()
        blob = repr((_SCHEMA, str(artifact_sha1), int(bucket),
                     str(device_kind), jax.__version__))
        return hashlib.sha1(blob.encode()).hexdigest()

    def path(self, key):
        return os.path.join(self.root, 'aot_%s.bin' % key) \
            if self.root else None

    @staticmethod
    def stats():
        """Process-wide {'hits','misses','corrupt','stores','orphans'}
        counts."""
        return dict(_STATS)

    def load_compiled(self, key):
        """The deserialized, ready-to-call compiled executable for
        ``key``, or None on miss.  A corrupted entry counts and reads
        as a miss (the caller compiles); a parseable header for a
        different schema/jax/device counts as a miss."""
        p = self.path(key)
        if p is None or not self.enabled():
            return None
        try:
            with open(p, 'rb') as f:
                header = f.readline()
                body = f.read()
        except FileNotFoundError:
            _count('misses')
            return None
        except OSError:
            _count('corrupt')
            return None
        try:
            hdr = json.loads(header.decode('utf-8'))
        except (ValueError, UnicodeDecodeError):
            _count('corrupt')
            return None
        if not isinstance(hdr, dict) or hdr.get('schema') != _SCHEMA \
                or hdr.get('jax') != jax.__version__ \
                or hdr.get('device_kind') != _device_kind():
            _count('misses')  # schema-versioned header mismatch
            return None
        by_id = {d.id: d for d in jax.devices()}
        ids = hdr.get('devices')
        if not isinstance(ids, list) or not ids \
                or any(i not in by_id for i in ids):
            _count('misses')  # compiled for devices this process lacks
            return None
        try:
            payload, in_tree, out_tree = pickle.loads(body)
            fn = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in ids])
        except Exception:
            _count('corrupt')
            return None
        _count('hits')
        return fn

    def store(self, key, compiled, artifact=None, bucket=None):
        """Atomically persist a compiled executable under ``key``
        (no-op when persistence is disabled, the executable is not
        serializable on this backend, or the dir is unwritable).
        ``artifact`` records the source bucket file so
        :meth:`sweep_orphans` can tie the entry's lifetime to it."""
        p = self.path(key)
        if p is None or not self.enabled():
            return False
        try:
            payload, in_tree, out_tree = _se.serialize(compiled)
            body = pickle.dumps((payload, in_tree, out_tree))
            devices = [d.id for d in
                       compiled.runtime_executable().local_devices()]
        except Exception:
            return False  # backend can't serialize: quiet degrade
        hdr = {'schema': _SCHEMA, 'jax': jax.__version__,
               'device_kind': _device_kind(), 'devices': devices,
               'artifact': (os.path.abspath(artifact)
                            if artifact else None),
               'bucket': int(bucket) if bucket is not None else None}
        try:
            os.makedirs(self.root, exist_ok=True)
            tmp = p + '.tmp.%d' % os.getpid()
            with open(tmp, 'wb') as f:
                f.write(json.dumps(hdr, sort_keys=True).encode() +
                        b'\n')
                f.write(body)
            os.replace(tmp, p)
        except OSError:
            return False
        _count('stores')
        return True

    def sweep_orphans(self):
        """The version-GC orphan-tombstone sweep, applied to the AOT
        cache dir: remove (a) ``.tmp.*`` leftovers from writers that
        crashed between tmp-write and replace (another process's pid —
        this process's own in-flight write is skipped), and (b)
        entries whose recorded source artifact no longer exists — the
        version dir was GC'd, so the executable can never be wanted
        again and would otherwise leak one file per retired version
        forever.  Entries with an unreadable header are removed too
        (counted corrupt).  Returns the removed file names."""
        if not self.root:
            return []
        try:
            entries = os.listdir(self.root)
        except OSError:
            return []
        removed = []
        own_tmp = '.tmp.%d' % os.getpid()
        for e in sorted(entries):
            p = os.path.join(self.root, e)
            if '.tmp.' in e:
                if e.endswith(own_tmp):
                    continue  # our own write, mid-replace
                try:
                    os.remove(p)
                    removed.append(e)
                    _count('orphans')
                except OSError:
                    pass
                continue
            if not (e.startswith('aot_') and e.endswith('.bin')):
                continue  # not ours: never touch foreign files
            try:
                with open(p, 'rb') as f:
                    hdr = json.loads(f.readline().decode('utf-8'))
                art = hdr.get('artifact') \
                    if isinstance(hdr, dict) else ''
            except (OSError, ValueError, UnicodeDecodeError):
                art = ''  # poisoned header: orphan it
                _count('corrupt')
            if art is None:
                continue  # stored without provenance: keep
            if art == '' or not os.path.exists(art):
                try:
                    os.remove(p)
                    removed.append(e)
                    _count('orphans')
                except OSError:
                    pass
        return removed

"""The PADDLE_TPU_MESH flag: parse it, key plans on it, build its Mesh.

``PADDLE_TPU_MESH`` parses once per lookup (cheap string work), and the
constructed ``jax.sharding.Mesh`` objects are cached per normalized spec
so every plan build under one configuration shares one Mesh instance
(Mesh identity participates in executor plan-cache keys).  Every
sharding-propagation consumer (transpiler/sharding.py, core/executor.py,
the benches) goes through this module.
"""
import threading

__all__ = ['mesh_axes_from_flag', 'mesh_key', 'mesh_for',
           'named_sharding', 'spmd_device_count']

_lock = threading.Lock()
_mesh_cache = {}  # canonical spec string -> Mesh


def mesh_axes_from_flag(value=None):
    """Normalized ``(('dp', 2), ('tp', 2))``-style axes tuple from the
    PADDLE_TPU_MESH flag (or an explicit ``value``), or None when the
    mesh is off.  Parsing/validation lives in
    distributed/spec_layout.py — ONE spec vocabulary."""
    from .spec_layout import parse_mesh_spec
    if value is None:
        from ..flags import FLAGS
        value = FLAGS.mesh
    value = (value or '').strip()
    if not value:
        return None
    return parse_mesh_spec(value)


def mesh_key(value=None):
    """The canonical plan-cache key component for the mesh flag: the
    normalized ``axis=size`` string, or None when off."""
    axes = mesh_axes_from_flag(value)
    if axes is None:
        return None
    return ','.join('%s=%d' % a for a in axes)


def spmd_device_count(axes):
    n = 1
    for _name, size in axes:
        n *= int(size)
    return n


def mesh_for(axes):
    """The cached ``jax.sharding.Mesh`` for a normalized axes tuple.

    Raises with an actionable message when the backend exposes fewer
    devices than the mesh needs (on CPU:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """
    key = ','.join('%s=%d' % a for a in axes)
    with _lock:
        m = _mesh_cache.get(key)
    if m is not None:
        return m
    import jax
    from jax.sharding import AxisType
    n = spmd_device_count(axes)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            "PADDLE_TPU_MESH=%s needs %d devices but the %s backend "
            "exposes %d; on CPU force host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=%d"
            % (key, n, devices[0].platform if devices else '?',
               len(devices), n))
    # make_mesh orders the first n devices along the chip interconnect
    # (enumeration order is not ring order on a TPU); Auto axes keep the
    # GSPMD semantics the sharding pass plans for
    m = jax.make_mesh([s for _n, s in axes],
                      tuple(name for name, _s in axes),
                      axis_types=(AxisType.Auto,) * len(axes),
                      devices=devices[:n])
    with _lock:
        _mesh_cache[key] = m
    return m


def named_sharding(mesh, spec):
    """Tuple-spec -> NamedSharding.  ``spec`` is the hashable per-dim
    tuple the sharding pass stamps (each entry an axis name, a tuple of
    axis names, or None); None means fully replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if spec is None:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(*spec))

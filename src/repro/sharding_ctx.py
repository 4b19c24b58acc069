"""Mesh context + logical-axis sharding constraints.

Model code calls ``constrain(x, *logical_axes)`` with logical names; outside
a mesh context this is a no-op (single-device smoke tests), inside it maps
logical -> physical mesh axes and applies with_sharding_constraint, skipping
any dim the mesh cannot divide evenly (divisibility fallback — see DESIGN.md).

Logical axes:
  "batch"   -> ("pod", "data") when the mesh has a pod axis, else ("data",)
  "tokens"  -> same as batch (flattened token dim)
  "data"    -> ("data",)
  "model"/"expert"/"heads"/"ff"/"vocab" -> ("model",)
  "seq"     -> ("model",)   (context/sequence sharding for long KV)
  None      -> unsharded dim
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

_state = threading.local()


def abstract_mesh(axis_sizes, axis_names):
    """``AbstractMesh`` for shape-only lowering:
    ``abstract_mesh((16, 16), ("data", "model"))``."""
    return jax.sharding.AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axes: the model code places arrays
    through ``constrain`` and expects the compiler to propagate the
    rest, which Explicit axes (the ``jax.make_mesh`` default) refuse."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def shard_map(f, mesh, in_specs, out_specs, check_replication=False):
    """``jax.shard_map`` with the replication check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_replication)


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU — the condition under
    which Pallas kernels compile natively.  Everywhere else (CPU CI,
    laptops) callers fall back to ``interpret=True``."""
    return jax.default_backend() == "tpu"


def default_interpret(interpret):
    """The kernels' shared interpret-mode policy (the ``flash_attention``
    idiom): an explicit True/False wins; ``None`` means "interpret
    everywhere but TPU"."""
    return (not on_tpu()) if interpret is None else bool(interpret)


_LOGICAL = {
    "data": ("data",),
    "model": ("model",),
    "expert": ("model",),
    "heads": ("model",),
    "ff": ("model",),
    "vocab": ("model",),
    "seq": ("model",),
}


def current_mesh():
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh):
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def _physical(mesh, logical):
    if logical is None:
        return None
    if logical in ("batch", "tokens"):
        return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    axes = _LOGICAL[logical]
    return tuple(a for a in axes if a in mesh.axis_names) or None


def axis_size(mesh, physical):
    if physical is None:
        return 1
    n = 1
    for a in (physical if isinstance(physical, tuple) else (physical,)):
        n *= mesh.shape[a]
    return n


def spec_for(mesh, shape, logical_axes):
    """PartitionSpec with divisibility fallback per dim."""
    parts = []
    for dim, logical in zip(shape, logical_axes):
        phys = _physical(mesh, logical)
        if phys is not None and dim % axis_size(mesh, phys) == 0:
            parts.append(phys if len(phys) > 1 else phys[0])
        else:
            parts.append(None)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def constrain(x, *logical_axes):
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = spec_for(mesh, x.shape, logical_axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

"""Production mesh builders.

Functions, not module-level constants, so importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax

from repro.sharding_ctx import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single v5e pod: (16,16)=(data,model), 256 chips.
    Multi-pod: (2,16,16)=(pod,data,model), 512 chips; "pod" is the elastic
    pure-DP axis the cloud provisioner grows/shrinks."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_elastic_mesh(n_pods: int, *, pod_shape=(16, 16)):
    """Mesh for an elastic pool of ``n_pods`` pods (n_pods >= 1). The pod
    axis is what core/elastic.py re-sizes when spot capacity changes."""
    if n_pods == 1:
        return make_mesh(pod_shape, ("data", "model"))
    return make_mesh((n_pods,) + pod_shape, ("pod", "data", "model"))


def make_host_mesh(shape=None, axes=("data", "model")):
    """Mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1) if len(axes) == 2 else (n,)
    return make_mesh(shape, axes)

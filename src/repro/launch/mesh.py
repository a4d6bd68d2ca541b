"""Production mesh construction (spec-mandated entry point).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state, so tests/benches that import it still see the
single CPU device unless they explicitly build the mesh.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (XLA SPMD propagation);
    enter it as the ambient mesh with ``jax.set_mesh(mesh)``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) single pod; 2x16x16 (pod, data, model) for two
    pods.  ``pod`` is the slow cross-pod (DCN/ICI-cross) axis and by
    default only ever carries batch (pure DP), so the sole cross-pod
    collective is the gradient all-reduce (DESIGN.md §4)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh on the real local device (smoke tests, examples)."""
    return make_mesh((1, 1), ("data", "model"))


def require_virtual_devices(n: int = 512) -> None:
    """Sanity check that the dry-run env var took effect."""
    have = jax.device_count()
    if have < n:
        raise RuntimeError(
            f"dry-run needs {n} host platform devices, found {have}. "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 must be "
            "set before jax initializes (launch/dryrun.py does this).")

"""Mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
XLA_FLAGS for 512 placeholder devices before any jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes.  The code places data through
    ``shard_map`` specs and ``NamedSharding``; jax's default Explicit
    axes would carry shardings in the array types, which it does not
    use."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


# the chip the production mesh is made of (as jax's ``device_kind``
# names it); compile-only runs on placeholder devices take their roofline
# peaks from it
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16x16 per pod, 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(tp: int = 1, *, pods: int = 1, devices=None):
    """Best-effort mesh over ``devices`` (default: whatever devices
    exist; tests / CPU runs)."""
    n = len(devices) if devices is not None else jax.device_count()
    tp = min(tp, n)
    dp = n // (tp * pods)
    if pods > 1:
        return make_mesh((pods, dp, tp), ("pod", "data", "model"), devices)
    return make_mesh((dp, tp), ("data", "model"), devices)


def mesh_axes(mesh) -> tuple[tuple, str]:
    """(data_axes, model_axis) for a mesh built by the functions above."""
    names = mesh.axis_names
    data_axes = tuple(n for n in names if n in ("pod", "data"))
    return data_axes, "model"

"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state.  Single pod: 16 x 16 = 256 chips (v5e-256 class).  Multi-pod:
2 x 16 x 16 = 512 chips with a leading "pod" axis (DCN-connected pods; the
"pod" axis carries only data parallelism + gradient reduction).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "make_mesh",
           "DEVICES_PER_HOST"]

#: v5e hosts drive 4 chips each
DEVICES_PER_HOST = 4


def make_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-partitioned).
    ``devices`` defaults to ``jax.devices()``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=None, axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (1, n)
    return make_mesh(shape, axes)

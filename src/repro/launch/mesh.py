"""Production meshes.

Target hardware: TPU v5e pods — 256 chips/pod (16x16), 197 bf16
TFLOP/s + 819 GB/s HBM per chip, ~50 GB/s/link ICI.

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state; callers opt in.
The dry-run spawns processes with
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so 512 host placeholder devices exist.
"""

from __future__ import annotations

import jax
import numpy as np


# ---- hardware constants used by the roofline analysis (EXPERIMENTS.md) ----
TPU_V5E = {
    "peak_flops_bf16": 197e12,   # per chip
    "hbm_bw": 819e9,             # bytes/s per chip
    "ici_bw": 50e9,              # bytes/s per link
    "hbm_bytes": 16e9,           # HBM capacity per chip
}

SINGLE_POD_SHAPE = (16, 16)
SINGLE_POD_AXES = ("data", "model")
MULTI_POD_SHAPE = (2, 16, 16)
MULTI_POD_AXES = ("pod", "data", "model")


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes: the sharding policy hands GSPMD
    NamedShardings and lets it propagate, which Explicit axes (the
    installed default) refuse at gathers and matmuls."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = MULTI_POD_AXES if multi_pod else SINGLE_POD_AXES
    return _auto_mesh(shape, axes)


def make_host_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over the visible devices (needs >= prod(shape))."""
    return _auto_mesh(shape, axes)


def chips(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))

"""Production mesh builders.

A function (not a module-level constant) so importing this module never
touches jax device state.  Production target: TPU v5e, 256 chips per pod,
(data=16, model=16); multi-pod doubles up with a leading "pod" axis.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto (GSPMD) mode."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    model = min(model, n)
    return make_mesh((n // model, model), ("data", "model"))


def batch_axes_of(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


# Hardware constants for the roofline model (TPU v5e).
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
HBM_PER_CHIP = 16 * 1024 ** 3     # 16 GiB

"""Production mesh construction.

A function (never a module-level constant) so importing this module does not
touch jax device state — device counts are locked at first jax init, and only
``launch/dryrun.py`` is allowed to force the 512-placeholder-device config.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one v5e pod's worth of chips) or 2x16x16 (two pods).

    Axes: "data" carries batch + FSDP; "model" carries TP/EP; "pod" is the
    cross-pod data-parallel axis (DCN-connected in a real deployment)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(*, multi_pod: bool = False):
    """Small-device-count mesh with the same axis names (CI smoke)."""
    shape = (2, 2, 4) if multi_pod else (4, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))

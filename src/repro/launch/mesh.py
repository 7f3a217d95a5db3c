"""Production meshes.

Single pod: (16, 16) = 256 chips, axes ("data", "model") — a TPU v5e
pod's 2-D ICI torus maps data-parallel x model-parallel.
Multi-pod: (2, 16, 16) = 512 chips, axes ("pod", "data", "model") —
the ``pod`` axis is the outer data-parallel dim whose collectives cross
the inter-pod links (where the int8 gradient compression applies).

Functions, not module constants: importing this module never touches
jax device state (device count is locked at first jax init, and only
``dryrun.py`` forces the 512-device host platform).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    # GSPMD sharding hints (``with_sharding_constraint`` in
    # ``distributed/sharding.py``) may only name Auto axes, and
    # ``jax.make_mesh`` makes Explicit ones by default
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires >=4 forced host devices)."""
    return _auto_mesh(shape, axes)


def describe(mesh) -> str:
    return " x ".join(f"{k}={v}" for k, v in mesh.shape.items())

"""Production mesh construction.

A TPU v5e pod is a 16x16 chip torus (256 chips); the multi-pod deployment
adds a leading ``pod`` axis over the (slower) DCN/pod-interconnect domain.
``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state (device count is locked at first use).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """Mesh with Auto axes (GSPMD propagates shardings from the constraints
    and in/out shardings); ``devices`` defaults to all visible ones."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_pipeline_mesh(n_stages: int, n_data: int):
    """Mesh for the shard_map merged-pipeline runtime."""
    return make_mesh((n_stages, n_data), ("stage", "data"))


def single_device_mesh(axes: tuple[str, ...] = ("data", "model")):
    return make_mesh((1,) * len(axes), axes)

"""Mesh constructors shared by tests and examples.

The *production* mesh lives in ``repro.launch.mesh`` (kept import-free of
device state); these helpers build small meshes out of whatever devices the
current process has (CPU tests run with XLA_FLAGS=--xla_force_host_platform_
device_count=8 in a subprocess).
"""
from __future__ import annotations

from typing import Tuple

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["make_mesh", "abstract_mesh", "single_device_mesh", "best_effort_mesh"]


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    n = int(np.prod(shape))
    devs = jax.devices()
    assert len(devs) >= n, f"need {n} devices, have {len(devs)}"
    return Mesh(np.asarray(devs[:n]).reshape(shape), axes)


def abstract_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Device-free mesh for spec-level tests and dry-runs."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


def single_device_mesh(axes: Tuple[str, ...] = ("data", "model")) -> Mesh:
    return Mesh(np.asarray(jax.devices()[:1]).reshape((1,) * len(axes)), axes)


def best_effort_mesh(axes: Tuple[str, ...] = ("data", "model"),
                     prefer_model: int = 1) -> Mesh:
    """Use all local devices: model axis = prefer_model (if it divides), rest data."""
    n = len(jax.devices())
    model = prefer_model if n % prefer_model == 0 else 1
    shape = (n // model, model)
    if len(axes) == 3:
        shape = (1,) + shape
    return make_mesh(shape, axes)

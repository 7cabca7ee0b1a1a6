"""Device meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state. Axes: ("data", "model").
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_host_mesh(data: Optional[int] = None, model: int = 1):
    """Small mesh over whatever devices this host actually has (tests)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    # jax.make_mesh defaults to Explicit axes, under which the models'
    # embedding gathers raise ShardingTypeError; they are written for Auto
    # (compiler-propagated) sharding.
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes that shard the batch dimension."""
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))


def model_size(mesh) -> int:
    return mesh.shape["model"]


def batch_shard_size(mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n

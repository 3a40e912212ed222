"""Mesh and shard_map helpers in the one spelling every sharded path uses."""
from __future__ import annotations

import math

import jax
import numpy as np


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` with replication checking off.

    Outputs of every caller in this repo are value-replicated after an
    all-gather/psum, which the static replication checker cannot prove —
    hence ``check_vma=False``.  ``axis_names`` restricts the manual axes.
    """
    kwargs = {} if axis_names is None else {"axis_names": frozenset(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False, **kwargs)


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` over every visible device, with Auto axes."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_submesh(shape, axis_names, devices=None):
    """A mesh over the FIRST prod(shape) devices.

    ``jax.make_mesh`` insists on consuming every visible device, which
    makes "run the P=2 layout on the 8-device CI host" impossible
    through it.  Build the Mesh directly over a device prefix instead;
    when the shape covers every device, :func:`make_mesh` picks the
    device order.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    need = math.prod(shape)
    if need > len(devices):
        raise ValueError(
            f"mesh shape {tuple(shape)} needs {need} devices, "
            f"only {len(devices)} visible")
    if need == len(devices) == len(jax.devices()):
        return make_mesh(tuple(shape), tuple(axis_names))
    grid = np.array(devices[:need]).reshape(tuple(shape))
    return jax.sharding.Mesh(
        grid, tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape))

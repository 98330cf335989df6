"""Mesh and shard_map construction with this repo's defaults.

``jax.make_mesh`` gives every axis the ``Explicit`` type unless told
otherwise; the sharding rules here rely on GSPMD propagation, so meshes are
built with ``Auto`` axes.  shard_map bodies use collectives whose
replication the varying-manual-axes checker cannot infer (ppermute rings,
psums of partial sums), so the check is off.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma=False``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))

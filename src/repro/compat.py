"""The repo's spellings of ``shard_map`` and ``make_mesh``: replication
checks off, and every mesh axis ``Auto`` (GSPMD propagates through them)."""
from __future__ import annotations

import jax
from jax import shard_map as _shard_map


def shard_map(f, *, mesh, in_specs, out_specs, check=False):
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=check)


def make_mesh(shape, axis_names):
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))

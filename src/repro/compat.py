"""JAX wrappers that pin an argument the repo always passes the same way.

The repo runs on one JAX, 0.9.0 (see ROADMAP.md "JAX version"); call sites
use its spellings directly (``jax.set_mesh``, ``pltpu.CompilerParams``).
A wrapper lives here only where it fixes an argument:

* ``make_mesh`` — every axis is ``AxisType.Auto``. ``jax.make_mesh``
  defaults to ``Explicit`` axes, which the sharding rules
  (``parallel/sharding.py``) and the serving TP plan do not use.
* ``shard_map`` — the replication check is always off
  (``check_vma=False``): the manual bodies the serving path maps contain
  ``pallas_call`` and explicit ``psum``s, which the checker cannot type.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence[Any]] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto. ``devices``: explicit
    device list (e.g. a replica's slice of ``jax.devices()``); default
    lets JAX pick all local devices."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=None if devices is None else tuple(devices))


def shard_map(fn, mesh: jax.sharding.Mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check disabled (manual bodies
    here carry pallas_call + explicit psums, which the checker rejects)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

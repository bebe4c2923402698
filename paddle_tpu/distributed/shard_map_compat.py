"""``shard_map`` — one call-site contract for the distributed runtimes.

Every caller — collectives, pipeline schedules, ring attention — speaks
ONE signature over ``jax.shard_map``:

    shard_map(f, mesh, in_specs, out_specs, axis_names=None, check=False)

``axis_names`` is the set of mesh axes the body maps manually (None =
all of them); ``check`` is the static varying-manual-axes checker
(``check_vma``), off by default because the schedules' bodies mix
replicated and device-varying carries.
"""
from __future__ import annotations

import jax


def pvary(x, axis_name):
    """Mark ``x`` device-varying over ``axis_name`` for the VMA checker
    (a loop carry initialized from a constant must be varying before a
    ppermute result is written into it)."""
    return jax.lax.pcast(x, axis_name, to="varying")


def shard_map(f, mesh, in_specs, out_specs, axis_names=None, check=False):
    # jax.shard_map's own "all axes" is the empty set
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check,
                         axis_names=frozenset(axis_names or ()))

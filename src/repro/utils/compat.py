"""Thin wrappers over jax APIs whose spelling the call sites keep stable.

Written for the one installed jax (0.9): ``shard_map`` is ``jax.shard_map``
(replication check ``check_vma``), ``axis_size`` is ``jax.lax.axis_size``,
``AbstractMesh`` takes ``(axis_sizes, axis_names)`` and
``Compiled.cost_analysis()`` returns a dict.
"""
from __future__ import annotations

from jax import shard_map as _shard_map
from jax.lax import axis_size


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True, axis_names=None, **kwargs):
    """``jax.shard_map`` with ``axis_names`` (the manual axes) optional."""
    if axis_names is not None:
        kwargs["axis_names"] = axis_names
    return _shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma, **kwargs
    )


def abstract_mesh(axis_sizes, axis_names):
    """``jax.sharding.AbstractMesh`` from parallel size/name sequences."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def compiled_cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (empty when XLA reports none)."""
    return dict(compiled.cost_analysis() or {})


__all__ = ["shard_map", "axis_size", "abstract_mesh", "compiled_cost_analysis"]

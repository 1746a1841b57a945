"""Mesh constructors with the axis types this repo's sharding code expects.

`jax.make_mesh` and `jax.sharding.AbstractMesh` default to `Explicit`
axes, under which every op must agree on its operands' shardings
(`dynamic_update_slice` of a replicated update into a sharded cache is a
`ShardingTypeError`). The planner and the program cells here place arrays
with `NamedSharding`s and `with_sharding_constraint` and leave the rest to
GSPMD, which is what `Auto` axes mean. Every mesh goes through these two
helpers so none is built with the other default by accident.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax

__all__ = ["make_mesh", "abstract_mesh"]


def _auto(axis_names: Sequence[str]) -> tuple:
    return (jax.sharding.AxisType.Auto,) * len(tuple(axis_names))


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices: Sequence[Any] | None = None) -> jax.sharding.Mesh:
    """`jax.make_mesh` with `Auto` axes."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=_auto(axis_names), devices=devices)


def abstract_mesh(axis_shapes: Sequence[int],
                  axis_names: Sequence[str]) -> jax.sharding.AbstractMesh:
    """`jax.sharding.AbstractMesh` with `Auto` axes."""
    return jax.sharding.AbstractMesh(tuple(axis_shapes), tuple(axis_names),
                                     axis_types=_auto(axis_names))

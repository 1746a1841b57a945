"""Production meshes.

Single pod  : (16, 16) over ("data", "model")  — 256 chips, the MemPool
              cluster analogue (256 PEs; `data` plays the tile-group rows,
              `model` the columns of the 2-D ICI torus).
Multi-pod   : (2, 16, 16) over ("pod", "data", "model") — 512 chips across
              two pods connected by DCN.

`make_production_mesh` is a function (not a module constant) so importing
this module never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax import
(see dryrun.py) and only then builds the mesh.
"""

from __future__ import annotations

import jax

from repro.core import compat


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Scaled-down mesh for CI: 8 devices, same axis structure."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)

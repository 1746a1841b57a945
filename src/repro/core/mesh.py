"""Hierarchical machine topology — the TPU analogue of MemPool's tile/group/cluster.

MemPool (paper Fig. 1)           This module (TPU v5e pod)
---------------------------      -------------------------------------------
tile   : 4 cores + 16 banks,     chip  : MXU+VPU + 16 GiB HBM   (level 0,
         1-cycle local xbar               zero-collective "local" accesses)
group  : 16 tiles, 3-cycle       group : 16-chip ICI mesh axis  (level 1,
         local crossbar                   1-hop neighbor links)
cluster: 4 groups, 5-cycle       pod   : 16x16 2-D ICI torus    (level 2,
         remote crossbars                 <= diameter-hop paths)
multi-cluster over L2/AXI        multi-pod over DCN             (level 3)

The latency/bandwidth numbers drive the sharding planner (core/addressing.py)
and the collective cost model (core/interconnect.py), the same way the paper's
1/3/5-cycle levels drive its hybrid addressing scheme.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax

from repro.core import compat

# ----------------------------------------------------------------------------
# Hardware constants (TPU v5e target, per task spec)
# ----------------------------------------------------------------------------

PEAK_FLOPS_BF16 = 197e12          # FLOP/s per chip
HBM_BW = 819e9                    # B/s per chip
ICI_BW_PER_LINK = 50e9            # B/s per ICI link (one direction)
DCN_BW_PER_HOST = 25e9            # B/s per host across pods (assumed)
HBM_BYTES = 16 * 1024**3          # 16 GiB HBM per chip
VMEM_BYTES = 128 * 1024**2        # ~128 MiB VMEM per chip (v5e ~ 128MB)
MXU_TILE = 128                    # systolic array edge; align matmul dims to this
VPU_LANE = 8 * 128                # (8, 128) vector registers

# MemPool reference constants (used by benchmarks reproducing paper figures)
MEMPOOL = dict(
    n_cores=256, n_banks=1024, l1_bytes=1 << 20, banking_factor=4,
    local_latency=1, group_latency=3, remote_latency=5,
    freq_hz=600e6, peak_ops=256,  # 1 op/core/cycle (MAC counts 2 in paper's GOPS)
)


@dataclasses.dataclass(frozen=True)
class Level:
    """One level of the machine hierarchy (tile/group/cluster/pod analogue)."""
    name: str
    fanout: int          # number of children units at this level
    latency_s: float     # one-way latency to cross this level
    bw_bytes: float      # per-chip bandwidth available at this level


@dataclasses.dataclass(frozen=True)
class Topology:
    """Hierarchical topology with per-level latency/bandwidth.

    `levels[0]` is the chip itself (HBM); higher indices are progressively
    remote — exactly the paper's tile < group < cluster ordering.
    """
    levels: tuple[Level, ...]
    mesh_shape: tuple[int, ...]
    axis_names: tuple[str, ...]

    @property
    def n_chips(self) -> int:
        return math.prod(self.mesh_shape)

    def level(self, name: str) -> Level:
        for lvl in self.levels:
            if lvl.name == name:
                return lvl
        raise KeyError(name)

    def axis_size(self, axis: str) -> int:
        return self.mesh_shape[self.axis_names.index(axis)]

    def bisection_bw(self, axis: str) -> float:
        """Aggregate bandwidth across the bisection of one mesh axis (B/s)."""
        n = self.axis_size(axis)
        other = self.n_chips // n
        # 2-D torus: each row/col contributes 2 wraparound links per cut.
        links = 2 * other
        return links * ICI_BW_PER_LINK

    def ring_allgather_time(self, axis: str, bytes_per_chip: float) -> float:
        """Ring all-gather of `bytes_per_chip` over one axis (α–β model)."""
        n = self.axis_size(axis)
        if n <= 1:
            return 0.0
        lvl = self._axis_level(axis)
        steps = n - 1
        return steps * (lvl.latency_s + bytes_per_chip / lvl.bw_bytes)

    def _axis_level(self, axis: str) -> Level:
        if axis == "pod":
            return self.level("dcn")
        return self.level("ici")


def v5e_topology(mesh_shape: Sequence[int], axis_names: Sequence[str]) -> Topology:
    """Standard v5e hierarchy for the production meshes used in this repo."""
    levels = (
        Level("hbm", 1, 1e-7, HBM_BW),
        Level("ici", 16, 1e-6, 2 * ICI_BW_PER_LINK),   # 2 links per axis direction
        Level("dcn", 2, 1e-5, DCN_BW_PER_HOST),
    )
    return Topology(levels=levels, mesh_shape=tuple(mesh_shape),
                    axis_names=tuple(axis_names))


# ----------------------------------------------------------------------------
# Mesh construction
# ----------------------------------------------------------------------------

def make_mesh(shape: Sequence[int], axis_names: Sequence[str]) -> jax.sharding.Mesh:
    """Build a jax Mesh, tolerating CPU hosts with fewer devices than requested.

    For single-device smoke runs, the caller should pass a shape matching the
    available device count; the production 16x16 / 2x16x16 meshes are built
    by launch/mesh.py under XLA_FLAGS=--xla_force_host_platform_device_count.
    """
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"mesh shape {tuple(shape)} needs {n} devices, but only "
            f"{len(devices)} are visible. Set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before importing jax "
            f"(see launch/dryrun.py).")
    return compat.make_mesh(shape, axis_names)


def smoke_mesh(axis_names: Sequence[str] = ("data", "model")) -> jax.sharding.Mesh:
    """1-chip (or few-chip) mesh for CPU smoke tests — every axis size 1."""
    return compat.make_mesh((1,) * len(axis_names), axis_names)

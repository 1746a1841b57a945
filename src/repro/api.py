"""High-level API — the "OpenMP layer" of the three programming models.

MemPool offers bare-metal C (full control), OpenMP (fork-join convenience),
and Halide (declarative). This framework mirrors that:

  bare-metal : repro.models.steps + explicit PartitionSpecs / shard_map
  OpenMP     : repro.cluster — Cluster + program specs; THIS module keeps
               the legacy one-call train/serve/plan signatures as thin
               deprecating shims over it
  Halide     : the config-driven launcher (repro.launch.train CLI)

New code should build a `repro.cluster.Cluster` and compile programs on it;
these wrappers exist so old call sites keep working.
"""

from __future__ import annotations

import warnings
from typing import Any

import jax

from repro.cluster import Cluster, ServeSessionProgram, TrainProgram

_UNSET = object()


def plan(arch: str, mesh: jax.sharding.Mesh) -> dict[str, Any]:
    """The hybrid addressing plan for an architecture on a mesh:
    {tree path: (logical axes, PartitionSpec, region)} for every parameter.

    Shim over `Cluster(arch, mesh).plan()`.
    """
    return Cluster(arch, mesh).plan()


def train(arch: str, *, num_steps: int | None = None, steps_=_UNSET,
          batch: int = 4, seq: int = 128, smoke: bool = True,
          checkpoint_dir: str = "/tmp/repro-api-train",
          mesh: jax.sharding.Mesh | None = None, seed: int = 0) -> dict:
    """One-call training on the synthetic stream. Returns the loop report.

    Shim over `Cluster(...).compile(TrainProgram(...)).run()`. `steps_` is a
    deprecated alias for `num_steps` (kept for one release).
    """
    if steps_ is not _UNSET:
        warnings.warn("api.train(steps_=...) is deprecated; use num_steps=",
                      DeprecationWarning, stacklevel=2)
        if num_steps is None:
            num_steps = steps_
    if num_steps is None:
        num_steps = 100
    cluster = Cluster(arch + ("-smoke" if smoke else ""), mesh)
    program = cluster.compile(TrainProgram(
        num_steps=num_steps, batch=batch, seq=seq, seed=seed,
        checkpoint_dir=checkpoint_dir))
    return program.run()


def serve(arch: str, params=None, *, batch: int = 4, max_seq: int = 64,
          max_new: int = 16, smoke: bool = True, seed: int = 0,
          chunk: int = 1) -> dict:
    """One-call batched greedy decoding. Returns tokens + session stats.

    Shim over the request-level serving API: opens a `ServeSession`
    (`Cluster(...).compile(ServeSessionProgram(...))`), submits one batch
    of requests (one per slot), and drains; the result is
    `CompiledServeSession.run()`'s `{"tokens", "stats"}`. `chunk` is the
    decode-steps-per-host-sync knob (1 = one sync per token; K > 1 buries
    K steps in one device program). New code should open a session
    directly and use `submit`/`stream`/`drain`.
    """
    cluster = Cluster(arch + ("-smoke" if smoke else ""))
    program = cluster.compile(ServeSessionProgram(
        slots=batch, max_seq=max_seq, max_new=max_new, seed=seed,
        chunk=chunk))
    return program.run(params=params)

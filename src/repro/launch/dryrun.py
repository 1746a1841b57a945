import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The dry-run compiles for 512 *CPU* host devices; pinning the platform
# keeps it off an attached TPU, which another process may hold.
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (jax locks platform and
# device count on first init). REPRO_DRYRUN_DEVICES overrides for
# scaled-down CI runs (still before the jax import below).
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, extract memory/cost/collective analysis, write one JSON per cell.

A thin wrapper over the Cluster façade: each cell builds a
`repro.cluster.Cluster` on the production mesh and compiles a
`DryRunProgram` on it (the lower/compile/analyze body lives there); this
module keeps the CLI, the variant table, and the JSON envelope.

This is the proof that the distribution config is coherent: a sharding
mismatch, OOM-at-compile, or unsupported collective fails the cell. The
roofline tables in EXPERIMENTS.md are generated from these JSONs by
launch/roofline.py.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both     # spawn one proc per cell
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

from repro.cluster import Cluster, DryRunProgram
from repro.cluster.cells import (batch_logical, build_cell,  # noqa: F401
                                 layer_gather_specs, model_flops,
                                 shardings_for)
from repro.configs import SHAPES, ARCHS, get
from repro.launch.mesh import make_production_mesh

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


# §Perf hillclimb variants: config deltas applied on top of the baseline.
# "_fsdp_gather" is a build-level switch (forces per-layer weight AG over
# the data axis inside the scan) rather than a config field.
VARIANTS: dict[str, dict] = {
    "folded": {"attn_schedule": "folded"},          # exact-causal schedule
    "localmoe": {"moe_local_dispatch": True},       # shard-local dispatch
    "tponly": {"rules_overrides": (("embed", None),)},  # no FSDP: p_local max
    "localmoe_tponly": {"moe_local_dispatch": True,
                        "rules_overrides": (("embed", None),)},
    "chunk512": {"attn_chunk": 512},
    "chunk2048": {"attn_chunk": 2048},
    "accum4": {"grad_accum": 4},
    "folded_chunk2048": {"attn_schedule": "folded", "attn_chunk": 2048},
    "fsdpgather": {"_fsdp_gather": True},
    "fsdpgather_localmoe": {"_fsdp_gather": True, "moe_local_dispatch": True},
    "fsdpgather_folded": {"_fsdp_gather": True, "attn_schedule": "folded"},
    "cap1": {"capacity_factor": 1.0},
    "cap1_localmoe": {"capacity_factor": 1.0, "moe_local_dispatch": True},
}


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_dir: Path = RESULTS, variant: str | None = None) -> dict:
    import dataclasses
    cfg = get(arch)
    fsdp_gather = False
    if variant:
        deltas = dict(VARIANTS[variant])
        fsdp_gather = deltas.pop("_fsdp_gather", False)
        if deltas:
            cfg = dataclasses.replace(cfg, **deltas)
    multi = mesh_kind == "multi"
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "variant": variant, "timestamp": time.time()}

    mesh = make_production_mesh(multi_pod=multi)
    cluster = Cluster(cfg, mesh)
    program = cluster.compile(DryRunProgram(shape=shape_name,
                                            fsdp_gather=fsdp_gather))
    record |= program.run()
    _write(record, out_dir)
    return record


def _write(record: dict, out_dir: Path):
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{record['arch']}__{record['shape']}__{record['mesh']}"
    if record.get("variant"):
        name += f"__{record['variant']}"
    (out_dir / (name + ".json")).write_text(
        json.dumps(record, indent=2, default=float))
    print(f"[dryrun] wrote {name}.json: status={record.get('status')} "
          f"(compiled on the cpu platform)")


def run_all(mesh_kinds: list[str], timeout: int = 3600,
            jobs: int = 1, only_missing: bool = False):
    cells = [(a, s, m) for a in ARCHS for s in SHAPES for m in mesh_kinds]
    if only_missing:
        cells = [c for c in cells
                 if not (RESULTS / f"{c[0]}__{c[1]}__{c[2]}.json").exists()]
    print(f"[dryrun] {len(cells)} cells to run, one cpu-platform process "
          f"each")
    procs: list[tuple] = []
    results = []

    def drain(block_all=False):
        while procs and (block_all or len(procs) >= jobs):
            p, cell, t0 = procs.pop(0)
            try:
                rc = p.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                p.kill()
                rc = -9
            results.append((cell, rc, time.time() - t0))
            print(f"[dryrun] {cell} rc={rc} ({time.time()-t0:.0f}s)")

    for cell in cells:
        drain()
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", cell[0],
               "--shape", cell[1], "--mesh", cell[2]]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        procs.append((subprocess.Popen(cmd, env=env), cell, time.time()))
    drain(block_all=True)
    failed = [c for c, rc, _ in results if rc != 0]
    print(f"[dryrun] done; {len(failed)} failed: {failed}")
    return failed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--only-missing", action="store_true")
    ap.add_argument("--variant", default=None, choices=sorted(VARIANTS))
    args = ap.parse_args()
    kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        failed = run_all(kinds, timeout=args.timeout, jobs=args.jobs,
                         only_missing=args.only_missing)
        sys.exit(1 if failed else 0)
    assert args.arch and args.shape, "--arch/--shape required without --all"
    try:
        for kind in kinds:
            run_cell(args.arch, args.shape, kind, variant=args.variant)
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()

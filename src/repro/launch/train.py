"""Training launcher — the declarative ("Halide-layer") entry point.

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen3-14b --smoke --steps 50 --batch 4 --seq 128

A thin wrapper over the Cluster façade: the CLI builds one
`repro.cluster.Cluster` (mesh + addressing + kernel policy) and compiles a
`TrainProgram` on it. On CPU this runs reduced configs end-to-end (data
pipeline -> region-planned shardings -> compiled train step ->
checkpointing); on a TPU fleet the same invocation with the production
mesh shape trains the full config.
"""

from __future__ import annotations

import argparse

import jax

from repro.cluster import Cluster, TrainProgram
from repro.cluster.policy import MODES
from repro.configs import get
from repro.core import compat
from repro.runtime.compile_cache import use_persistent_cache


def main():
    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro-train")
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--data-axis", type=int, default=0,
                    help="data axis size (0 = all devices)")
    ap.add_argument("--policy", default=None, choices=MODES,
                    help="kernel policy mode (default: env-derived)")
    ap.add_argument("--no-resume", action="store_true",
                    help="ignore checkpoints in --checkpoint-dir")
    args = ap.parse_args()

    cfg = get(args.arch + ("-smoke" if args.smoke else ""))
    n_dev = jax.device_count()
    data = args.data_axis or n_dev
    mesh = compat.make_mesh((data, n_dev // data), ("data", "model"))

    cluster = Cluster(cfg, mesh, policy=args.policy)
    program = cluster.compile(TrainProgram(
        num_steps=args.steps, batch=args.batch, seq=args.seq,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        resume=not args.no_resume))
    report = program.run()

    print(f"\nfinal step {report['final_step']} "
          f"in {report['wall_seconds']:.1f}s; "
          f"stragglers={len(report['straggler_events'])}")
    for m in report["metrics"][-5:]:
        print(f"  step {m['step']:>5d} loss={m['loss']:.4f} "
              f"{m['seconds'] * 1e3:.0f}ms")


if __name__ == "__main__":
    main()

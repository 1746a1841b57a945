"""Batched serving: decode a batch of requests against a shared KV cache.

A thin wrapper over the Cluster façade: one `ServeProgram` handles the
token-by-token prompt ingest (continuous-batching style) and the greedy
generation loop. Generation runs on the device-resident execution engine —
`--chunk K` decode steps are compiled into one `lax.scan` program with
donated cache/token buffers, so the host syncs once per K tokens instead
of per token (`--chunk 1` falls back to the per-token loop; the tokens are
bit-identical either way). The stats line reports the StallClock ledger:
host-sync count and `stall_pct`, the host-side dispatch gap as a fraction
of wall time — the paper's execution-stall figure.

    PYTHONPATH=src python examples/serve_batched.py --batch 8 --new 32

This is the fixed-batch path: every slot runs to the slowest request.
For request-level serving — submit/stream/cancel against a slot pool with
continuous batching — see `examples/serve_continuous.py` (ServeSession).
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax

from repro.cluster import Cluster, ServeProgram
from repro.runtime.compile_cache import use_persistent_cache


def main():
    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=16,
                    help="decode steps per host sync (1 = per-token loop)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop a slot once it emits this token id")
    args = ap.parse_args()

    cluster = Cluster(args.arch + "-smoke")
    cfg = cluster.arch
    program = cluster.compile(ServeProgram(batch=args.batch, max_seq=64,
                                           max_new=args.new,
                                           chunk=args.chunk,
                                           eos_id=args.eos_id))

    prompt = jax.random.randint(jax.random.PRNGKey(0), (args.batch, 8), 0,
                                cfg.vocab)
    out = program.run(prompt=prompt)
    stats = out["stats"]
    stall = stats["stall"]
    print(f"arch={cfg.name} batch={args.batch} generated {args.new} "
          f"tokens/slot (chunk={stats['chunk']})")
    print(f"p50={stats['p50_ms']:.1f}ms p99={stats['p99_ms']:.1f}ms "
          f"{stats['tokens_per_s_per_slot']:.1f} tok/s/slot")
    print(f"engine: {stall['host_syncs']} host syncs, "
          f"stall={stall['stall_pct']:.1f}% "
          f"(dispatch gap {stall['dispatch_gap_s'] * 1e3:.1f}ms over "
          f"{stall['wall_s'] * 1e3:.0f}ms)")
    if "finished_slots" in stats:
        print(f"finished at eos: {stats['finished_slots']}/{args.batch}, "
              f"emitted={stats['emitted_per_slot']}")
    print("sample:", out["tokens"][0][:16].tolist())


if __name__ == "__main__":
    main()

"""Batched serving: decode one batch of requests through a serving session.

A thin wrapper over the Cluster façade: `ServeSessionProgram(...).run()`
submits one request per slot, prefills each prompt inside the compiled
session cell, and drains the greedy generation. `--chunk K` decode steps
are compiled into one `lax.scan` program over the donated pool state, so
the host syncs once per K tokens instead of per token (`--chunk 1` syncs
every token; the tokens are bit-identical either way). The stats lines
report the session's per-request TTFT and latency and the StallClock
ledger: host-sync count and `stall_pct`, the host-side dispatch gap as a
fraction of wall time — the paper's execution-stall figure.

    PYTHONPATH=src python examples/serve_batched.py --batch 8 --new 32

For a stream of requests — submit/stream/cancel against the slot pool as
they arrive — see `examples/serve_continuous.py`.
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax

from repro.cluster import Cluster, ServeSessionProgram
from repro.runtime.compile_cache import use_persistent_cache

PROMPT_LEN = 8


def main():
    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=16,
                    help="decode steps per host sync (1 = every token)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop a slot once it emits this token id")
    args = ap.parse_args()

    cluster = Cluster(args.arch + "-smoke")
    cfg = cluster.arch
    program = cluster.compile(ServeSessionProgram(
        slots=args.batch, max_seq=64, max_prompt=PROMPT_LEN,
        max_new=args.new, chunk=args.chunk, eos_id=args.eos_id))

    prompt = jax.random.randint(jax.random.PRNGKey(0),
                                (args.batch, PROMPT_LEN), 0, cfg.vocab)
    out = program.run(prompt=prompt)
    stats = out["stats"]
    stall = stats["stall"]
    print(f"arch={cfg.name} batch={args.batch} generated {args.new} "
          f"tokens/slot (chunk={stats['chunk']})")
    print(f"ttft p50={stats['ttft_ms']['p50']:.1f}ms "
          f"p99={stats['ttft_ms']['p99']:.1f}ms, latency "
          f"p50={stats['latency_ms']['p50']:.1f}ms "
          f"p99={stats['latency_ms']['p99']:.1f}ms")
    print(f"session: {stall['host_syncs']} host syncs, "
          f"stall={stall['stall_pct']:.1f}% "
          f"(dispatch gap {stall['dispatch_gap_s'] * 1e3:.1f}ms over "
          f"{stall['wall_s'] * 1e3:.0f}ms)")
    if "finished_slots" in stats:
        print(f"finished at eos: {stats['finished_slots']}/{args.batch}, "
              f"emitted={stats['emitted_per_slot']}")
    print("sample:", out["tokens"][0][:16].tolist())


if __name__ == "__main__":
    main()

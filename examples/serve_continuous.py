"""Continuous serving: Poisson request arrivals through a ServeSession.

Requests with mixed prompt/output lengths arrive on a Poisson clock and
flow through a fixed slot pool: the scheduler admits each one into the
first recycled slot (per-slot prompt prefill and position reset happen
inside the compiled chunk), so short requests finish and free their slot
while long ones keep decoding — no slot waits for a batch to drain. For
one batch submitted at once, see `examples/serve_batched.py` (the same
session's one-shot `run()`).

Prints per-request TTFT/latency as requests complete, then the session
stats: slot occupancy (the MemPool PE-utilization analogue), tokens/s,
and the StallClock ledger.

    PYTHONPATH=src python examples/serve_continuous.py --slots 4 --requests 12

`--groups N` (N > 1) shards the session across N serving groups
(`ShardedServeSessionProgram`): each group owns a full slot pool on its
own device slice and a two-level scheduler places arrivals — run it
under `XLA_FLAGS=--xla_force_host_platform_device_count=8` to give every
group its own host device. The drive loop is unchanged: the sharded
session speaks the same submit/poll/stats API.
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.cluster import (Cluster, ServeSessionProgram,
                           ShardedServeSessionProgram)
from repro.runtime.compile_cache import use_persistent_cache


def main():
    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--slots", type=int, default=4,
                    help="slot-pool size (per group when --groups > 1)")
    ap.add_argument("--groups", type=int, default=1,
                    help="serving groups; > 1 shards the session")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="mean request arrivals per second (Poisson)")
    ap.add_argument("--chunk", type=int, default=4,
                    help="decode steps per host sync")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cluster = Cluster(args.arch + "-smoke")
    cfg = cluster.arch
    common = dict(slots=args.slots, max_seq=64, max_prompt=8,
                  chunk=args.chunk)
    program = cluster.compile(
        ShardedServeSessionProgram(groups=args.groups, **common)
        if args.groups > 1 else ServeSessionProgram(**common))
    session = program.open()

    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    prompts = [rng.integers(0, cfg.vocab, size=rng.integers(1, 9))
               .astype(np.int32) for _ in range(args.requests)]
    out_lens = rng.choice([8, 12, 16, 24, 32, 48], size=args.requests)

    shard = f" groups={args.groups}" if args.groups > 1 else ""
    print(f"arch={cfg.name} slots={args.slots} chunk={args.chunk}{shard} — "
          f"{args.requests} requests, ~{args.rate}/s Poisson arrivals, "
          f"prompts 1-8, outputs {sorted(set(out_lens.tolist()))}")
    t0 = time.perf_counter()
    next_up = 0
    while next_up < args.requests or session.busy:
        now = time.perf_counter() - t0
        while next_up < args.requests and arrivals[next_up] <= now:
            session.submit(prompts[next_up], int(out_lens[next_up]))
            next_up += 1
        events = session.poll()
        for handle, _toks, done in events:
            if done:
                where = (f" [g{handle.group}]"
                         if handle.group is not None else "")
                print(f"  req {handle.id}{where}: "
                      f"{handle.tokens.size} tokens, "
                      f"ttft {handle.ttft_s * 1e3:.0f}ms, "
                      f"latency {handle.latency_s * 1e3:.0f}ms")
        if not events and next_up < args.requests:
            time.sleep(min(0.005, max(arrivals[next_up] - now, 0.0)))

    wall = time.perf_counter() - t0
    st = session.stats()
    stall = st["stall"]
    print(f"done: {st['requests_done']} requests, "
          f"{st['emitted_total']} tokens in {wall:.2f} s "
          f"({st['emitted_total'] / wall:.1f} tok/s wall clock)")
    print(f"slot occupancy {st['occupancy_pct']:.0f}%  "
          f"ttft p50={st['ttft_ms']['p50']:.0f}ms "
          f"p99={st['ttft_ms']['p99']:.0f}ms  "
          f"latency p99={st['latency_ms']['p99']:.0f}ms")
    print(f"engine: {stall['host_syncs']} host syncs, "
          f"stall={stall['stall_pct']:.1f}%, queue peak {st['queue_peak']}")
    if args.groups > 1:
        placed = st["placement"]["placed"]
        print(f"placement: {placed} per group, "
              f"locality rate {st['placement']['locality_rate']:.0%}, "
              f"quarantined {st['placement']['quarantined_groups']}")


if __name__ == "__main__":
    main()

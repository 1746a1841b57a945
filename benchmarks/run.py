"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows, exactly one section per paper
artifact (Table 1, Fig. 4, 5, 13, 14, 15, 16). Modules degrade gracefully
when optional inputs (dry-run results) are absent.

A thin wrapper over the Cluster façade: the CLI builds a kernel-only
`repro.cluster.Cluster`, scopes the requested `KernelPolicy` on it, and
compiles a `BenchProgram` — every section runs under that policy, and the
emitted JSON records the active policy (mode, overrides, and the
tune-record hit/miss counters) so every row is attributable to a policy.

Flags:
  --smoke       tiny shapes / model-only paths so every bench finishes in
                seconds — the CI smoke lane
  --json PATH   also write the rows as structured JSON (uploaded as a CI
                artifact)
  --only NAMES  comma-separated subset of sections
  --repeat N    run each section N times and report the per-row median
                us_per_call (derived fields from the first run)
  --policy MODE kernel policy mode the sweep runs under (default "tuned")
  --tune-db P   persistent TuneDB path: timed tune races warm-start from it
                and write back to it (default: the REPRO_TUNE_DB env var;
                unset means no persistence). A warm DB makes the second
                run race-free — the `# tune:` summary line shows
                hits/misses/races/warm-start counts either way.

Whenever the table1 section runs, its rows are also persisted to
`BENCH_table1.json` at the repo root — the perf-trajectory record the CI
smoke job refreshes on every run — and a `fused-vs-unfused:` summary line
is printed for the fused kernel path. The decode (K=1 vs K=16 engine) and
serve (continuous vs static batching) rows and their `decode-throughput:`
/ `serve-continuous:` summary lines ride along in the same record.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.cluster import BenchProgram, Cluster  # noqa: E402
from repro.cluster.policy import MODES  # noqa: E402
from repro.runtime.compile_cache import use_persistent_cache  # noqa: E402

from benchmarks import (bench_decode_throughput,  # noqa: E402
                        bench_fig4_interconnect, bench_fig5_hybrid,
                        bench_fig13_scaling, bench_fig14_breakdown,
                        bench_fig15_double_buffer, bench_fig16_energy,
                        bench_serve_continuous, bench_table1_kernels)

MODULES = [
    ("table1", bench_table1_kernels),
    ("decode", bench_decode_throughput),
    ("serve", bench_serve_continuous),
    ("fig4", bench_fig4_interconnect),
    ("fig5", bench_fig5_hybrid),
    ("fig13", bench_fig13_scaling),
    ("fig14", bench_fig14_breakdown),
    ("fig15", bench_fig15_double_buffer),
    ("fig16", bench_fig16_energy),
]


def _fused_comparison_line(rows: list[dict]) -> str | None:
    """One-line fused-vs-unfused summary from the table1_fused rows."""
    parts = []
    for r in rows:
        if not r["name"].startswith("table1_fused/"):
            continue
        kv = dict(p.split("=", 1) for p in r["derived"].split(";"))
        parts.append(
            f"{r['name'].removeprefix('table1_fused/')}"
            f" {r['us_per_call']:.0f}us (unfused {float(kv['unfused_us']):.0f}us,"
            f" bytes x{kv['bytes_reduction']})")
    if not parts:
        return None
    return "# fused-vs-unfused: " + " | ".join(parts)


def _decode_rows(results: dict) -> list[dict]:
    section = results["sections"].get("decode")
    if not section or section["status"] != "ok":
        return []
    return section["rows"]


def _decode_comparison_line(rows: list[dict]) -> str | None:
    """K=1 (per-token loop) vs K=16 (scan-compiled engine) summary."""
    by_k = {}
    for r in rows:
        kv = dict(p.split("=", 1) for p in r["derived"].split(";"))
        by_k[r["name"].removeprefix("decode/")] = (r["us_per_call"], kv)
    if "K1" not in by_k or "K16" not in by_k:
        return None
    (us1, kv1), (us16, kv16) = by_k["K1"], by_k["K16"]
    return (f"# decode-throughput: K16 {float(kv16['tokens_per_s']):.1f} tok/s"
            f" (stall {float(kv16['stall_pct']):.1f}%,"
            f" {kv16['host_syncs']} syncs) vs"
            f" K1 {float(kv1['tokens_per_s']):.1f} tok/s"
            f" (stall {float(kv1['stall_pct']):.1f}%,"
            f" {kv1['host_syncs']} syncs) —"
            f" {us1 / max(us16, 1e-9):.2f}x per-token speedup")


def _serve_rows(results: dict) -> list[dict]:
    section = results["sections"].get("serve")
    if not section or section["status"] != "ok":
        return []
    return section["rows"]


def _serve_comparison_line(rows: list[dict]) -> str | None:
    """Continuous vs static batching summary from the serve section."""
    by_name = {}
    for r in rows:
        kv = dict(p.split("=", 1) for p in r["derived"].split(";"))
        by_name[r["name"].removeprefix("serve/")] = kv
    if "continuous" not in by_name or "static" not in by_name:
        return None
    c, s = by_name["continuous"], by_name["static"]
    tps_c, tps_s = float(c["tokens_per_s"]), float(s["tokens_per_s"])
    occ_c, occ_s = float(c["occupancy_pct"]), float(s["occupancy_pct"])
    return (f"# serve-continuous: {tps_c:.1f} tok/s, occ {occ_c:.1f}% vs"
            f" static {tps_s:.1f} tok/s, occ {occ_s:.1f}% —"
            f" {tps_c / max(tps_s, 1e-9):.2f}x tok/s,"
            f" {occ_c / max(occ_s, 1e-9):.2f}x occupancy;"
            f" p99 {float(c['p99_ms']):.0f}ms vs {float(s['p99_ms']):.0f}ms"
            f" ({c['requests']} reqs, {c['slots']} slots)")


def _persist_table1(results: dict, repeat: int) -> Path | None:
    section = results["sections"].get("table1")
    if not section or section["status"] != "ok":
        return None
    path = Path(__file__).resolve().parents[1] / "BENCH_table1.json"
    record = {"smoke": results["smoke"], "timestamp": results["timestamp"],
              "repeat": repeat, "policy": results["policy"],
              "rows": section["rows"]}
    if "tuning" in results:
        record["tuning"] = results["tuning"]
    decode = _decode_rows(results)
    if decode:
        # the K=1 vs K=16 engine trajectory rides with the kernel table
        record["decode"] = [r for r in decode
                            if r["name"] in ("decode/K1", "decode/K16")]
        line = _decode_comparison_line(decode)
        if line:
            record["decode_summary"] = line.removeprefix(
                "# decode-throughput: ")
    serve = _serve_rows(results)
    if serve:
        # continuous vs static batching rows ride along too
        record["serve_continuous"] = serve
        line = _serve_comparison_line(serve)
        if line:
            record["serve_summary"] = line.removeprefix(
                "# serve-continuous: ")
    path.write_text(json.dumps(record, indent=2))
    return path


def main(argv: list[str] | None = None) -> None:
    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes; every section in seconds")
    ap.add_argument("--json", default=None,
                    help="write structured results to this path")
    ap.add_argument("--only", default=None,
                    help="comma-separated section subset (e.g. table1,fig4)")
    ap.add_argument("--repeat", type=int, default=1,
                    help="median-of-N timing: run each section N times")
    ap.add_argument("--policy", default="tuned", choices=MODES,
                    help="kernel policy mode the sweep runs under")
    ap.add_argument("--tune-db", default=None,
                    help="TuneDB path (default: REPRO_TUNE_DB env)")
    args = ap.parse_args(argv)
    if args.repeat < 1:
        ap.error("--repeat must be >= 1")

    only = tuple(args.only.split(",")) if args.only else ()
    if only:
        unknown = set(only) - {name for name, _ in MODULES}
        if unknown:
            ap.error(f"unknown section(s) {sorted(unknown)}; "
                     f"available: {[n for n, _ in MODULES]}")

    # kernel-only cluster; a tune DB (flag or env) warm-starts KERNEL_TUNES
    cluster = Cluster(policy=args.policy, tune_db=args.tune_db)
    program = cluster.compile(BenchProgram(sections=only, smoke=args.smoke,
                                           repeat=args.repeat))
    print("name,us_per_call,derived")
    results = program.run(MODULES)
    results["timestamp"] = time.time()
    stats = cluster._policy.stats
    tune_line = (f"# tune: hits={stats.get('tune_hits', 0)}"
                 f" misses={stats.get('tune_misses', 0)}"
                 f" races={stats.get('tune_races', 0)}"
                 f" warm={cluster.tune_db_warm}")
    results["tuning"] = {"hits": stats.get("tune_hits", 0),
                         "misses": stats.get("tune_misses", 0),
                         "races": stats.get("tune_races", 0),
                         "warm_started": cluster.tune_db_warm}
    if cluster.tune_db is not None:
        db = cluster.tune_db
        results["tuning"]["tunedb"] = db.describe()
        tune_line += (f" db={db.path} entries={len(db)}"
                      f"{' (frozen)' if db.frozen else ''}")
    print(tune_line)
    failed = results.pop("failed")
    decode_rows = _decode_rows(results)
    if decode_rows:
        dec_line = _decode_comparison_line(decode_rows)
        if dec_line:
            print(dec_line)
    serve_rows = _serve_rows(results)
    if serve_rows:
        srv_line = _serve_comparison_line(serve_rows)
        if srv_line:
            print(srv_line)
    table1 = results["sections"].get("table1")
    if table1 and table1["status"] == "ok":
        cmp_line = _fused_comparison_line(table1["rows"])
        if cmp_line:
            print(cmp_line)
        persisted = _persist_table1(results, args.repeat)
        if persisted:
            print(f"# wrote {persisted}", file=sys.stderr)
    if args.json:
        Path(args.json).parent.mkdir(parents=True, exist_ok=True)
        Path(args.json).write_text(json.dumps(results, indent=2))
        print(f"# wrote {args.json}", file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()

"""Readings that set a cell's correctness limit, and the knee sweep that
sets its load: tools run by hand on the chip when a cell is defined, never
by the driver's runs.

    python3 bench/calibrate.py limits --workload <cell> --seeds 1,2,... \
        --seconds 51
    python3 bench/calibrate.py sweep --workload <cell> --seed <n> \
        --rates 0.6,0.8,1.0 --seconds 51

`limits`: for each seed, in one process, the cell's run at its own load,
then over the same sample of finished requests the harness's own check
twice: of the program's served tokens, and of the tokens that the control
(the reference in fp8) puts first. The limit lies between the program's
largest reading and the control's smallest; the control's check has to
come out not correct.

`sweep`: one set of weights, a fresh session for each arrival rate, each
run as the cell runs (its lead-in, window and drain); per rate the cell's
end-to-end readers, the slot occupancy, and the mean time to first token
of the requests due in each half of the window (a queue that grows shows
as a later half that waits longer), to find the highest rate the cell
sustains.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
import types

import run  # noqa: F401  (puts the benchmark and the program on the path)
from harness import serve as serve_lib
from harness import spec as spec_lib

SWEEP_READERS = ("ttft_mean_ms", "ttft_p90_ms.chat", "tpot_p90_ms",
                 "output_tokens_per_s", "slot_occupancy_pct.batch")


def _run_ns(cell, win):
    s = cell.config["bench"]["serving"]
    return types.SimpleNamespace(cell=cell, cfg=cell.config,
                                 slots=s["slots"], window=win, setup_s=0.0)


def _drive(cell, server, cellfile, seed, seconds):
    traffic = run.make_traffic(cell, cellfile, seed, seconds)
    run.warm(server, traffic, cell)
    return serve_lib.drive(server, traffic, seconds=seconds,
                           drain_cap=float(cell.traffic["drain_cap_s"]))


def limits(args) -> None:
    cell = spec_lib.load_cell(args.workload, run.ROOT)
    cellfile = run.cell_file(cell)
    ref = spec_lib.reference_module(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        server = serve_lib.Server(cell, seed, ref.INIT)
        win = _drive(cell, server, cellfile, seed, args.seconds)
        server.close()
        checks = {c: run.correctness(cell, seed, win, ref,
                                     cellfile["correct"], control=c)
                  for c in (False, True)}
        print(json.dumps({
            "seed": seed,
            "metrics": run.end_to_end(cell, _run_ns(cell, win)),
            "measured": sum(r.req.measured for r in win.records),
            "program": checks[False],
            "program_correct": run.is_correct(checks[False]),
            "control": checks[True],
            "control_correct": run.is_correct(checks[True]),
            "seconds": time.perf_counter() - t0}), flush=True)


def sweep(args) -> None:
    cell = spec_lib.load_cell(args.workload, run.ROOT)
    ref = spec_lib.reference_module(cell)
    server = serve_lib.Server(cell, args.seed, ref.INIT)
    for rate in (float(r) for r in args.rates.split(",")):
        server.session.close()
        server.session = None           # the old pool is freed first
        gc.collect()
        server.session = server.program.open(params=server.params)
        cellfile = dict(run.cell_file(cell), rate_per_s=rate)
        win = _drive(cell, server, cellfile, args.seed, args.seconds)
        ns = _run_ns(cell, win)
        mid = (win.open_t + win.close_t) / 2
        halves = []
        for lo, hi in ((win.open_t, mid), (mid, win.close_t)):
            part = dataclasses.replace(win, records=[
                r for r in win.records if lo <= r.due < hi])
            halves.append(run.end_to_end(cell, _run_ns(cell, part),
                                         ["ttft_mean_ms"]))
        print(json.dumps({
            "rate": rate,
            "measured": sum(r.req.measured for r in win.records),
            "done": sum(r.done for r in win.records if r.req.measured),
            "unissued": win.unissued, "drained_s": win.end_t - win.close_t,
            "metrics": run.end_to_end(cell, ns, SWEEP_READERS),
            "ttft_mean_ms_by_half": halves}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    a = sub.add_parser("limits")
    a.add_argument("--workload", required=True)
    a.add_argument("--seeds", required=True)
    a.add_argument("--seconds", type=float, default=51.0)
    b = sub.add_parser("sweep")
    b.add_argument("--workload", required=True)
    b.add_argument("--seed", type=int, required=True)
    b.add_argument("--rates", required=True)
    b.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    run.use_compile_cache(run.ROOT)
    try:
        run.devices_for(spec_lib.load_cell(args.workload, run.ROOT), True)
    except run.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    {"limits": limits, "sweep": sweep}[args.what](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())

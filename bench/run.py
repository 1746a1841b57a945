"""Runs one benchmark cell on the chip and prints one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (`BENCHMARK.json` -> `workloads`) names a configuration and a
traffic mix; `harness/spec.py` finds their files. One process: it refuses
any device that is not a TPU, draws the weights on the device from the
seed, warms every program the cell's traffic drives, runs the traffic for
a lead-in and then the measured window, and checks what the served path
produced against the plain float32 reference once the program is freed.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from the program's counters,
from the benchmark's own spans and from a profiler trace of a few seconds
in the middle of the window.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()            # set-up is counted from here

import argparse                      # noqa: E402
import contextlib                    # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402
import tempfile                      # noqa: E402
import types                         # noqa: E402
from pathlib import Path             # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec as spec_lib   # noqa: E402
from harness import stats             # noqa: E402

TRACE_S = 4.0           # length of the traced part of the window


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program cached, so only the first run compiles."""
    import jax

    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(cell, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < cell.chips:
        raise NoDevice(f"{cell.name} needs {cell.chips} chips, JAX found "
                       f"{len(devs)}")
    return devs


class Tracer:
    """A profiler trace of part of the window, with the benchmark's own
    `bench.window` span marking the traced interval."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._span = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def stop(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self):
        import shutil

        from harness import trace
        try:
            files = sorted(Path(self.dir).rglob("*.xplane.pb"))
            if not files:
                return None
            return trace.summarize(trace.load(files[-1]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class CompileLog:
    """When JAX traced or compiled a program, from its monitoring events:
    a warm run does neither inside the window."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.times: list[tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.times.append((time.perf_counter(), event.rsplit("/", 1)[-1]))

    def between(self, lo: float, hi: float) -> dict:
        out: dict[str, int] = {}
        for t, e in self.times:
            if lo <= t <= hi:
                out[e] = out.get(e, 0) + 1
        return out


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def warm(server, traffic, cell) -> None:
    """Run every program this cell's traffic drives, and no others."""
    import numpy as np

    from harness import traffic as traffic_lib

    s = cell.config["bench"]["serving"]
    lo, hi = traffic_lib.prompt_range(cell.traffic, s["max_seq"])
    server.warm_page_programs(traffic_lib.page_counts((lo, hi),
                                                      s["page_size"]))
    if traffic.system_prompts:
        # a deployment has its system prompts published; serving one of
        # them again is a full-prefix hit, whose copy-on-write fork is a
        # program of its own
        server.serve_now(traffic.system_prompts, 1)
        server.serve_now(traffic.system_prompts[:1], 1)
    else:
        rng = np.random.default_rng(0)
        server.serve_now([rng.integers(0, cell.config["vocab_size"], lo,
                                       dtype=np.int32)], 2)


def end_to_end(cell, run, names=None) -> dict:
    """The cell's end-to-end metrics (or those `names`), each from its
    reader `bench/metrics/<name>.py`."""
    names = names or [m["name"] for m in cell.end_to_end]
    out = {}
    for name in names:
        value = spec_lib.metric_reader(cell, name)(run)
        if value is not None:
            out[name] = value
    return out


def per_layer(cell, run) -> dict:
    return end_to_end(cell, run, [m["name"] for m in cell.per_layer])


def check_sample(win, seed: int, k: int) -> list:
    """The requests the check compares: `k` drawn from the seed among the
    measured ones that finished, the one with the most served tokens
    always among them."""
    import numpy as np

    done = [r for r in win.records if r.done and r.n >= 1 and r.req.measured]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.req.prompt.size + r.n, r.n))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def correctness(cell, seed: int, win, ref, limits: dict,
                control: bool = False) -> dict:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over a sample of finished requests. With
    `control`, the gaps are those of the tokens that the reference in the
    next precision down (the control) puts first at the same positions."""
    import numpy as np

    from harness import weights

    sample = check_sample(win, seed, limits["sample"])
    # a measured request that failed, was refused, was never answered, or
    # was never due before the run stopped
    failed = win.unissued + sum(r.failed or (r.req.measured and not r.done
                                             and win.drains)
                                for r in win.records)
    gap = None                  # nothing finished: nothing to compare
    tokens = 0
    if sample:
        cfg = cell.config
        draw_layer = weights.layer_drawer(ref.layer_shapes(cfg), ref.INIT)
        draw_top = weights.layer_drawer(ref.top_shapes(cfg), ref.INIT)
        gaps = ref.logit_gaps(
            cfg, draw_layer, draw_top, seed,
            [(r.req.prompt, np.asarray(r.tokens, np.int32)) for r in sample],
            length=cfg["bench"]["serving"]["max_seq"], control=control)
        gap = max(float(g.max()) for g in gaps)
        tokens = sum(int(g.size) for g in gaps)
    return {
        "max_logit_gap": {"value": gap, "limit": limits["max_logit_gap"]},
        "failed_requests": {"value": failed, "limit": 0},
        "checked_tokens": {"value": tokens,
                           "limit": limits["min_checked_tokens"]},
    }


def is_correct(check: dict) -> bool:
    gap = check["max_logit_gap"]
    return (gap["value"] is not None and gap["value"] <= gap["limit"]
            and check["failed_requests"]["value"] == 0
            and check["checked_tokens"]["value"]
            >= check["checked_tokens"]["limit"])


def cell_file(cell) -> dict:
    """The cell's load level and limits, `bench/cells/<cell>.json`."""
    return json.loads((cell.bench_dir / "cells" / f"{cell.name}.json")
                      .read_text(encoding="utf-8"))


def make_traffic(cell, cellfile: dict, seed: int, seconds: float):
    from harness import traffic as traffic_lib

    s = cell.config["bench"]["serving"]
    return traffic_lib.load(cell.traffic, cellfile,
                            vocab=cell.config["vocab_size"], seed=seed,
                            max_context=s["max_seq"], slots=s["slots"],
                            lead_in=float(cell.traffic["lead_in_s"]),
                            seconds=seconds)


def memory_peak(devs) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True,
         on_server=None) -> int:
    """`require_tpu=False` and `on_server` (a hook given the server once it
    is warm) serve the tests, which run the rest of a run on the CPU."""
    args = parse(argv)
    cell = spec_lib.load_cell(args.workload, root)
    cellfile = cell_file(cell)
    use_compile_cache(root)
    try:
        devs = devices_for(cell, require_tpu)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    import jax

    from harness import peaks as peaks_lib
    from harness import serve as serve_lib

    peak = (peaks_lib.peak_for(devs[0].device_kind)
            if devs[0].platform == "tpu" else None)
    ref = spec_lib.reference_module(cell)
    s = cell.config["bench"]["serving"]
    server = serve_lib.Server(cell, args.seed, ref.INIT)
    traffic = make_traffic(cell, cellfile, args.seed, args.seconds)
    warm(server, traffic, cell)
    if on_server is not None:
        on_server(server)
    tracer = Tracer() if args.trace else None
    compiles = CompileLog()
    win = serve_lib.drive(
        server, traffic, seconds=args.seconds,
        drain_cap=float(cell.traffic.get("drain_cap_s", 60.0)),
        tracer=tracer, trace_s=min(TRACE_S, args.seconds),
        span=_span if args.trace else contextlib.nullcontext)
    setup_s = win.open_t - T0
    peak_bytes = memory_peak(devs) if devs[0].platform == "tpu" else 0
    summary = tracer.summary() if tracer is not None else None
    server.close()
    check = correctness(cell, args.seed, win, ref, cellfile["correct"])
    correct = is_correct(check)
    attempted = [r for r in win.records if r.req.measured]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    run = types.SimpleNamespace(
        cell=cell, cfg=cell.config, slots=s["slots"], window=win,
        setup_s=setup_s, trace=summary, peak=peak,
        counts=spec_lib.counts_module(cell),
        probe_totals=serve_lib.probe_totals)
    if args.trace:
        values = per_layer(cell, run)
        if summary is not None:
            device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        values = end_to_end(cell, run)
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result = {
        "correct": bool(correct),
        "attempted": len(attempted),
        "failed": check["failed_requests"]["value"],
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items()},
        "device": device,
    }
    if args.trace and summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
    result["check"] = check
    late = sorted(win.late_s) or [0.0]
    print(f"bench: {cell.name} seed {args.seed}: setup {setup_s:.3f} s, "
          f"window {win.close_t - win.open_t:.3f} s, drained "
          f"{win.end_t - win.close_t:.3f} s, {len(attempted)} measured "
          f"requests, {win.unissued} never due before the end, "
          f"generator late p50 {stats.percentile(late, 50) * 1e3:.2f} ms "
          f"max {late[-1] * 1e3:.2f} ms; traced or compiled in the window: "
          f"{compiles.between(win.open_t, win.close_t) or 'nothing'}",
          file=sys.stderr)
    for name, c in check.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())

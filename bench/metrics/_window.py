"""Arithmetic the metric readers share: the measured requests' times,
differences of the program's counters across the window, and the chunks
the probe saw."""

from harness import stats

CHUNK_PROGRAM = "jit_chunk_fn"      # the session cell's program in a trace


def measured(run):
    return [r for r in run.window.records if r.req.measured]


def ttft_s(run):
    """Per measured request, from when it was due to its first token; one
    with no first token by the end counts at its age then."""
    end = run.window.end_t
    return [(r.first if r.first is not None else end) - r.due
            for r in measured(run)]


def tpot_s(run):
    """Per measured request of two tokens or more, (last token - first
    token) / (tokens - 1)."""
    return [(r.last - r.first) / (r.n - 1) for r in measured(run) if r.n >= 2]


def percentile_ms(xs, q):
    return stats.percentile(xs, q) * 1e3 if xs else None


def mean_ms(xs):
    return 1e3 * sum(xs) / len(xs) if xs else None


def delta(run, key):
    a, b = run.window.before.get(key), run.window.after.get(key)
    return None if a is None or b is None else b - a


def first_tokens(run) -> int:
    w = run.window
    return sum(1 for r in w.records
               if r.first is not None and w.open_t < r.first <= w.close_t)


def prompt_steps(run):
    """Busy slot-steps that consumed a prompt token: those that emitted
    nothing, and the step of each first token (which consumed the last
    prompt token)."""
    busy, emitted = delta(run, "busy"), delta(run, "emitted")
    if busy is None or emitted is None:
        return None
    return busy - emitted + first_tokens(run)


def window_probe(run):
    w = run.window
    if w.probe is None or w.before.get("probe") is None:
        return None
    return run.probe_totals(w.probe, w.before["probe"], w.after["probe"])


def traced_probe(run):
    w = run.window
    if w.probe is None or w.traced is None:
        return None
    return run.probe_totals(w.probe, w.traced[2], w.traced[3])


def step_ms(run):
    walls = run.window.chunk_walls
    steps = sum(n for _, n in walls)
    return 1e3 * sum(dt for dt, _ in walls) / steps if steps else None


def step_mfu(run):
    tot = window_probe(run)
    if tot is None or run.peak is None or not tot["slot_steps"]:
        return None
    w = run.window
    flops = run.counts.flops_needed(run.cfg, tot["slot_steps"],
                                    tot["attended"])
    return 100.0 * flops / (w.close_t - w.open_t) / run.peak.bf16_flops


def step_roofline(run):
    """The least time the traced steps could take on this chip (bytes over
    HBM bandwidth or operations over peak, whichever is larger) over the
    device time of the session's chunk programs in the trace. A poll
    dispatches its chunk and waits for it, and the trace starts and stops
    between polls, so each chunk the probe counted in the trace ran whole
    inside it: the trace has to hold as many runs as the probe has rows."""
    from harness import trace

    tot = traced_probe(run)
    if tot is None or run.peak is None or run.trace is None \
            or not tot["steps"]:
        return None
    t, runs = trace.module_time(run.trace, CHUNK_PROGRAM)
    rows = run.window.traced[3] - run.window.traced[2]
    if runs != rows * run.trace.n_devices:
        raise ValueError(f"the trace holds {runs} runs of {CHUNK_PROGRAM} "
                         f"where the probe dispatched {rows} chunks")
    c = run.counts
    nbytes = c.bytes_needed(run.cfg, tot["steps"], tot["slot_steps"],
                            tot["attended"])
    flops = c.flops_needed(run.cfg, tot["slot_steps"], tot["attended"])
    t_min = max(nbytes / run.peak.hbm_bytes_per_s,
                flops / run.peak.bf16_flops)
    return 100.0 * t_min / t


def idle_pct(run):
    return None if run.trace is None else run.trace.idle_pct

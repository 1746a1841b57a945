"""Reduction of a profiler trace to device metrics, kept with the
benchmark so that every PR computes them the same way.

The trace is the XSpace the JAX profiler writes (`*.xplane.pb`), read with
`jax.profiler.ProfileData`. Device planes are named ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per operation run on the device,
their ``XLA Modules`` line one event per program run. The benchmark's own
host spans (``bench.*``, written with `jax.profiler.TraceAnnotation`) sit
on the ``/host:CPU`` plane, on the same clock.

- busy: the union of the operation intervals inside the traced window;
- idle share: 1 - busy / window;
- top ops: device time by operation name, control-flow ops left out;
- idle gaps: the intervals of the window in which no operation ran,
  split over the host spans they overlap (``other`` where none does),
  summed per span name.
"""

from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict

OPS_LINE = "XLA Ops"
# control-flow ops hold the ops of their bodies on the same line: they
# count towards busy time, not as ops of their own
CONTAINERS = ("while", "conditional", "cond", "call")
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def from_text(text: str):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


def _events(plane, line_name: str) -> list[tuple[str, float, float]]:
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return []


def op_name(event_name: str) -> str:
    """`fusion.12` from an XLA op event's `%fusion.12 = bf16[...] ...`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def device_planes(pd) -> list:
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")]


def host_spans(pd) -> list[tuple[str, float, float]]:
    plane = pd.find_plane_with_name(HOST_PLANE)
    if plane is None:
        return []
    out = []
    for line in plane.lines:
        for e in line.events:
            if e.name.startswith(SPAN_PREFIX):
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap: tuple[float, float], spans, starts) -> dict[str, float]:
    """The gap's length split over the host spans it overlaps; the rest is
    ``other``. `spans` are the benchmark's spans but the window's, which
    do not nest, sorted by start (`starts`)."""
    out: dict[str, float] = defaultdict(float)
    rest = gap[1] - gap[0]
    k = bisect.bisect_left(starts, gap[1]) - 1
    while k >= 0 and spans[k][2] > gap[0]:
        n, a, b = spans[k]
        ov = min(b, gap[1]) - max(a, gap[0])
        out[n] += ov
        rest -= ov
        k -= 1
    if rest > 0:
        out["other"] += rest
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float               # mean over the device planes
    module_busy_s: dict         # device time per program name (union of
    #                             its ops), summed over planes
    module_runs: dict           # runs per program name, summed over planes
    top_ops: list               # [[name, seconds]], longest total first
    idle_gaps: list             # [[host span, seconds]], longest first
    n_devices: int

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def window_bounds(pd) -> tuple[float, float]:
    spans = [s for s in host_spans(pd) if s[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0][1], spans[0][2]


def summarize(pd, top: int = 10) -> Summary | None:
    """None where the trace holds no TPU plane (a run on the CPU)."""
    planes = device_planes(pd)
    if not planes:
        return None
    lo, hi = window_bounds(pd)
    spans = sorted((sp for sp in host_spans(pd) if sp[0] != WINDOW_SPAN),
                   key=lambda sp: sp[1])
    span_starts = [a for _, a, _ in spans]
    busy_total = 0.0
    by_op: dict[str, float] = defaultdict(float)
    by_gap: dict[str, float] = defaultdict(float)
    by_module: dict[str, float] = defaultdict(float)
    runs: dict[str, int] = defaultdict(int)
    for plane in planes:
        ops = sorted(_events(plane, OPS_LINE), key=lambda e: e[1])
        starts = [a for _, a, _ in ops]
        busy = union([(a, b) for _, a, b in ops], lo, hi)
        busy_total += sum(b - a for a, b in busy)
        for name, a, b in ops:
            d = min(b, hi) - max(a, lo)
            short = op_name(name)
            if d > 0 and short.split(".")[0] not in CONTAINERS:
                by_op[short] += d
        for g in gaps(busy, lo, hi):
            for name, t in attribute(g, spans, span_starts).items():
                by_gap[name] += t
        for name, a, b in _events(plane, MODULES_LINE):
            runs[name] += b > lo and a < hi
            i, j = bisect.bisect_left(starts, a), bisect.bisect_left(starts, b)
            inside = union([(x, y) for _, x, y in ops[i:j]], max(a, lo),
                           min(b, hi))
            by_module[name] += sum(y - x for x, y in inside)
    n = len(planes)
    ns = 1e-9
    rank = lambda d: [[k, v * ns] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return Summary(window_s=(hi - lo) * ns, busy_s=busy_total / n * ns,
                   module_busy_s={k: v * ns for k, v in by_module.items()},
                   module_runs=dict(runs),
                   top_ops=rank(by_op), idle_gaps=rank(by_gap), n_devices=n)


def module_time(summary: Summary, fragment: str) -> tuple[float, int]:
    """Device seconds and runs of the programs whose name contains
    `fragment`."""
    return (sum(v for k, v in summary.module_busy_s.items() if fragment in k),
            sum(n for k, n in summary.module_runs.items() if fragment in k))


def to_text_proto(planes: list[tuple[str, list[tuple[str, list]]]]) -> str:
    """An XSpace text proto from [(plane, [(line, [(name, start_ns,
    duration_ns)])])]: how the recorded fixture in `tests/fixtures` is
    written."""
    out = []
    for pid, (pname, lines) in enumerate(planes, 1):
        meta: dict[str, int] = {}
        body = []
        for lid, (lname, events) in enumerate(lines, 1):
            evs = []
            for name, start, dur in events:
                mid = meta.setdefault(name, len(meta) + 1)
                evs.append(f"    events {{ metadata_id: {mid} offset_ps: "
                           f"{int(round(start * 1000))} duration_ps: "
                           f"{int(round(dur * 1000))} }}")
            body.append(f"  lines {{ id: {lid} name: {_q(lname)} "
                        f"timestamp_ns: 0\n" + "\n".join(evs) + "\n  }")
        md = [f"  event_metadata {{ key: {i} value {{ id: {i} name: "
              f"{_q(n)} }} }}" for n, i in meta.items()]
        out.append(f"planes {{\n  id: {pid}\n  name: {_q(pname)}\n"
                   + "\n".join(body + md) + "\n}")
    return "\n".join(out) + "\n"


def _q(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'

"""Spans and counters of the serve loop, on the host clock and in the
profiler's trace.

One `Recorder` per process (`recorder()`). Every `ServeSession` writes into
it through its own `SessionTelemetry` handle, tagged with a session id and,
under `ShardedServeSession`, the serving group's id:

- **spans** of each `poll()`: ``serve.poll`` and its phases ``serve.retire``,
  ``serve.admit``, ``serve.refill``, ``serve.chunk``, ``serve.harvest``,
  ``serve.scrub`` and ``serve.journal`` (durable sessions only), with
  ``serve.page_read`` nested in whichever phase reads KV pages back to the
  host. Each carries the index of the poll's chunk. Phases of one poll do
  not overlap; a span's self time is its length less its children's.
  ``serve.chunk`` carries the page gauges of a paged session;
- **request records**, one per request: when it was submitted, first
  granted a slot and gave its first token;
- **compiles**: the time of every program the process compiles (or loads
  from the persistent compilation cache), from JAX's monitoring events.

Times are `time.perf_counter()`. Every span is also opened as a
`jax.profiler.TraceAnnotation` (``serve.chunk`` as a `StepTraceAnnotation`
whose ``step_num`` is the chunk index), so under a profiler it lands on the
``/host:CPU`` plane, on the device ops' clock. Span names start with
``serve.``; ``bench.`` belongs to the benchmark's own spans.

Memory stays flat: spans, request records and compile times are kept in
rings of fixed size, and what is kept outlives the sessions that wrote it.
The recorder has no switch: with no profiler running, a poll costs it a few
tens of microseconds.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import NamedTuple

import jax

SPANS = 1 << 15             # spans kept, all sessions together
REQUESTS = 1 << 14          # request records kept
SESSIONS = 1 << 10          # session descriptions kept
COMPILES = 1 << 12          # compile times kept
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    start: float
    end: float
    session: int
    group: int | None
    chunk: int              # index of the chunk the poll runs
    depth: int              # 0: serve.poll; 1: its phases; 2: page reads
    attrs: dict | None


@dataclasses.dataclass
class RequestRecord:
    session: int
    group: int | None
    rid: int
    submitted_at: float
    admitted_at: float | None = None    # first grant of a slot
    first_token_at: float | None = None


class Recorder:
    """Rings of spans, request records and compile times (see the module
    docstring). Appends take a lock: the groups of a sharded session poll
    on threads of their own."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: deque[Span] = deque(maxlen=SPANS)
        self._requests: dict[tuple[int, int], RequestRecord] = {}
        self._sessions: dict[int, dict] = {}
        self._compile_times: deque[float] = deque(maxlen=COMPILES)
        self._next_session = 0
        self.spans_dropped = 0          # spans pushed out of the ring
        self.compiles = 0

    # -- writers ------------------------------------------------------------
    def session(self, *, group: int | None = None,
                kv_pages: int | None = None) -> "SessionTelemetry":
        """A handle for one session. `kv_pages` is its KV pool's usable
        pages (all but the trash page), None for an unpaged session."""
        with self._lock:
            sid = self._next_session
            self._next_session += 1
            self._sessions[sid] = {"group": group, "kv_pages": kv_pages}
            _bound(self._sessions, SESSIONS)
        return SessionTelemetry(self, sid, group)

    def _add(self, span: Span) -> None:
        with self._lock:
            self.spans_dropped += len(self._spans) == self._spans.maxlen
            self._spans.append(span)

    def _note(self, key: tuple[int, int], group: int | None, req) -> None:
        with self._lock:
            r = self._requests.get(key)
            if r is None:
                r = self._requests[key] = RequestRecord(
                    key[0], group, req.rid, req.submitted_at)
                _bound(self._requests, REQUESTS)
            r.admitted_at = req.admitted_at
            r.first_token_at = req.first_token_at

    def on_compile(self, event: str, duration: float, **_) -> None:
        """`jax.monitoring` duration listener: one call per program built."""
        if event == COMPILE_EVENT:
            with self._lock:
                self.compiles += 1
                self._compile_times.append(time.perf_counter())

    # -- readers ------------------------------------------------------------
    def spans(self, lo: float = float("-inf"),
              hi: float = float("inf")) -> list[Span]:
        """Kept spans that start in [lo, hi], in the order they ended."""
        with self._lock:
            return [s for s in self._spans if lo <= s.start <= hi]

    def requests(self) -> list[RequestRecord]:
        """Kept request records, oldest submission first (copies)."""
        with self._lock:
            return [dataclasses.replace(r) for r in self._requests.values()]

    def session_info(self, sid: int) -> dict | None:
        with self._lock:
            info = self._sessions.get(sid)
            return None if info is None else dict(info)

    def compile_times(self) -> list[float]:
        with self._lock:
            return list(self._compile_times)


def _bound(d: dict, n: int) -> None:
    while len(d) > n:
        del d[next(iter(d))]        # oldest insertion first


class _SpanScope:
    """One span: a `TraceAnnotation` plus a record on exit."""

    __slots__ = ("tel", "name", "attrs", "start", "depth", "ann")

    def __init__(self, tel: "SessionTelemetry", name: str, attrs: dict):
        self.tel, self.name, self.attrs = tel, name, attrs

    def __enter__(self) -> "_SpanScope":
        tel = self.tel
        self.ann = jax.profiler.TraceAnnotation(self.name, chunk=tel.chunk)
        self.ann.__enter__()
        self.depth = tel.depth
        tel.depth += 1
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        end = time.perf_counter()
        tel = self.tel
        tel.depth -= 1
        self.ann.__exit__(*exc)
        tel.rec._add(Span(self.name, self.start, end, tel.sid, tel.group,
                          tel.chunk, self.depth, self.attrs or None))
        return False


class SessionTelemetry:
    """One session's writer. `chunk` is set by the poll to the index of the
    chunk it runs; spans opened inside it carry that index."""

    def __init__(self, rec: Recorder, sid: int, group: int | None):
        self.rec, self.sid, self.group = rec, sid, group
        self.chunk = 0
        self.depth = 0

    def span(self, name: str, **attrs) -> _SpanScope:
        """`with tel.span("serve.admit"): ...`; `attrs` may be filled in
        inside the block (``sp.attrs``)."""
        return _SpanScope(self, name, dict(attrs))

    def chunk_annotation(self):
        """The profiler's side of ``serve.chunk``, opened around dispatch
        and sync; the record comes from `record` with the stall clock's
        own stamps."""
        return jax.profiler.StepTraceAnnotation("serve.chunk",
                                                step_num=self.chunk)

    def record(self, name: str, start: float, end: float, **attrs) -> None:
        """A span whose ends were stamped elsewhere (the chunk's)."""
        self.rec._add(Span(name, start, end, self.sid, self.group,
                           self.chunk, self.depth, attrs or None))

    def request(self, req) -> None:
        """Copy `req`'s stage stamps into its record (made at the first
        call)."""
        self.rec._note((self.sid, req.rid), self.group, req)


_RECORDER: Recorder | None = None
_RECORDER_LOCK = threading.Lock()


def recorder() -> Recorder:
    """The process's recorder; the first call also starts counting
    compiles."""
    global _RECORDER
    with _RECORDER_LOCK:
        if _RECORDER is None:
            _RECORDER = Recorder()
            jax.monitoring.register_event_duration_secs_listener(
                _RECORDER.on_compile)
        return _RECORDER

"""Drives the served path that users run:

    Cluster -> cluster.compile(ServeSessionProgram(paged=True,
    prefix_cache=True, preempt=False, ...)) under cluster.policy("tuned")
    -> ServeSession.submit / poll

with every other session knob at its default, and records when each
request was due, when it was submitted, and when its tokens came back.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import numpy as np

from harness import traffic as traffic_lib
from harness import weights


@dataclasses.dataclass
class Record:
    req: traffic_lib.Req
    due: float                  # host clock, seconds
    submitted: float = 0.0
    first: float | None = None
    last: float | None = None
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    failed: bool = False

    @property
    def n(self) -> int:
        return len(self.tokens)


def program_config(cell):
    """The program's own registered architecture, cut as the configuration
    file says, and checked against the file's published widths."""
    import dataclasses as dc

    from repro.configs import get

    prog = cell.config["bench"]["program"]
    cfg = dc.replace(get(prog["arch"]), **prog.get("set", {}))
    c = cell.config
    want = {"d_model": c["hidden_size"], "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "hd": c.get("head_dim") or c["hidden_size"]
            // c["num_attention_heads"],
            "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "n_layers": c["num_hidden_layers"],
            "rope_theta": float(c["rope_theta"]),
            "qk_norm": bool(c["bench"].get("qk_norm", False))}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {prog['arch']} differs from "
                         f"{cell.config_name}: {got} != {want}")
    return cfg


class Server:
    """One cell's program, weights and session."""

    def __init__(self, cell, seed: int, init: dict):
        from repro.cluster import Cluster, ServeSessionProgram
        from repro.models import steps

        self.cell = cell
        self.seed = seed
        self.serving = cell.config["bench"]["serving"]
        s = self.serving
        self.arch = program_config(cell)
        self.cluster = Cluster(self.arch)
        spec = ServeSessionProgram(
            paged=True, prefix_cache=True, preempt=False, slots=s["slots"],
            max_seq=s["max_seq"], max_prompt=s["max_prompt"],
            chunk=s["chunk"], page_size=s["page_size"],
            n_pages=s.get("n_pages"))
        with self.cluster.policy("tuned"):
            self.program = self.cluster.compile(spec)
        abstract, logical = steps.abstract_params(self.arch,
                                                  max_seq=s["max_seq"])
        self.params = weights.program_params(abstract, logical, init, seed)
        self.session = self.program.open(params=self.params)

    def close(self) -> None:
        """Free the session, the weights and the compiled programs."""
        import jax

        if self.session is not None:
            self.session.close()
        self.session = self.params = self.program = self.cluster = None
        gc.collect()
        jax.clear_caches()

    # -- warm-up ------------------------------------------------------------
    def warm_page_programs(self, counts) -> None:
        """The per-page-count programs (integrity readback of published and
        matched prompt pages, and of the scrub's pages) compile per
        distinct count: warm each count the traffic can produce."""
        import jax

        read = session_attr(self.session, "_page_read_fn")
        for n in sorted(set(counts) | {1, 2}):
            jax.block_until_ready(read(self.session.state,
                                       np.arange(1, n + 1, dtype=np.int32)))

    def serve_now(self, prompts, max_new: int) -> list[np.ndarray]:
        """Submit `prompts` and poll until all are done (set-up only)."""
        handles = [self.session.submit(p, max_new) for p in prompts]
        while any(not h.done for h in handles):
            self.session.poll()
        return [h.result() for h in handles]


def session_attr(sess, name: str):
    """An attribute of the session that the benchmark reads or wraps. It is
    the program's own, not an interface: where a change to the program
    takes it away, the benchmark stops here rather than measure less."""
    if not hasattr(sess, name):
        raise AttributeError(
            f"the serving session has no {name!r}, which the benchmark "
            f"reads; bring bench/harness/serve.py up to date with it")
    return getattr(sess, name)


def _probe(sess):
    """Counts, per chunk, the positions the busy slots attended to (trace
    runs only): wraps the session's chunk program so that the slot
    positions before and after each chunk are reduced on the device."""
    import jax
    import jax.numpy as jnp

    inner = session_attr(sess, "_chunk_fn")

    @jax.jit
    def tri(pos):
        p = pos.astype(jnp.float32)
        return jnp.sum(p * (p + 1.0) / 2.0)

    rows = []

    def wrapped(params, state):
        before = tri(state["pos"])
        out = inner(params, state)
        rows.append((before, tri(out[0]["pos"]), out[3]))
        return out

    sess._chunk_fn = wrapped
    return rows


def probe_totals(rows, lo: int = 0, hi: int | None = None) -> dict:
    """Sum the probe's rows[lo:hi]: steps with any busy slot, busy
    slot-steps, and attended positions (sum over busy slot-steps of the
    position plus one)."""
    steps = slot_steps = attended = 0
    for before, after, busy in rows[lo:hi]:
        busy = np.asarray(busy)
        steps += int(busy.max(initial=0))
        slot_steps += int(busy.sum())
        attended += float(after) - float(before)
    return {"steps": steps, "slot_steps": slot_steps, "attended": attended}


def _counters(sess, probe) -> dict:
    kv = sess.stats().get("kv", {})
    return {"busy": session_attr(sess, "_busy_steps"),
            "total": session_attr(sess, "_total_steps"),
            "emitted": sess.stats()["emitted_total"],
            "skipped": kv.get("prefill_skipped_tokens"),
            "chunks": len(sess.chunk_latencies),
            "probe": None if probe is None else len(probe),
            "t": time.perf_counter()}


@dataclasses.dataclass
class Window:
    records: list
    open_t: float
    close_t: float
    end_t: float
    before: dict
    after: dict
    chunk_walls: list           # (seconds, steps) of the window's chunks
    tokens_in_window: int
    late_s: list                # submit - due, per request
    drains: bool                # waited for the measured requests
    unissued: int               # measured requests never due before the end
    traced: tuple | None = None  # (trace_start, trace_stop, probe lo, hi)
    probe: list | None = None


def drive(server: Server, traffic, *, seconds: float, drain_cap: float,
          tracer=None, trace_s: float = 0.0,
          span=contextlib.nullcontext) -> Window:
    """Run the traffic: its lead-in before the window opens, the window of
    `seconds`, then (open loops) until every measured request has finished
    or `drain_cap` seconds have passed.

    `span(name)` wraps the harness's calls into the program so that a
    profiler trace can attribute idle device time to them. With `tracer`,
    a trace of `trace_s` seconds is taken in the middle of the window."""
    sess = server.session
    probe = _probe(sess) if tracer is not None else None
    records: dict[int, Record] = {}
    by_handle: dict = {}
    late: list[float] = []
    t0 = time.perf_counter()
    traffic.start()
    open_t = t0 + traffic.lead_in
    close_t = open_t + seconds
    trace_at = open_t + max(seconds - trace_s, 0.0) / 2.0
    traced = None
    before = after = None
    walls_lo = None
    tokens_in_window = 0
    end_t = None

    def submit_due(now: float) -> None:
        with span("bench.submit"):
            for req in traffic.due(now - t0):
                rec = Record(req, due=t0 + req.due)
                try:
                    h = sess.submit(req.prompt, req.max_new)
                except Exception:       # refused: counts as failed
                    rec.failed = True
                    rec.submitted = time.perf_counter()
                    records[req.rid] = rec
                    traffic.finished(req, np.zeros(0, np.int32),
                                     rec.submitted - t0)
                    continue
                rec.submitted = time.perf_counter()
                late.append(rec.submitted - rec.due)
                records[req.rid] = rec
                by_handle[h.id] = (h, rec)

    while True:
        now = time.perf_counter()
        if before is None and now >= open_t:
            before = _counters(sess, probe)
            walls_lo = before["chunks"]
            open_t = before["t"]
            close_t = open_t + seconds
            trace_at = open_t + max(seconds - trace_s, 0.0) / 2.0
        if tracer is not None and traced is None and before is not None \
                and now >= trace_at:
            tracer.start()
            traced = [time.perf_counter(), None, len(probe), None]
        if traced is not None and traced[1] is None \
                and now >= traced[0] + trace_s:
            traced[1] = time.perf_counter()
            traced[3] = len(probe)
            tracer.stop()
        if before is not None and after is None and now >= close_t:
            after = _counters(sess, probe)
            close_t = after["t"]
        if after is not None:
            if not traffic.measuring() or now >= close_t + drain_cap:
                end_t = now
                break
        submit_due(now)
        if sess.busy:
            with span("bench.poll"):
                events = sess.poll()
            t = time.perf_counter()
            in_window = before is not None and after is None
            for h, toks, done in events:
                h_rec = by_handle.get(h.id)
                if h_rec is None:
                    continue
                rec = h_rec[1]
                if toks.size:
                    if rec.first is None:
                        rec.first = t
                    rec.last = t
                    rec.tokens.extend(toks.tolist())
                    if in_window:
                        tokens_in_window += toks.size
                if done:
                    del by_handle[h.id]
                    if h.failed:
                        rec.failed = True
                    else:
                        rec.done = True
                    traffic.finished(rec.req, np.asarray(rec.tokens,
                                                         np.int32), t - t0)
        else:
            nxt = traffic.next_due()
            wait = 0.001 if nxt is None else t0 + nxt - time.perf_counter()
            if wait > 0:
                with span("bench.idle"):
                    time.sleep(min(wait, 0.005))
    if traced is not None and traced[1] is None:
        traced[1] = time.perf_counter()
        traced[3] = len(probe)
        tracer.stop()
    # the session keeps its last HISTORY chunk walls; a run stays far
    # below that, so the window's chunks are these
    walls = list(sess.chunk_latencies)[walls_lo:after["chunks"]]
    return Window(records=list(records.values()), open_t=open_t,
                  close_t=close_t, end_t=end_t, before=before, after=after,
                  chunk_walls=walls, tokens_in_window=tokens_in_window,
                  late_s=late, drains=traffic.drains,
                  unissued=traffic.unissued,
                  traced=tuple(traced) if traced else None,
                  probe=probe)

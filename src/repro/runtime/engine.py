"""Device-resident execution engine — burying the host round-trip.

MemPool's headline number is <2% execution stalls at 256 cores: every PE has
an independent instruction path and the DMA engine streams operands, so
cores never wait on a slow shared frontend. Our runtime's "frontend" is the
Python host loop — one dispatch plus one `block_until_ready` per decode
token (or train step) is the execution stall of the TPU translation, and at
small models it dominates wall time.

This module rolls the loop onto the device:

* `make_session_chunk` compiles K decode steps over a whole slot pool into
  ONE `lax.scan` program. Per-slot prompt prefill, positions, EOS and
  budget masking all live inside the scan (`lax.cond` skips the model body
  once every slot is done), so the host syncs once per K tokens instead of
  once per token. The pool state is donated (`donate_argnums`), so
  steady-state serving re-uses the same device allocations chunk after
  chunk; the refill and the programs that write the pool donate it too.
* `make_train_chunk` is the same treatment for training: a scan over a
  stacked batch of `steps_per_sync` micro-iterations with the whole train
  state donated; the straggler detector and logger sample at chunk
  granularity.
* `StallClock` is the stall-accounting layer: host-sync count, dispatch-gap
  time (host-side work between one sync finishing and the next dispatch —
  the paper's execution stall), and device-wait time, reported as a
  `stall_pct` figure to track against the paper's <2%.

The chunk programs are pure functions of explicit carries — no hidden
state — so they compose with any decode/train step built by
`models/steps.py` (or a scripted stand-in in tests).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp


# ----------------------------------------------------------------------------
# Stall accounting
# ----------------------------------------------------------------------------


@dataclasses.dataclass
class StallClock:
    """Host-side stall ledger for a device-resident loop.

    Call `dispatch()` right before handing work to the device and
    `sync(*arrays)` when the host blocks on results. The gap between one
    sync completing and the next dispatch is host-only time — the device
    sits idle, the direct analogue of MemPool's execution stall. `sync`
    time itself is the host waiting on the *device* (compute, not stall).
    """

    host_syncs: int = 0
    dispatch_gap_s: float = 0.0
    device_wait_s: float = 0.0
    _t_start: float = dataclasses.field(default_factory=time.perf_counter)
    _last_sync_end: float | None = None

    def dispatch(self) -> float:
        now = time.perf_counter()
        if self._last_sync_end is not None:
            self.dispatch_gap_s += now - self._last_sync_end
        return now

    def sync(self, *arrays) -> float:
        """Block on `arrays`; returns the post-sync timestamp."""
        t0 = time.perf_counter()
        if arrays:
            jax.block_until_ready(arrays)
        now = time.perf_counter()
        self.host_syncs += 1
        self.device_wait_s += now - t0
        self._last_sync_end = now
        return now

    def sync_done(self, t_wait_start: float) -> float:
        """Record a sync whose device wait happened externally (e.g. on a
        watchdog thread): the wait ran from `t_wait_start` to now."""
        now = time.perf_counter()
        self.host_syncs += 1
        self.device_wait_s += now - t_wait_start
        self._last_sync_end = now
        return now

    def report(self) -> dict:
        wall = time.perf_counter() - self._t_start
        return {
            "host_syncs": self.host_syncs,
            "dispatch_gap_s": self.dispatch_gap_s,
            "device_wait_s": self.device_wait_s,
            "wall_s": wall,
            "stall_pct": 100.0 * self.dispatch_gap_s / max(wall, 1e-12),
        }

    @staticmethod
    def merge(clocks) -> "StallClock":
        """Fold per-group ledgers into one aggregate clock.

        Additive counters (syncs, gaps, device waits) sum; wall time does
        NOT — concurrent ledgers cover the same wall-clock span, so the
        merged clock keeps the earliest member start and `report()`
        divides the summed gap by ONE shared wall, never N overlapping
        copies of it. The merged `stall_pct` is therefore host-idle
        device-seconds per wall second — a load-average-style figure
        that can exceed 100% when several groups stall concurrently
        inside the same span (cap: 100% x n_groups). Per-group ratios
        live in each member's own report. An empty merge is a fresh
        clock.
        """
        clocks = list(clocks)
        if not clocks:
            return StallClock()
        out = StallClock(
            host_syncs=sum(c.host_syncs for c in clocks),
            dispatch_gap_s=sum(c.dispatch_gap_s for c in clocks),
            device_wait_s=sum(c.device_wait_s for c in clocks),
            _t_start=min(c._t_start for c in clocks))
        ends = [c._last_sync_end for c in clocks
                if c._last_sync_end is not None]
        out._last_sync_end = max(ends) if ends else None
        return out


# ----------------------------------------------------------------------------
# Scan-compiled slot-scheduled decode — the continuous-batching session cell
# ----------------------------------------------------------------------------


def init_session_state(cache, n_slots: int, max_prompt: int,
                       pages_per_slot: int | None = None) -> dict:
    """Fresh device state for a ServeSession's slot pool (all slots idle).

    The state is one pytree so the whole pool is donated through every
    chunk: steady-state serving re-uses the same device buffers no matter
    how many requests cycle through the slots.

    `pages_per_slot` (paged KV sessions only) adds the per-slot page
    tables: a (B, pages_per_slot) int32 row per slot, all entries starting
    at the reserved trash page 0 so an idle slot's scatter-writes land
    where nobody reads.
    """
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    state = {
        "cache": cache,
        "tok": i32(n_slots, 1),                # last sampled token per slot
        "pos": i32(n_slots),                   # per-slot decode position
        "consumed": i32(n_slots),              # prompt tokens consumed
        "prompt_len": i32(n_slots),
        "prompt_buf": i32(n_slots, max_prompt),
        "budget": i32(n_slots),                # max_new per slot
        "emitted": i32(n_slots),
        "finished": jnp.zeros((n_slots,), bool),
        "active": jnp.zeros((n_slots,), bool),
        "age": i32(n_slots),                   # admissions seen by the slot
    }
    if pages_per_slot is not None:
        state["pages"] = i32(n_slots, pages_per_slot)
    return state


def session_chunk_fn(decode_step: Callable, chunk: int,
                     eos_id: int | None = None) -> Callable:
    """The pure K-step session program (unjitted — see `make_session_chunk`).

    Signature::

        chunk_fn(params, state) -> (state, tokens, emit, busy, all_done)

    `state` is the `init_session_state` pytree; every slot advances through
    its own request: while `consumed < prompt_len` the step feeds the next
    prompt token (prefill — outputs discarded until the step that consumes
    the last prompt token, whose output is the request's first emitted
    token), afterwards it feeds back its own sampled token. Slots are
    *done* — frozen in place, position not advancing — once inactive,
    finished (EOS), or out of budget (`emitted == budget`); `lax.cond`
    skips the model body entirely when every slot is done. Each slot keeps
    its own `pos`, so a freshly refilled slot restarts at position 0 while
    its neighbours are mid-generation.

    Returns per-chunk `tokens` (B, K) raw step outputs, `emit` (B, K) bool
    (which of them are emitted tokens of the slot's request — step order),
    `busy` (B,) how many of the K steps each slot was live for (occupancy
    accounting), and `all_done` for the host's early exit.
    """

    def _done(s):
        return (~s["active"]) | s["finished"] | (s["emitted"] >= s["budget"])

    def chunk_fn(params, state):
        p_max = state["prompt_buf"].shape[1]

        def body(s, _):
            done = _done(s)
            fed_prompt = (~done) & (s["consumed"] < s["prompt_len"])
            idx = jnp.clip(s["consumed"], 0, p_max - 1)
            p_tok = jnp.take_along_axis(s["prompt_buf"], idx[:, None], axis=1)
            in_tok = jnp.where(fed_prompt[:, None], p_tok, s["tok"])

            def run(operand):
                cache, tok = operand
                batch = {"tokens": tok, "pos": s["pos"]}
                if "pages" in s:        # paged KV: per-slot page tables
                    batch["pages"] = s["pages"]
                return decode_step(params, cache,
                                   batch)

            def skip(operand):
                return operand

            new_cache, raw = jax.lax.cond(jnp.any(~done), run, skip,
                                          (s["cache"], in_tok))
            consumed = s["consumed"] + fed_prompt
            # the step that consumed the last prompt token emits the first
            # token; pure-prefill outputs are discarded
            emit = (~done) & (consumed >= s["prompt_len"])
            finished = s["finished"]
            if eos_id is not None:
                finished = finished | (emit & (raw[:, 0] == eos_id))
            s = dict(s, cache=new_cache,
                     tok=jnp.where(done[:, None], s["tok"], raw),
                     pos=s["pos"] + (~done), consumed=consumed,
                     emitted=s["emitted"] + emit, finished=finished)
            return s, (raw[:, 0], emit, ~done)

        state, (toks, emit, live) = jax.lax.scan(
            body, state, None, length=chunk)
        return (state, jnp.moveaxis(toks, 0, 1), jnp.moveaxis(emit, 0, 1),
                jnp.sum(live, axis=0, dtype=jnp.int32),
                jnp.all(_done(state)))

    return chunk_fn


def make_session_chunk(decode_step: Callable, chunk: int, *,
                       eos_id: int | None = None,
                       donate: bool = True) -> Callable:
    """Jit `session_chunk_fn`, donating the whole slot-pool state pytree so
    steady-state serving runs allocation-free. The donated state is invalid
    after the call — the caller threads the returned state forward."""
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    fn = session_chunk_fn(decode_step, chunk, eos_id)
    return jax.jit(fn, donate_argnums=(1,) if donate else ())


def _default_cache_zero(cache, mask):
    """Zero masked batch rows of a flat cache (batch axis 0 on every leaf).
    Model caches with stacked layer axes pass `steps.zero_cache_slots`."""
    def one(c):
        shape = (mask.shape[0],) + (1,) * (c.ndim - 1)
        return jnp.where(mask.reshape(shape), jnp.zeros((), c.dtype), c)
    return jax.tree.map(one, cache)


def make_session_refill(*, cache_zero: Callable | None = None,
                        donate: bool = True) -> Callable:
    """Compile the slot-refill program: `refill(state, admit, release,
    prompt_buf, prompt_len, budget) -> state`.

    `admit`/`release` are (B,) bool masks; admitted slots get their cache
    rows zeroed (recurrent block states must not leak across requests),
    position/counters reset, the new request's prompt row and budget
    installed, and `age` bumped; released slots just go inactive. Rows of
    the new-request arrays outside `admit` are ignored. The state is
    donated, so refills recycle the pool's buffers in place — the DMA-refill
    analogue of the paper's always-addressable L1 slots.
    """
    cache_zero = cache_zero or _default_cache_zero

    def refill(state, admit, release, prompt_buf, prompt_len, budget):
        zero = jnp.zeros_like(state["pos"])
        pick = lambda new, old: jnp.where(admit, new, old)
        return dict(
            state,
            cache=cache_zero(state["cache"], admit),
            tok=jnp.where(admit[:, None], 0, state["tok"]),
            pos=pick(zero, state["pos"]),
            consumed=pick(zero, state["consumed"]),
            emitted=pick(zero, state["emitted"]),
            finished=jnp.where(admit, False, state["finished"]),
            active=(state["active"] & ~release) | admit,
            age=state["age"] + admit,
            prompt_buf=jnp.where(admit[:, None], prompt_buf,
                                 state["prompt_buf"]),
            prompt_len=pick(prompt_len, state["prompt_len"]),
            budget=pick(budget, state["budget"]),
        )

    return jax.jit(refill, donate_argnums=(0,) if donate else ())


def make_paged_session_refill(*, cache_zero: Callable,
                              donate: bool = True) -> Callable:
    """The paged-KV refill program: `refill(state, admit, release,
    prompt_buf, prompt_len, budget, pages, start) -> state`.

    Differences from `make_session_refill`:

    * `pages` (B, pages_per_slot) installs each admitted slot's page
      table row; released slots' rows are re-pointed at the trash page
      (0) so their frozen-position scatter-writes can never corrupt a
      page that has been reallocated;
    * `start` (B,) is the admitted slot's initial position/consumed
      count — non-zero exactly when shared prefix pages cover the first
      `start` prompt tokens, i.e. the prefill-skip that collapses TTFT;
    * `cache_zero` must be the *paged-aware* zero (`make_paged_cache_ops`
      ["zero_slots"]): only private (recurrent/rolling) leaves are
      zeroed — pool pages are left as-is, which is the point: refill is
      a table install, not a cache wipe.
    """

    def refill(state, admit, release, prompt_buf, prompt_len, budget,
               pages, start):
        start = start.astype(jnp.int32)
        pick = lambda new, old: jnp.where(admit, new, old)
        new_pages = jnp.where(admit[:, None], pages,
                              jnp.where(release[:, None], 0,
                                        state["pages"]))
        return dict(
            state,
            cache=cache_zero(state["cache"], admit),
            tok=jnp.where(admit[:, None], 0, state["tok"]),
            pos=pick(start, state["pos"]),
            consumed=pick(start, state["consumed"]),
            emitted=pick(jnp.zeros_like(state["emitted"]),
                         state["emitted"]),
            finished=jnp.where(admit, False, state["finished"]),
            active=(state["active"] & ~release) | admit,
            age=state["age"] + admit,
            prompt_buf=jnp.where(admit[:, None], prompt_buf,
                                 state["prompt_buf"]),
            prompt_len=pick(prompt_len, state["prompt_len"]),
            budget=pick(budget, state["budget"]),
            pages=new_pages,
        )

    return jax.jit(refill, donate_argnums=(0,) if donate else ())


def make_paged_nan_scan(cache_nan: Callable) -> Callable:
    """Paged corruption sentinel: `nan_scan(state) -> (B,) bool`.
    `cache_nan(cache, tables)` is `make_paged_cache_ops["nan_slots"]` —
    pool leaves are attributed to slots through the page tables."""

    def nan_scan(state):
        return cache_nan(state["cache"], state["pages"])

    return jax.jit(nan_scan)


def make_paged_slot_corrupt(cache_corrupt: Callable,
                            donate: bool = True) -> Callable:
    """Paged fault-injection write: `corrupt(state, mask) -> state` NaNs
    the masked slots' private rows *and* their table-addressed pool
    pages (`make_paged_cache_ops["corrupt_slots"]`)."""

    def corrupt(state, mask):
        return dict(state, cache=cache_corrupt(state["cache"], mask,
                                               state["pages"]))

    return jax.jit(corrupt, donate_argnums=(0,) if donate else ())


def make_page_copy(cache_copy: Callable, donate: bool = True) -> Callable:
    """Pool page copy: `page_copy(state, src, dst) -> state` (the COW
    fork's device half — `src`/`dst` are equal-length page-id vectors).
    Retraces per distinct copy count; forks are rare (one per exact
    full-prefix hit) and almost always a single page."""

    def page_copy(state, src, dst):
        return dict(state, cache=cache_copy(state["cache"], src, dst))

    return jax.jit(page_copy, donate_argnums=(0,) if donate else ())


def make_page_scrub(cache_scrub: Callable, donate: bool = True) -> Callable:
    """Pool page scrub: `page_scrub(state, pages) -> state` zeroes the
    listed pages in every pool leaf. Runs only on pages freed from a
    corrupted slot — NaN is the one thing masked attention cannot hide
    (0 * NaN poisons the gathered V row)."""

    def page_scrub(state, pages):
        return dict(state, cache=cache_scrub(state["cache"], pages))

    return jax.jit(page_scrub, donate_argnums=(0,) if donate else ())


def make_page_read(cache_read: Callable) -> Callable:
    """Pool page readback: `page_read(state, pages) -> tuple of arrays`,
    one per pool leaf, page axis first — the host digests these for the
    per-page integrity checksum. Read-only (never donated); retraces per
    distinct page count, which stays small (publish batches and the
    bounded scrub budget)."""

    def page_read(state, pages):
        return cache_read(state["cache"], pages)

    return jax.jit(page_read)


def make_page_flip(cache_flip: Callable, donate: bool = True) -> Callable:
    """Silent page corruption for the `bit_flip` fault:
    `page_flip(state, pages) -> state` perturbs the pages' float content
    by +1 — finite values the NaN sentinel scan cannot see, so only the
    content checksum catches it."""

    def page_flip(state, pages):
        return dict(state, cache=cache_flip(state["cache"], pages))

    return jax.jit(page_flip, donate_argnums=(0,) if donate else ())


# ----------------------------------------------------------------------------
# Slot-granular checkpoint/resume + fault detection — the elastic layer
# ----------------------------------------------------------------------------
#
# MemPool's robustness story is that every PE executes independently: one
# stalled or dead core never wedges the cluster, because the shared-L1 rows
# it owned stay addressable. The serving analogue: a slot must be
# *individually* checkpointable (preemption snapshots its KV rows + decode
# counters and requeues the request for a bit-identical resume later) and
# *individually* condemnable (a dead or corrupted slot is quarantined and
# the pool degrades instead of crashing). These helpers are the device half
# of that machinery; `ServeSession` (runtime/serve_loop.py) drives them.
#
# The per-request device rows that travel with a slot snapshot. `active`
# and `age` are *slot* properties, not request properties — restore forces
# active=True and bumps age like any other admission.
SLOT_FIELDS = ("tok", "pos", "consumed", "prompt_len", "prompt_buf",
               "budget", "emitted", "finished")


def _default_cache_take(cache, slot):
    """Slice slot `slot` out of a flat cache (batch axis 0 on every leaf).
    Model caches with stacked layer axes pass `steps.take_cache_slot`."""
    return jax.tree.map(
        lambda c: jax.lax.dynamic_index_in_dim(c, slot, axis=0,
                                               keepdims=False), cache)


def _default_cache_put(cache, slot, rows):
    """Inverse of `_default_cache_take` (batch axis 0 on every leaf)."""
    return jax.tree.map(lambda c, r: c.at[slot].set(r), cache, rows)


def _default_cache_fill(cache, mask, value):
    """Fill masked batch rows of a flat cache with `value` (axis 0).
    Non-float leaves are skipped when `value` is not finite (NaN fault
    injection must not touch integer state)."""
    import math

    def one(c):
        if (not jnp.issubdtype(c.dtype, jnp.inexact)
                and not math.isfinite(value)):
            return c
        shape = (mask.shape[0],) + (1,) * (c.ndim - 1)
        return jnp.where(mask.reshape(shape), jnp.asarray(value, c.dtype), c)
    return jax.tree.map(one, cache)


def _default_cache_nan(cache):
    """(B,) bool: any-NaN per batch row of a flat cache (axis 0)."""
    flags = [jnp.any(jnp.isnan(c), axis=tuple(range(1, c.ndim)))
             for c in jax.tree.leaves(cache)
             if jnp.issubdtype(c.dtype, jnp.inexact)]
    if not flags:
        return jnp.zeros((jax.tree.leaves(cache)[0].shape[0],), bool)
    out = flags[0]
    for f in flags[1:]:
        out = out | f
    return out


def make_slot_snapshot(*, cache_take: Callable | None = None) -> Callable:
    """Compile the slot-checkpoint program: `snapshot(state, slot) -> rows`.

    `rows` is the pytree of slot `slot`'s per-request device state — its
    cache rows plus every `SLOT_FIELDS` entry. Nothing is donated: the
    pool state stays live (the slot is released/refilled separately).
    The caller typically `jax.device_get`s the result so the snapshot
    survives the pool's donation cycle on the host.
    """
    cache_take = cache_take or _default_cache_take

    def snapshot(state, slot):
        rows = {k: state[k][slot] for k in SLOT_FIELDS}
        rows["cache"] = cache_take(state["cache"], slot)
        return rows

    return jax.jit(snapshot)


def make_slot_restore(*, cache_put: Callable | None = None,
                      donate: bool = True) -> Callable:
    """Compile the slot-resume program: `restore(state, slot, rows) ->
    state`. Writes a snapshot's rows back into slot `slot` — bit-exact,
    so the resumed request continues exactly where it was preempted —
    marks the slot active, and bumps its `age` (a resume is an admission).
    The pool state is donated, like refill."""
    cache_put = cache_put or _default_cache_put

    def restore(state, slot, rows):
        out = dict(state)
        for k in SLOT_FIELDS:
            out[k] = state[k].at[slot].set(rows[k])
        out["cache"] = cache_put(state["cache"], slot, rows["cache"])
        out["active"] = state["active"].at[slot].set(True)
        out["age"] = state["age"].at[slot].add(1)
        return out

    return jax.jit(restore, donate_argnums=(0,) if donate else ())


def make_nan_scan(*, cache_nan: Callable | None = None) -> Callable:
    """Compile the corruption sentinel: `nan_scan(state) -> (B,) bool`,
    true for any slot whose cache rows hold a NaN. One device reduction
    per chunk when the session runs with fault detection on; the driver
    quarantines/requeues flagged slots instead of streaming garbage."""
    cache_nan = cache_nan or _default_cache_nan

    def nan_scan(state):
        return cache_nan(state["cache"])

    return jax.jit(nan_scan)


def make_slot_corrupt(*, cache_fill: Callable | None = None,
                      donate: bool = True) -> Callable:
    """Compile the fault-injection write: `corrupt(state, mask) -> state`
    with masked slots' float cache rows set to NaN (integer rows
    untouched). Only the fault harness calls this."""
    cache_fill = cache_fill or _default_cache_fill

    def corrupt(state, mask):
        return dict(state,
                    cache=cache_fill(state["cache"], mask, float("nan")))

    return jax.jit(corrupt, donate_argnums=(0,) if donate else ())


# ----------------------------------------------------------------------------
# Scan-compiled multi-step training
# ----------------------------------------------------------------------------


def make_train_chunk(train_step: Callable, *, donate: bool = True) -> Callable:
    """Roll `train_step` into a scan over a stacked batch.

    `chunk(state, batches)` runs one step per leading-dim slice of
    `batches` and returns `(state, metrics)` with every metric stacked
    (shape (k, ...)). The train state is donated, so steady-state training
    re-uses the param/opt-state buffers; the chunk length is inferred from
    the stacked batch (jit re-specializes per distinct length — at most two
    per run: the steady chunk and the final partial one).
    """

    def chunk(state, batches):
        def body(s, b):
            return train_step(s, b)
        return jax.lax.scan(body, state, batches)

    return jax.jit(chunk, donate_argnums=(0,) if donate else ())


def stack_batches(batches: list) -> dict:
    """Stack host/device batch pytrees on a new leading step axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)

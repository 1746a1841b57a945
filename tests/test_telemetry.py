"""The serve loop's spans, request records and compile count
(runtime/telemetry.py): the recorder alone, a paged `ServeSession` at
smoke widths writing into it, the named scopes of the decode step, and
a recorded chip trace showing that the program's spans share the device
ops' clock."""

from __future__ import annotations

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import telemetry
from repro.runtime.scheduler import Request

ARCH = "qwen3-14b-smoke"
FIXTURE = Path(__file__).parent / "fixtures" / "serve_trace_chat.pbtxt"


def _fake_poll(tel, req, i: int) -> None:
    """The span shape of one poll, with no device behind it."""
    tel.chunk = i
    with tel.span("serve.poll"):
        with tel.span("serve.retire"):
            pass
        with tel.span("serve.admit"):
            with tel.span("serve.page_read", pages=2) as sp:
                sp.attrs["bytes"] = 64
        with tel.chunk_annotation():
            pass
        with tel.span("serve.harvest"):
            tel.request(req)
        tel.record("serve.chunk", 1.0, 2.0, pages_held=4)


def self_times(spans) -> dict[str, float]:
    """Each span's length less its children's (next depth, inside it, same
    session), summed per name."""
    out: dict[str, float] = {}
    for s in spans:
        kids = sum(k.end - k.start for k in spans
                   if k.session == s.session and k.depth == s.depth + 1
                   and s.start <= k.start and k.end <= s.end)
        out[s.name] = out.get(s.name, 0.0) + (s.end - s.start - kids)
    return out


def check_poll_shape(spans) -> None:
    """Phases lie inside their poll, carry its chunk, and do not
    overlap; page reads lie inside a phase."""
    polls = [s for s in spans if s.depth == 0]
    assert polls and all(s.name == "serve.poll" for s in polls)
    for s in spans:
        assert s.name.startswith("serve.") and s.start <= s.end
        if s.depth == 0:
            continue
        parents = [p for p in spans if p.depth == s.depth - 1
                   and p.session == s.session
                   and p.start <= s.start and s.end <= p.end]
        assert len(parents) == 1, s
        assert parents[0].chunk == s.chunk
        if s.name == "serve.page_read":
            assert s.depth == 2 and s.attrs["pages"] >= 1
            assert s.attrs["bytes"] > 0
    for p in polls:
        kids = sorted((k for k in spans if k.depth == 1
                       and k.session == p.session
                       and p.start <= k.start and k.end <= p.end),
                      key=lambda k: k.start)
        for a, b in zip(kids, kids[1:]):
            assert a.end <= b.start, (a, b)


def test_spans_nest_and_self_time():
    rec = telemetry.Recorder()
    tel = rec.session(kv_pages=10)
    req = Request(rid=0, prompt=np.zeros(3, np.int32), max_new=2)
    with tel.span("serve.poll"):
        with tel.span("serve.admit"):
            with tel.span("serve.page_read", pages=1) as sp:
                sp.attrs["bytes"] = 8
        with tel.span("serve.harvest"):
            tel.request(req)
    spans = rec.spans()
    assert [s.name for s in spans] == ["serve.page_read", "serve.admit",
                                       "serve.harvest", "serve.poll"]
    check_poll_shape(spans)
    by = {s.name: s for s in spans}
    st = self_times(spans)
    assert st["serve.admit"] == pytest.approx(
        (by["serve.admit"].end - by["serve.admit"].start)
        - (by["serve.page_read"].end - by["serve.page_read"].start))
    assert sum(st.values()) == pytest.approx(
        by["serve.poll"].end - by["serve.poll"].start)
    assert by["serve.page_read"].attrs == {"pages": 1, "bytes": 8}
    assert rec.session_info(tel.sid) == {"group": None, "kv_pages": 10}
    [r] = rec.requests()
    assert (r.rid, r.submitted_at, r.admitted_at, r.first_token_at) == (
        0, req.submitted_at, None, None)


def test_rings_stay_bounded(monkeypatch):
    monkeypatch.setattr(telemetry, "SPANS", 16)
    monkeypatch.setattr(telemetry, "REQUESTS", 4)
    rec = telemetry.Recorder()
    tel = rec.session()
    for i in range(50):
        req = Request(rid=i, prompt=np.zeros(2, np.int32), max_new=1)
        _fake_poll(tel, req, i)
    spans = rec.spans()
    assert len(spans) == 16
    assert rec.spans_dropped == 50 * 6 - 16     # chunk_annotation: no record
    assert [r.rid for r in rec.requests()] == [46, 47, 48, 49]
    assert all(s.chunk >= 47 for s in spans)   # the newest polls are kept


def test_recorder_is_one_per_process():
    assert telemetry.recorder() is telemetry.recorder()


@pytest.fixture(scope="module")
def paged():
    from repro.cluster.session import Cluster, ServeSessionProgram
    cluster = Cluster(ARCH)
    prog = cluster.compile(ServeSessionProgram(
        paged=True, page_size=4, slots=4, max_seq=48, max_prompt=24,
        max_new=6, chunk=4, preempt=False))
    return prog, prog.init_params()


def test_session_spans_counts_and_request_stages(paged):
    prog, params = paged
    sess = prog.open(params=params)
    rng = np.random.default_rng(3)
    pre = rng.integers(1, 50, size=12).astype(np.int32)     # 3 full pages
    prompts = [np.concatenate([pre, rng.integers(1, 50, size=n)])
               .astype(np.int32) for n in (2, 3, 4, 5, 6, 7, 2, 3)]
    first = [sess.submit(p, 5) for p in prompts[:2]]
    sess.drain()                    # publishes the shared prefix
    handles = first + [sess.submit(p, 5) for p in prompts[2:]]
    sess.drain()
    sess.close()
    rec = telemetry.recorder()
    spans = [s for s in rec.spans() if s.session == sess._tel.sid]
    check_poll_shape(spans)
    names = {s.name for s in spans}
    assert {"serve.poll", "serve.retire", "serve.admit", "serve.refill",
            "serve.chunk", "serve.harvest", "serve.scrub",
            "serve.page_read"} <= names
    assert "serve.journal" not in names             # not durable
    chunks = [s for s in spans if s.name == "serve.chunk"]
    assert len(chunks) == len(sess.chunk_latencies)
    assert [s.chunk for s in chunks] == list(range(len(chunks)))
    # the chunk span's ends are the stall clock's stamps
    assert [c.end - c.start for c in chunks] == [
        dt for dt, _ in sess.chunk_latencies]
    # shared prefixes were verified at admission: a page read under admit
    assert sess.kv.prefill_skipped_tokens > 0
    admits = [s for s in spans if s.name == "serve.admit"]
    assert any(a.start <= r.start and r.end <= a.end for a in admits
               for r in spans if r.name == "serve.page_read")
    n_usable = sess.kv.pool.n_pages - 1
    assert rec.session_info(sess._tel.sid)["kv_pages"] == n_usable
    assert all(set(c.attrs) == {"pages_held", "pages_cached_only"}
               for c in chunks)
    assert all(0 < c.attrs["pages_held"] <= n_usable for c in chunks)
    assert max(c.attrs["pages_cached_only"] for c in chunks) > 0
    recs = {r.rid: r for r in rec.requests() if r.session == sess._tel.sid}
    assert sorted(recs) == sorted(h.id for h in handles)
    for h in handles:
        r, req = recs[h.id], h._req
        assert r.submitted_at <= r.admitted_at <= r.first_token_at \
            <= req.finished_at
        assert (r.submitted_at, r.admitted_at, r.first_token_at) == (
            req.submitted_at, req.admitted_at, req.first_token_at)


def test_page_gauges_match_a_count_of_the_tables():
    from repro.runtime.kvpool import PagedKV
    kv = PagedKV(n_pages=40, page_size=4, n_slots=3, pages_per_slot=8)
    rng = np.random.default_rng(0)
    pre = rng.integers(1, 50, size=8).astype(np.int32)
    kv.admit(0, np.concatenate([pre, [7, 8]]).astype(np.int32), 6)
    kv.publish(0)
    kv.release(0)                       # 2 prefix pages, cache-only now
    kv.admit(1, np.concatenate([pre, [9]]).astype(np.int32), 6)
    kv.admit(2, rng.integers(1, 50, size=5).astype(np.int32), 6)
    tables = [set(kv.slot_pages(s)) for s in range(3)]
    held, cached_only = kv.page_gauges()
    assert held == len(set().union(*tables))
    cache_pages = {e.page for e in kv.prefix._chain.values()}
    assert cached_only == len(cache_pages - set().union(*tables)) == 0
    kv.release(1)
    assert kv.page_gauges() == (len(tables[2]), len(cache_pages))


def test_fresh_shape_adds_one_compile(paged):
    prog, params = paged
    sess = prog.open(params=params)
    x = jax.block_until_ready(jnp.asarray(np.zeros((7, 11), np.float32)))
    f = jax.jit(lambda x: x * 3 + 1)
    before = sess.stats()["compiles"]
    jax.block_until_ready(f(x))
    assert sess.stats()["compiles"] == before + 1
    jax.block_until_ready(f(x))                     # warm: nothing new
    assert sess.stats()["compiles"] == before + 1
    assert "tokens_per_s" not in sess.stats()
    sess.close()


def test_decode_step_carries_named_scopes(paged):
    prog, params = paged
    sess = prog.open(params=params)
    text = sess._chunk_fn.lower(params, sess.state).as_text(debug_info=True)
    sess.close()
    # a scope prefixes the name of each op traced inside it
    for scope in ("attention", "ffn", "unembed", "kv_write",
                  "paged_attention"):
        assert re.search(rf'"([^"]*/)?{scope}/\w', text), scope
    # the paged decode kernel reads the pool through the page table: no
    # gather of each slot's whole page list
    assert not re.search(r'"([^"]*/)?kv_gather/\w', text)


def test_recorded_chip_trace_shares_the_device_clock():
    """A few polls of one `--trace 1` chip run of the chat cell: every run
    of the session cell on the device lies inside the `serve.chunk` span
    the host wrote around its dispatch and sync, one span per run, up to
    the profiler's alignment of the device clock to the host's. In this
    fixture the device starts each program 0.13-0.25 ms before the span
    that dispatched it begins, which no dispatch can do, and ends it about
    2 ms before the span's sync returns; a chunk runs 763 ms."""
    from jax.profiler import ProfileData

    align_ns = 0.5e6
    pd = ProfileData.from_text_proto(FIXTURE.read_text())
    host = pd.find_plane_with_name("/host:CPU")
    chunks = [(e.start_ns, e.start_ns + e.duration_ns)
              for line in host.lines for e in line.events
              if e.name == "serve.chunk"]
    [device] = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    runs = [(e.start_ns, e.start_ns + e.duration_ns)
            for line in device.lines if line.name == "XLA Modules"
            for e in line.events if "jit_chunk_fn" in e.name]
    assert len(runs) == len(chunks) == 3
    for a, b in runs:
        around = [(lo, hi) for lo, hi in chunks if lo < b and a < hi]
        assert len(around) == 1
        lo, hi = around[0]
        assert lo - align_ns <= a and b <= hi + align_ns

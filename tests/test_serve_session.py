"""Request-level serving: ServeSession + the slot-scheduled session cell.

Acceptance coverage: a ServeSession fed one batch up-front is bit-identical
to a plain per-token greedy loop over the same decode step (tokens, EOS
early-stop and masking, `emitted_per_slot`) at every chunk length, with
O(T/K) host syncs; the chunk consumes its donated state; staggered
requests decoding at independent per-slot positions match what each
request gets in isolation; finished slots are recycled in place
(allocation-free steady state, on-device `age`/`active` masks); streaming
delivers incremental tokens; cancel frees the slot; submit applies
bounded-queue backpressure.
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import Cluster, ServeSessionProgram
from repro.models import steps
from repro.runtime import engine
from repro.runtime.scheduler import QueueFull, RequestFailed
from repro.runtime.serve_loop import ServeSession


# ----------------------------------------------------------------------------
# Scripted harness: a decode step aware of per-slot positions
# ----------------------------------------------------------------------------


SCRIPT = np.array([[7, 1, 2], [3, 7, 4], [5, 6, 8], [9, 9, 9],
                   [2, 3, 4], [5, 6, 7]], np.int32)


def scripted_step(script: np.ndarray):
    """Emits script[pos[i], i] per slot — `pos` scalar or (B,) vector."""
    table = jnp.asarray(script, jnp.int32)

    def decode_step(params, cache, batch):
        pos = jnp.asarray(batch["pos"])
        idx = jnp.clip(pos, 0, table.shape[0] - 1)
        if pos.ndim == 0:
            return cache, jnp.take(table, idx, axis=0)[:, None]
        rows = jnp.take(table, idx, axis=0)              # (B, B)
        return cache, jnp.diagonal(rows)[:, None]

    return decode_step


def make_session(script=SCRIPT, *, chunk=2, eos_id=7, max_prompt=4,
                 max_queue=None, admission="fifo"):
    B = script.shape[1]
    chunk_fn = engine.make_session_chunk(scripted_step(script), chunk,
                                         eos_id=eos_id)
    refill_fn = engine.make_session_refill()
    state = engine.init_session_state({"kv": jnp.zeros((B, 4), jnp.float32)},
                                      B, max_prompt)
    return ServeSession(chunk_fn, refill_fn, None, state, n_slots=B,
                        chunk=chunk, max_prompt=max_prompt, eos_id=eos_id,
                        max_queue=max_queue, admission=admission)


def reference_loop(step, params, cache, prompts, n, eos_id=None):
    """The per-token oracle: one decode call a position for the whole batch,
    feeding each slot its prompt, then its own greedy token. Slot i emits
    the outputs from its last prompt position on: n of them, or up to and
    with its EOS."""
    prompts = np.asarray(prompts, np.int32)
    P = prompts.shape[1]
    rows, tok = [[] for _ in prompts], None
    for pos in range(P + n - 1):
        feed = prompts[:, pos:pos + 1] if pos < P else tok
        cache, tok = step(params, cache, {"tokens": jnp.asarray(feed),
                                          "pos": jnp.asarray(pos, jnp.int32)})
        tok = np.asarray(tok)
        if pos < P - 1:
            continue
        for row, t in zip(rows, tok[:, 0]):
            if not row or row[-1] != eos_id:
                row.append(int(t))
    return rows


# ----------------------------------------------------------------------------
# Parity with the per-token loop (scripted)
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("eos_id", [7, None])
@pytest.mark.parametrize("chunk", [1, 2, 3, 4, 16])
def test_scan_decode_matches_per_token_loop(chunk, eos_id):
    B = SCRIPT.shape[1]
    ref = reference_loop(scripted_step(SCRIPT), None, None,
                         np.zeros((B, 1)), 4, eos_id)
    sess = make_session(chunk=chunk, eos_id=eos_id)
    handles = [sess.submit([0], 4) for _ in range(B)]
    sess.drain()
    assert [h.tokens.tolist() for h in handles] == ref
    assert [h.hit_eos for h in handles] == [eos_id in r for r in ref]
    # host syncs once per chunk, not per token
    assert sess.clock.report()["host_syncs"] <= -(-4 // chunk) + 1


def test_scan_decode_eos_early_stop_and_masking():
    sess = make_session(chunk=2)
    hs = [sess.submit([0], 4) for _ in range(3)]
    sess.drain()
    # slot 0 finishes at step 0, slot 1 at step 1; slot 2 never does: a
    # finished slot emits nothing past its EOS
    assert [h.tokens.tolist() for h in hs] == [[7], [1, 7], [2, 4, 8, 9]]
    assert [h.hit_eos for h in hs] == [True, True, False]

    all_eos = np.full((4, 2), 7, np.int32)
    sess = make_session(all_eos, chunk=4)
    hs = [sess.submit([0], 10) for _ in range(2)]
    sess.drain()
    assert [h.tokens.tolist() for h in hs] == [[7], [7]]
    assert sess.clock.report()["host_syncs"] == 1   # one chunk was enough


@pytest.mark.parametrize("chunk", [4, 16])
def test_scan_decode_no_eos_and_partial_chunk(chunk):
    """K > max_new: one chunk, its steps past the budget skipped."""
    B = SCRIPT.shape[1]
    sess = make_session(chunk=chunk, eos_id=None)
    hs = [sess.submit([0], 3) for _ in range(B)]
    sess.drain()
    assert [h.tokens.tolist() for h in hs] == reference_loop(
        scripted_step(SCRIPT), None, None, np.zeros((B, 1)), 3)
    assert [h.tokens.size for h in hs] == [3, 3, 3]
    assert sess.clock.report()["host_syncs"] == 1


def test_decode_chunk_rejects_bad_k():
    with pytest.raises(ValueError):
        engine.make_session_chunk(scripted_step(SCRIPT), 0)


def test_decode_chunk_donates_buffers():
    chunk_fn = engine.make_session_chunk(
        scripted_step(np.zeros((64, 2), np.int32)), 8)
    state = engine.init_session_state({"kv": jnp.zeros((2, 4), jnp.float32)},
                                      2, 4)
    state = dict(state, active=jnp.ones((2,), bool),
                 budget=jnp.full((2,), 64, jnp.int32))
    leaf = state["cache"]["kv"]

    def one_chunk(state):
        out = chunk_fn(None, state)
        state = out[0]
        del out
        jax.block_until_ready(state)
        gc.collect()
        return state

    state = one_chunk(state)                # warmup (compile)
    assert leaf.is_deleted()                # the donated input is consumed
    state = one_chunk(state)                # first steady-state chunk
    baseline = len(jax.live_arrays())
    for _ in range(3):
        state = one_chunk(state)
        # steady state: no growth in live device allocations across chunks
        assert len(jax.live_arrays()) == baseline


def test_serve_stats_report_stall_and_chunk():
    sess = make_session(chunk=4)
    sess.submit([0], 4)
    st = sess.drain()
    assert st["chunk"] == 4
    for key in ("host_syncs", "dispatch_gap_s", "device_wait_s", "stall_pct"):
        assert key in st["stall"]


def test_session_slot_recycling_and_age():
    sess = make_session(chunk=2)
    first = [sess.submit([0], 4) for _ in range(3)]
    sess.drain()
    # all three slots saw one admission
    np.testing.assert_array_equal(np.asarray(sess.state["age"]), [1, 1, 1])
    late = sess.submit([1, 2], 3)             # prefill 1 then 2, emit 3
    sess.drain()
    assert late.done and not late.hit_eos
    # exactly one slot was recycled (age bumped), in place
    assert sorted(np.asarray(sess.state["age"]).tolist()) == [1, 1, 2]
    assert late.tokens.size == 3
    assert all(h.done for h in first)


def test_session_steady_state_allocates_nothing():
    sess = make_session(chunk=2, eos_id=None)
    sess.submit([0], 4)
    sess.drain()                              # compile + first cycle
    gc.collect()
    baseline = len(jax.live_arrays())
    for _ in range(3):                        # recycle the pool repeatedly
        sess.submit([0], 4)
        sess.drain()
        gc.collect()
        assert len(jax.live_arrays()) == baseline


# ----------------------------------------------------------------------------
# Streaming, cancel, backpressure, validation
# ----------------------------------------------------------------------------


def test_stream_yields_incremental_tokens_in_order():
    sess = make_session(chunk=2, eos_id=None)
    h = sess.submit([0], 4)
    seen = []
    dones = 0
    for handle, toks, done in sess.stream():
        assert handle is h
        seen.extend(toks.tolist())
        dones += done
    assert dones == 1
    np.testing.assert_array_equal(seen, h.result())
    assert h.tokens.size == 4


def test_poll_is_noop_when_idle():
    sess = make_session()
    assert sess.poll() == []
    assert sess.clock.report()["host_syncs"] == 0


def test_cancel_running_frees_slot_for_queued_work():
    script = np.full((8, 1), 3, np.int32)     # B=1: queue forms behind slot 0
    sess = make_session(script, chunk=2, eos_id=None)
    a = sess.submit([0], 8)
    b = sess.submit([0], 2)
    sess.poll()                               # a admitted + 2 tokens
    assert a.tokens.size == 2 and b.state == "queued"
    assert sess.cancel(a)
    sess.drain()
    assert a.cancelled and a.tokens.size == 2     # truncated, kept
    assert b.done and b.tokens.size == 2          # got the freed slot
    with pytest.raises(RequestFailed) as exc:     # typed failure, partial
        a.result()                                # tokens attached
    assert exc.value.reason == "cancelled"
    assert exc.value.partial_tokens.size == 2


def test_cancel_queued_never_runs():
    script = np.full((8, 1), 3, np.int32)
    sess = make_session(script, chunk=2, eos_id=None)
    a = sess.submit([0], 4)
    b = sess.submit([0], 4)
    sess.cancel(b)
    sess.drain()
    assert b.cancelled and b.tokens.size == 0
    assert a.done and a.tokens.size == 4


def test_submit_backpressure_and_validation():
    sess = make_session(max_queue=2)
    sess.submit([0], 1)
    sess.submit([0], 1)
    with pytest.raises(QueueFull):
        sess.submit([0], 1)
    with pytest.raises(ValueError):
        sess.submit([1] * 99, 1)              # prompt > max_prompt
    sess2 = make_session()
    sess2.max_seq = 4
    with pytest.raises(ValueError):
        sess2.submit([1, 2], 4)               # P + max_new > max_seq


def test_longest_prefix_admission_orders_by_prompt():
    script = np.full((8, 1), 3, np.int32)
    sess = make_session(script, chunk=2, eos_id=None,
                        admission="longest_prefix")
    a = sess.submit([1], 2)
    b = sess.submit([1, 2, 3], 2)
    sess.drain()
    assert list(sess.scheduler.admitted_order) == [b.id, a.id]


def test_idle_session_stats_are_zero_not_nan():
    st = make_session().stats()
    assert st["occupancy_pct"] == 0.0
    assert st["emitted_total"] == 0 and st["stall"]["host_syncs"] == 0
    zero = {"p50": 0.0, "p99": 0.0}
    for s in [st] + list(st["classes"].values()):
        assert s["ttft_ms"] == zero and s["latency_ms"] == zero


def test_session_stats_shape():
    sess = make_session(chunk=2, eos_id=None)
    hs = [sess.submit([0], 3) for _ in range(4)]
    st = sess.drain()
    assert st["requests_done"] == 4
    assert st["emitted_total"] == sum(h.tokens.size for h in hs) == 12
    assert 0.0 < st["occupancy_pct"] <= 100.0
    assert st["ttft_ms"]["p50"] >= 0.0
    assert st["latency_ms"]["p99"] >= st["latency_ms"]["p50"] >= 0.0
    assert st["stall"]["host_syncs"] == len(sess.chunk_latencies)


# ----------------------------------------------------------------------------
# Model path (slow): one-shot parity + staggered isolation
# ----------------------------------------------------------------------------


@pytest.mark.slow
def test_one_shot_session_bit_identical_to_serve_program():
    """`run()` against the per-token loop over the jitted decode step,
    with and without a prompt and an EOS: tokens, EOS padding,
    `emitted_per_slot` and `finished_slots`."""
    cluster = Cluster("xlstm-125m-smoke")
    cfg = cluster.arch
    program = lambda **kw: cluster.compile(ServeSessionProgram(
        slots=2, max_seq=16, max_new=8, chunk=4, **kw))
    params = program().init_params()
    decode = jax.jit(steps.make_decode_step(cfg, max_seq=16))

    def reference(prompt, eos_id):
        # column 0 is the start token 0 without a prompt, else the first
        # sampled token; 8 generated tokens follow it
        cache = steps.init_cache(cfg, 2, steps.decode_cache_len(cfg, 16))
        if prompt is None:
            rows = reference_loop(decode, params, cache, np.zeros((2, 1)),
                                  8, eos_id)
            return [[0] + r for r in rows]
        return reference_loop(decode, params, cache, prompt, 9, eos_id)

    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 3), 0,
                                           cfg.vocab))
    eos = reference(None, None)[0][4]         # a token the model emits
    for p, eos_id in ((None, None), (None, eos), (prompt, None), (prompt, eos)):
        rows = reference(p, eos_id)
        out = program(eos_id=eos_id).run(params=params, prompt=p)
        w = max(map(len, rows))
        pad = 0 if eos_id is None else eos_id
        np.testing.assert_array_equal(
            out["tokens"], [r + [pad] * (w - len(r)) for r in rows])
        st = out["stats"]
        assert st["emitted_per_slot"] == [len(r) - 1 for r in rows]
        if eos_id is None:
            assert "finished_slots" not in st
        else:
            emitted = [r if p is not None else r[1:] for r in rows]
            assert st["finished_slots"] == sum(eos_id in e for e in emitted)


@pytest.mark.slow
def test_model_decode_parity_and_donation():
    """Real model: chunk 1 vs chunk 4 — tokens and EOS bit-identical, one
    host sync a chunk."""
    cluster = Cluster("xlstm-125m-smoke")
    program = lambda **kw: cluster.compile(ServeSessionProgram(
        slots=2, max_seq=16, max_new=8, **kw))
    params = program(chunk=1).init_params()
    r1 = program(chunk=1).run(params=params)
    r4 = program(chunk=4).run(params=params)
    np.testing.assert_array_equal(r1["tokens"], r4["tokens"])
    assert r1["stats"]["stall"]["host_syncs"] == 8
    assert r4["stats"]["stall"]["host_syncs"] == 2

    # EOS parity with a token the model really emits
    eos = int(r1["tokens"][0, 4])
    re1 = program(chunk=1, eos_id=eos).run(params=params)
    re4 = program(chunk=4, eos_id=eos).run(params=params)
    np.testing.assert_array_equal(re1["tokens"], re4["tokens"])
    assert (re1["stats"]["emitted_per_slot"]
            == re4["stats"]["emitted_per_slot"])
    assert re1["stats"]["finished_slots"] == re4["stats"]["finished_slots"]


@pytest.mark.slow
def test_staggered_requests_match_isolated_decode():
    """Slots at independent positions (the continuous-batching invariant):
    a request admitted into a recycled slot mid-session decodes the same
    tokens it would get alone in a fresh pool."""
    cluster = Cluster("qwen3-14b-smoke")      # attention arch: per-slot KV pos
    prog = cluster.compile(ServeSessionProgram(slots=2, max_seq=32,
                                               max_prompt=8, chunk=4))
    params = prog.init_params()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cluster.arch.vocab, size=n).astype(np.int32)
               for n in (3, 5, 2, 4)]
    lens = [6, 9, 5, 7]

    isolated = []
    for p, n in zip(prompts, lens):
        s = prog.open(params=params)
        h = s.submit(p, n)
        s.drain()
        isolated.append(h.tokens.tolist())

    sess = prog.open(params=params)
    hs = [sess.submit(p, n) for p, n in zip(prompts, lens)]
    st = sess.drain()
    assert [h.tokens.tolist() for h in hs] == isolated
    assert st["requests_done"] == 4
    # four requests through two slots: both slots recycled at least once
    assert np.asarray(sess.state["age"]).sum() == 4


@pytest.mark.slow
def test_api_serve_routes_through_session():
    from repro import api
    out = api.serve("xlstm-125m", batch=2, max_seq=16, max_new=4)
    assert out["tokens"].shape == (2, 5)
    st = out["stats"]
    assert st["emitted_per_slot"] == [4, 4]
    assert st["stall"]["host_syncs"] == 4     # chunk 1: a sync a token
    assert st["requests_done"] == 2

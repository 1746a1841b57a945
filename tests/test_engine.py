"""Device-resident execution engine (runtime/engine.py).

Acceptance coverage: the scan-compiled K-step decode is bit-identical to
the per-token host loop (tokens, EOS masking/early-stop, emitted_per_slot)
while cutting host syncs from O(T) to O(T/K); steady-state decode chunks
allocate no new device buffers (donation); the chunked train path matches
the per-step loop and samples straggler/logging at chunk granularity; the
StallClock ledger behaves; every Pallas kernel is compiled under the VMEM
limit the autotuner budgets against.
"""

import math
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import Cluster, ServeProgram, TrainProgram
from repro.models import steps
from repro.runtime.engine import (DecodeEngine, StallClock, make_decode_chunk,
                                  make_train_chunk, stack_batches)
from repro.runtime.serve_loop import ServeLoop


# ----------------------------------------------------------------------------
# Scripted-decode parity: scan path == per-token loop, bit for bit
# ----------------------------------------------------------------------------


def scripted_step(script: np.ndarray):
    """Traceable decode_step emitting script[pos] (a (B,) row) per position."""
    table = jnp.asarray(script, jnp.int32)

    def decode_step(params, cache, batch):
        tok = jnp.take(table, batch["pos"], axis=0)[:, None]
        return cache, tok

    return decode_step


def fresh_cache(B: int):
    return {"kv": jnp.zeros((B, 4), jnp.float32)}


SCRIPT = np.array([[7, 1, 2], [3, 7, 4], [5, 6, 8], [9, 9, 9]], np.int32)


def run_loop(chunk: int, *, eos_id=7, max_new=4, script=SCRIPT):
    B = script.shape[1]
    loop = ServeLoop(scripted_step(script), None, fresh_cache(B),
                     batch_size=B, eos_id=eos_id, chunk=chunk)
    out = loop.generate(np.zeros((B, 1), np.int32), max_new=max_new)
    return out, loop.stats()


@pytest.mark.parametrize("chunk", [2, 3, 4, 16])
def test_scan_decode_matches_per_token_loop(chunk):
    ref_out, ref_st = run_loop(1)
    out, st = run_loop(chunk)
    np.testing.assert_array_equal(out, ref_out)
    assert st["emitted_per_slot"] == ref_st["emitted_per_slot"]
    assert st["finished_slots"] == ref_st["finished_slots"]
    # O(T) -> O(T/K) host syncs
    assert st["stall"]["host_syncs"] <= -(-4 // chunk)
    assert ref_st["stall"]["host_syncs"] == 4


def test_scan_decode_eos_early_stop_and_masking():
    out, st = run_loop(2)
    # slot 0 finishes at step 1, slot 1 at step 2; slot 2 never does
    np.testing.assert_array_equal(out[0], [0, 7, 7, 7, 7])
    np.testing.assert_array_equal(out[1], [0, 1, 7, 7, 7])
    np.testing.assert_array_equal(out[2], [0, 2, 4, 8, 9])
    assert st["emitted_per_slot"] == [1, 2, 4]

    all_eos = np.full((4, 2), 7, np.int32)
    ref_out, ref_st = run_loop(1, script=all_eos, max_new=10)
    out, st = run_loop(4, script=all_eos, max_new=10)
    np.testing.assert_array_equal(out, ref_out)
    assert out.shape == (2, 2)                  # stopped after one step
    assert st["emitted_per_slot"] == ref_st["emitted_per_slot"] == [1, 1]
    assert st["stall"]["host_syncs"] == 1       # one chunk was enough


def test_scan_decode_no_eos_and_partial_chunk():
    ref_out, _ = run_loop(1, eos_id=None, max_new=3)
    out, st = run_loop(4, eos_id=None, max_new=3)      # K > max_new
    np.testing.assert_array_equal(out, ref_out)
    assert out.shape == (3, 4)
    assert st["emitted_per_slot"] == [3, 3, 3]
    assert st["stall"]["host_syncs"] == 1


def test_decode_chunk_rejects_bad_k():
    with pytest.raises(ValueError):
        DecodeEngine(scripted_step(SCRIPT), 0)


def test_tail_chunk_compiles_short_scan_variant():
    """max_new % chunk != 0: the final chunk runs a short scan (exactly the
    remaining steps) instead of K iterations with every slot masked off."""
    script = np.tile(np.arange(24, dtype=np.int32)[:, None], (1, 2))
    eng = DecodeEngine(scripted_step(script), 16, eos_id=None)
    out, _, _, emitted = eng.generate(None, fresh_cache(2),
                                      np.zeros((2, 1), np.int32), max_new=20)
    assert out.shape == (2, 21)
    assert sorted(eng._chunk_fns) == [4, 16]        # steady + tail variant
    assert [n for _, n in eng.chunk_latencies] == [16, 4]
    # parity with the per-token loop
    ref, _ = run_loop(1, eos_id=None, max_new=20, script=script)
    np.testing.assert_array_equal(out, ref)
    # the tail variant is cached: a second generate re-uses both programs
    eng.generate(None, fresh_cache(2), np.zeros((2, 1), np.int32),
                 max_new=20)
    assert sorted(eng._chunk_fns) == [4, 16]


def test_tail_chunk_shorter_than_one_chunk():
    out, st = run_loop(16, eos_id=None, max_new=3)  # K > max_new: one short scan
    ref, _ = run_loop(1, eos_id=None, max_new=3)
    np.testing.assert_array_equal(out, ref)
    assert st["stall"]["host_syncs"] == 1


# ----------------------------------------------------------------------------
# Donation: steady-state decode chunks allocate nothing new
# ----------------------------------------------------------------------------


def test_decode_chunk_donates_buffers():
    import gc

    step = scripted_step(np.zeros((64, 2), np.int32))
    chunk_fn = make_decode_chunk(step, 8)
    cache = fresh_cache(2)
    leaf = cache["kv"]
    state = (cache, jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), bool),
             jnp.zeros((2,), jnp.int32))

    def one_chunk(state, i):
        out = chunk_fn(None, *state, jnp.asarray(8 * i, jnp.int32),
                       jnp.asarray(8, jnp.int32))
        state = out[:4]
        del out
        jax.block_until_ready(state)
        gc.collect()
        return state

    state = one_chunk(state, 0)             # warmup (compile)
    # the donated input buffers are consumed
    assert leaf.is_deleted()
    state = one_chunk(state, 1)             # first steady-state chunk
    baseline = len(jax.live_arrays())
    for i in range(2, 5):
        state = one_chunk(state, i)
        # steady state: no growth in live device allocations across chunks
        assert len(jax.live_arrays()) == baseline


@pytest.mark.slow
def test_model_decode_parity_and_donation():
    """Real model: K=1 loop vs scan engine — tokens and EOS bit-identical."""
    cluster = Cluster("xlstm-125m-smoke")
    p1 = cluster.compile(ServeProgram(batch=2, max_seq=16, max_new=8,
                                      chunk=1))
    params = p1.init_params()
    r1 = p1.run(params=params)
    r4 = cluster.compile(ServeProgram(batch=2, max_seq=16, max_new=8,
                                      chunk=4)).run(params=params)
    np.testing.assert_array_equal(r1["tokens"], r4["tokens"])
    assert r1["stats"]["stall"]["host_syncs"] == 8
    assert r4["stats"]["stall"]["host_syncs"] == 2

    # EOS parity with a token the model really emits
    eos = int(r1["tokens"][0, 4])
    re1 = cluster.compile(ServeProgram(batch=2, max_seq=16, max_new=8,
                                       chunk=1, eos_id=eos)).run(params=params)
    re4 = cluster.compile(ServeProgram(batch=2, max_seq=16, max_new=8,
                                       chunk=4, eos_id=eos)).run(params=params)
    np.testing.assert_array_equal(re1["tokens"], re4["tokens"])
    assert (re1["stats"]["emitted_per_slot"]
            == re4["stats"]["emitted_per_slot"])
    assert re1["stats"]["finished_slots"] == re4["stats"]["finished_slots"]


_HLO_RESULT = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(", re.M)


@pytest.mark.parametrize("arch", ["qwen3-14b-smoke",
                                  # recurrent state carried beside the pool
                                  "falcon-h1-34b-smoke"])
def test_paged_chunk_program_copies_no_pool(arch):
    """The compiled paged session chunk touches the KV pool only through
    the row scatter and the page gather: no `copy`, `dynamic-slice` or
    `dynamic-update-slice`, fused or not, yields a whole pool leaf or one
    layer of it (sizes compared by element count, so a bitcast view of
    the pool counts too). The pool's page count is prime so that no other
    buffer of the program matches by accident. (The recurrent state's own
    check is compiled for the TPU, in `test_tpu_compile.py`: the CPU's
    compiler copies what the TPU's updates in place.)"""
    from repro.cluster import ServeSessionProgram

    cluster = Cluster(arch)
    prog = cluster.compile(ServeSessionProgram(
        slots=4, max_seq=48, max_prompt=16, chunk=4, paged=True,
        page_size=4, n_pages=53))
    state = prog._make_state()
    pool = state["cache"]["blocks"]["sub0"]["k"]
    n_super, n_pages = pool.shape[:2]
    assert n_super >= 2 and n_pages == 53
    sizes = {pool.size, pool.size // n_super}
    text = prog._chunk_fn.lower(prog.init_params(), state).compile().as_text()
    ops = [(dims, op) for dims, op in _HLO_RESULT.findall(text)
           if math.prod(int(d) for d in dims.split(",") if d) in sizes]
    assert ops, "the pool's own buffers must be found"
    bad = [(dims, op) for dims, op in ops
           if op in ("copy", "dynamic-slice", "dynamic-update-slice")]
    assert not bad, bad


# ----------------------------------------------------------------------------
# Chunked training: scan-of-steps matches the per-step loop
# ----------------------------------------------------------------------------


def _toy_step(state, batch):
    w = state["w"] + batch["x"].sum()
    return {"w": w}, {"loss": w * 0.5}


def test_train_chunk_matches_per_step():
    batches = [{"x": jnp.full((2,), float(i))} for i in range(4)]
    state = {"w": jnp.zeros(())}
    for b in batches:
        state, metrics = _toy_step(state, b)
    chunk = make_train_chunk(_toy_step, donate=False)
    cstate, cmetrics = chunk({"w": jnp.zeros(())}, stack_batches(batches))
    np.testing.assert_allclose(np.asarray(cstate["w"]), np.asarray(state["w"]))
    assert cmetrics["loss"].shape == (4,)
    np.testing.assert_allclose(float(cmetrics["loss"][-1]),
                               float(metrics["loss"]))


@pytest.mark.slow
def test_train_program_steps_per_sync(tmp_path):
    cluster = Cluster("xlstm-125m-smoke")
    r1 = cluster.compile(TrainProgram(
        num_steps=6, batch=2, seq=16, log_every=3,
        checkpoint_dir=str(tmp_path / "a"))).run()
    r3 = cluster.compile(TrainProgram(
        num_steps=6, batch=2, seq=16, log_every=3, steps_per_sync=3,
        checkpoint_dir=str(tmp_path / "b"))).run()
    assert r3["final_step"] == r1["final_step"] == 6
    assert r3["steps_per_sync"] == 3
    # host syncs collapse to one per chunk
    assert r1["stall"]["host_syncs"] == 6
    assert r3["stall"]["host_syncs"] == 2
    # logger samples at chunk granularity, same sampled losses
    assert [m["step"] for m in r3["metrics"]] == [3, 6]
    np.testing.assert_allclose([m["loss"] for m in r3["metrics"]],
                               [m["loss"] for m in r1["metrics"]],
                               rtol=1e-5)
    assert all(m["steps_in_chunk"] == 3 for m in r3["metrics"])


# ----------------------------------------------------------------------------
# Stall accounting
# ----------------------------------------------------------------------------


def test_stall_clock_ledger():
    clock = StallClock()
    clock.dispatch()
    clock.sync(jnp.zeros(()))
    time.sleep(0.02)                        # host-side gap (the stall)
    clock.dispatch()
    clock.sync(jnp.zeros(()))
    rep = clock.report()
    assert rep["host_syncs"] == 2
    assert rep["dispatch_gap_s"] >= 0.02
    assert 0.0 < rep["stall_pct"] <= 100.0
    assert rep["wall_s"] >= rep["dispatch_gap_s"]


def test_serve_stats_report_stall_and_chunk():
    _, st = run_loop(4)
    assert st["chunk"] == 4
    for key in ("host_syncs", "dispatch_gap_s", "device_wait_s", "stall_pct"):
        assert key in st["stall"]


# ----------------------------------------------------------------------------
# Pallas compile hints: the VMEM limit the autotuner budgets against
# ----------------------------------------------------------------------------


def _captured_pallas_kwargs(monkeypatch, pipe) -> dict:
    from repro.kernels import pipeline as pp
    captured = {}

    def fake_pallas_call(body, **kwargs):
        captured.update(kwargs)
        return lambda *operands: None

    monkeypatch.setattr(pp.pl, "pallas_call", fake_pallas_call)
    pipe.pallas_call()
    return captured


def test_pallas_call_compiles_under_the_autotuner_budget(monkeypatch):
    """Every kernel is compiled under the scoped VMEM limit the autotuner
    admits blockings against, so a modeled pick is one the compiler takes."""
    from repro.kernels import matmul
    from repro.kernels import pipeline as pp

    pipe = matmul.build_pipeline(256, 512, 1024, jnp.bfloat16, bm=128,
                                 bn=256, bk=512, dtype_bytes=2)
    kw = _captured_pallas_kwargs(monkeypatch, pipe)
    params = kw["compiler_params"]
    assert params.vmem_limit_bytes == pp.VMEM_BUDGET_BYTES
    assert params.dimension_semantics == ("parallel", "parallel", "arbitrary")
    assert kw["grid"] == (2, 2, 2)
    assert kw["cost_estimate"].flops == 2 * 256 * 512 * 1024
    assert pipe.cost.vmem_bytes <= pp.VMEM_BUDGET_BYTES


def test_pallas_call_without_cost_model_has_no_estimate(monkeypatch):
    from repro.kernels import pipeline as pp

    tile = pp.TileSpec((128, 128), lambda i: (0, 0))
    pipe = pp.KernelPipeline(
        "synthetic", lambda *refs: None, grid=(pp.GridAxis("i", 1),),
        in_tiles=[tile], out_tiles=tile,
        out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32))
    kw = _captured_pallas_kwargs(monkeypatch, pipe)
    assert kw["cost_estimate"] is None
    assert kw["compiler_params"].vmem_limit_bytes == pp.VMEM_BUDGET_BYTES

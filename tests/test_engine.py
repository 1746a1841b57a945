"""Device-resident execution engine (runtime/engine.py).

Acceptance coverage: the compiled paged session chunk copies no pool
buffer; the chunked train path matches the per-step loop and samples
straggler/logging at chunk granularity; the StallClock ledger behaves;
every Pallas kernel is compiled under the VMEM limit the autotuner budgets
against. (The session chunk's decode parity, EOS handling and donation
are pinned in `test_serve_session.py`.)
"""

import math
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import Cluster, TrainProgram
from repro.runtime.engine import StallClock, make_train_chunk, stack_batches


# ----------------------------------------------------------------------------
# The paged session chunk program
# ----------------------------------------------------------------------------


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

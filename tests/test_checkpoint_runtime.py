"""Checkpoint manager + runtime (train loop, straggler, elastic) tests."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import compat
from repro.configs import get
from repro.models import steps
from repro.runtime import TrainLoop, TrainLoopConfig, CompileCache
from repro.runtime.coordination import Coordinator, replan_mesh_shape
from repro.runtime.train_loop import StragglerDetector


def small_state():
    return {"params": {"w": jnp.arange(8, dtype=jnp.float32),
                       "b": jnp.ones((2, 3), jnp.bfloat16)},
            "opt": {"step": jnp.asarray(5, jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=False)
    state = small_state()
    mgr.save(10, state)
    assert mgr.latest_step() == 10
    restored = mgr.restore(10, jax.tree.map(jnp.zeros_like, state))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float64),
                                      np.asarray(b, np.float64))


def test_checkpoint_async_and_gc(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_save=True)
    for s in (1, 2, 3, 4):
        mgr.save(s, small_state())
    mgr.wait()
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_async_write_failure_surfaces(tmp_path, monkeypatch):
    """A failed background write must raise on wait() (once) and on the
    next save() — a dropped checkpoint is never silent."""
    mgr = CheckpointManager(tmp_path, async_save=True)

    def boom(step, snapshot):
        raise OSError("disk full")

    monkeypatch.setattr(mgr, "_write_step", boom)
    mgr.save(1, small_state())
    with pytest.raises(OSError, match="disk full"):
        mgr.wait()
    mgr.wait()                              # raised once, then cleared
    mgr.save(2, small_state())              # fails in the background again
    with pytest.raises(OSError, match="disk full"):
        mgr.save(3, small_state())          # surfaced before the new write
    monkeypatch.undo()
    mgr.save(4, small_state())              # recovered: a real write lands
    mgr.wait()
    assert mgr.latest_step() == 4


def test_checkpoint_atomicity(tmp_path):
    """A stale tmp dir must never be visible as a checkpoint."""
    mgr = CheckpointManager(tmp_path, async_save=False)
    (tmp_path / ".tmp-99").mkdir()
    (tmp_path / ".tmp-99" / "garbage").write_text("x")
    mgr.save(1, small_state())
    assert mgr.all_steps() == [1]


def test_elastic_restore_resharding(tmp_path):
    """Save with one layout, restore onto explicit shardings (new mesh)."""
    mgr = CheckpointManager(tmp_path, async_save=False)
    state = small_state()
    mgr.save(3, state)
    mesh = compat.make_mesh((1,), ("data",))
    sh = jax.tree.map(
        lambda _: jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()), state)
    restored = mgr.restore(3, state, sh)
    assert restored["params"]["w"].sharding.mesh.shape["data"] == 1


@pytest.mark.slow
def test_train_loop_end_to_end_with_resume(tmp_path):
    cfg = get("xlstm-125m-smoke")
    state = steps.init_train_state(cfg, jax.random.PRNGKey(0), max_seq=16)
    ts = jax.jit(steps.make_train_step(cfg))

    def batches():
        k = jax.random.PRNGKey(1)
        while True:
            yield {"tokens": jax.random.randint(k, (2, 16), 0, cfg.vocab),
                   "labels": jax.random.randint(k, (2, 16), 0, cfg.vocab)}

    loop_cfg = TrainLoopConfig(total_steps=6, checkpoint_every=3,
                               log_every=2, checkpoint_dir=str(tmp_path))
    loop = TrainLoop(loop_cfg, ts, state, batches())
    report = loop.run(start_step=0)
    assert report["final_step"] == 6
    # resume continues from latest checkpoint
    loop2 = TrainLoop(TrainLoopConfig(total_steps=8, checkpoint_every=3,
                                      checkpoint_dir=str(tmp_path)),
                      ts, jax.tree.map(jnp.zeros_like, state), batches())
    report2 = loop2.run()
    assert report2["final_step"] == 8


def test_straggler_detector():
    det = StragglerDetector(z=3.0, warmup=5)
    for i in range(20):
        det.observe(i, 0.1 + 0.001 * (i % 3))
    assert not det.events
    assert det.observe(20, 1.5)
    assert det.events[0]["step"] == 20


def test_compile_cache_hits():
    cache = CompileCache()
    calls = []
    for _ in range(3):
        cache.get(("step", "a"), lambda: calls.append(1) or "exe")
    assert cache.hits == 2 and cache.misses == 1 and len(calls) == 1


@pytest.fixture
def _restore_cache_dir():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_persistent_cache_honours_env(monkeypatch, tmp_path,
                                      _restore_cache_dir):
    from repro.runtime import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_persistent_cache() == str(tmp_path)
    # jax reads the variable itself; the helper changes nothing
    assert jax.config.jax_compilation_cache_dir == before


def test_persistent_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                         _restore_cache_dir):
    from pathlib import Path

    from repro.runtime import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_persistent_cache()
    assert compile_cache.use_persistent_cache() == first
    repo = Path(__file__).resolve().parents[1]
    assert first == str(repo / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_coordinator_and_replan():
    coord = Coordinator(n_hosts=64)
    seen = []
    coord.subscribe(lambda ev: seen.append(ev.kind))
    coord.emit("leave", "host-3")
    assert coord.n_hosts == 63 and seen == ["leave"]
    assert replan_mesh_shape(256, model_parallel=16) == (16, 16)
    assert replan_mesh_shape(240, model_parallel=16) == (8, 16)
    assert replan_mesh_shape(512, model_parallel=16, pods=2) == (2, 16, 16)
    with pytest.raises(ValueError):
        replan_mesh_shape(8, model_parallel=16)

"""The Cluster/Session façade and the KernelPolicy dispatch layer.

Covers the policy satellites (scoped override nesting, per-op overrides,
interpret-mode equivalence with the REPRO_INTERPRET env path, policy-
respected dispatch in tuned_call), the Cluster programs + compile cache,
the api.* shims (identical report keys and matching loss/tokens vs the
Cluster path on a smoke config), and the one-shot session result's EOS
handling.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import (Cluster, DryRunProgram, KernelPolicy,
                           ServeSessionProgram, TrainProgram, current_policy,
                           default_policy, use_policy)
from repro.configs import registry
from repro.kernels import ops, ref
from repro.models import steps


def rand(seed, shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


# ----------------------------------------------------------------------------
# KernelPolicy: scoping, overrides, interpret equivalence, tuned_call
# ----------------------------------------------------------------------------


def test_policy_scope_nesting():
    assert current_policy().mode == "tuned"          # env default
    with use_policy("fused") as outer:
        assert current_policy() is outer
        assert current_policy().fused
        with use_policy(KernelPolicy(mode="reference")) as inner:
            assert current_policy() is inner
            assert current_policy().mode == "reference"
            assert not current_policy().fused
        assert current_policy() is outer             # inner scope popped
    assert current_policy().mode == "tuned"


def test_policy_validation():
    with pytest.raises(ValueError):
        KernelPolicy(mode="warp-speed")
    with pytest.raises(ValueError):
        KernelPolicy(overrides={"matmul": "warp-speed"})
    with pytest.raises(TypeError):
        KernelPolicy(overrides={"matmul": 42})


def test_policy_per_op_override_routes_to_reference():
    a, b = rand(0, (16, 24)), rand(1, (24, 16))
    pol = KernelPolicy(mode="tuned", overrides={"matmul": "reference"})
    assert pol.mode_for("matmul") == "reference"
    assert pol.mode_for("axpy") == "tuned"
    with use_policy(pol):
        got = ops.matmul(a, b)
        other = ops.axpy(2.0, a, a)
    assert pol.stats["ref_calls"] == 1               # matmul short-circuited
    assert pol.stats["pallas_calls"] == 1            # axpy ran the kernel
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.matmul(a, b)),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(other),
                               np.asarray(ref.axpy(2.0, a, a)),
                               rtol=1e-6, atol=1e-6)


def test_interpret_mode_matches_env_path():
    """KernelPolicy(mode='interpret') == the legacy REPRO_INTERPRET env."""
    a, b = rand(2, (16, 16)), rand(3, (16, 16))
    with use_policy("interpret") as pol:
        assert pol.interpret_for("matmul")
        got_policy = ops.matmul(a, b)
    old = os.environ.get("REPRO_INTERPRET")
    try:
        os.environ["REPRO_INTERPRET"] = "1"
        assert default_policy().mode == "interpret"  # env -> default policy
        got_env = ops.matmul(a, b)                   # no scope: env default
    finally:
        if old is None:
            os.environ.pop("REPRO_INTERPRET", None)
        else:
            os.environ["REPRO_INTERPRET"] = old
    assert default_policy().mode == "tuned"
    np.testing.assert_array_equal(np.asarray(got_policy), np.asarray(got_env))


_ASK_FOR_INTERPRET = {
    "mode": lambda mp: KernelPolicy(mode="interpret"),
    "override": lambda mp: KernelPolicy(overrides={"matmul": "interpret"}),
    "REPRO_INTERPRET": lambda mp: (mp.setenv("REPRO_INTERPRET", "1"),
                                   default_policy()),
    "REPRO_KERNEL_POLICY": lambda mp: (
        mp.setenv("REPRO_KERNEL_POLICY", "interpret"), current_policy()),
}


@pytest.mark.parametrize("how", sorted(_ASK_FOR_INTERPRET))
def test_interpret_refused_on_tpu_backend(monkeypatch, how):
    """Interpret mode is for the CPU: on a TPU backend every way of asking
    for it raises instead of running the interpreter behind the device."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU only"):
        _ASK_FOR_INTERPRET[how](monkeypatch)


def test_tpu_backend_never_interprets(monkeypatch):
    built_on_cpu = KernelPolicy(mode="interpret")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for mode in ("tuned", "fused"):
        assert KernelPolicy(mode=mode).interpret_for("matmul") is False
    with pytest.raises(RuntimeError, match="CPU only"):
        built_on_cpu.interpret_for("matmul")


def test_tuned_call_respects_policy():
    registry.KERNEL_TUNES.clear()
    a, b = rand(4, (48, 32)), rand(5, (32, 40))
    want = np.asarray(ref.matmul(a, b))

    # (1) reference override short-circuits tuned_call entirely
    with use_policy(KernelPolicy(overrides={"matmul": "reference"})) as pol:
        got = ops.tuned_call("matmul", a, b)
    assert pol.stats == {"ref_calls": 1}
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)

    # (2) pinned blocks skip the registry (block_overrides counted)
    pinned = KernelPolicy(overrides={"matmul": {"bm": 16, "bn": 8, "bk": 32}})
    with use_policy(pinned):
        got = ops.tuned_call("matmul", a, b)
    assert pinned.stats["block_overrides"] == 1
    assert "tune_hits" not in pinned.stats
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)

    # (3) default: autotune-on-miss then registry hit, both counted
    with use_policy("tuned") as pol:
        ops.tuned_call("matmul", a, b)
        ops.tuned_call("matmul", a, b)
    assert pol.stats["tune_misses"] == 1
    assert pol.stats["tune_hits"] == 1
    key = pp_shape_key({"m": 48, "k": 32, "n": 40})
    assert registry.get_kernel_tune("matmul", key) is not None


def pp_shape_key(shapes):
    from repro.kernels import pipeline as pp
    return pp.shape_key(shapes)


# ----------------------------------------------------------------------------
# Cluster: plan, policy scope, compile cache
# ----------------------------------------------------------------------------


def test_cluster_plan_matches_api_plan():
    from repro import api
    from repro.core import compat
    mesh = compat.abstract_mesh((2, 2), ("data", "model"))
    assert Cluster("qwen3-14b", mesh).plan() == api.plan("qwen3-14b", mesh)


def test_cluster_policy_scope_sets_cluster_default():
    cluster = Cluster()                              # kernel-only cluster
    assert cluster.kernel_policy.mode == "tuned"
    with cluster.policy("fused") as pol:
        assert cluster.kernel_policy is pol
        assert current_policy() is pol
    assert cluster.kernel_policy.mode == "tuned"
    with cluster.policy(mode="tuned", overrides={"matmul": "reference"}) as p:
        assert p.mode_for("matmul") == "reference"
    with pytest.raises(ValueError):
        cluster.plan()                               # no arch attached


def test_cluster_compile_cache_memoizes_programs():
    cluster = Cluster("xlstm-125m-smoke")
    spec = ServeSessionProgram(slots=2, max_seq=16, max_new=2)
    p1 = cluster.compile(spec)
    p2 = cluster.compile(ServeSessionProgram(slots=2, max_seq=16, max_new=2))
    assert p1 is p2
    assert cluster.compile_cache.hits == 1
    # a different spec, and a different policy scope, compile fresh
    p3 = cluster.compile(ServeSessionProgram(slots=4, max_seq=16, max_new=2))
    assert p3 is not p1
    with cluster.policy("fused"):
        p4 = cluster.compile(ServeSessionProgram(slots=2, max_seq=16,
                                                 max_new=2))
    assert p4 is not p1
    assert p4.policy.fused


def test_cluster_rejects_unknown_program():
    with pytest.raises(TypeError):
        Cluster("xlstm-125m-smoke").compile({"not": "a program"})


def test_dry_run_refuses_a_chunked_decode_cell_outside_the_session():
    """Only the session runs a K-step decode chunk, so only its cell lowers
    one."""
    prog = Cluster("qwen3-14b-smoke").compile(
        DryRunProgram(shape="decode_32k", decode_chunk=4))
    with pytest.raises(ValueError, match="session"):
        prog.run()


# ----------------------------------------------------------------------------
# Shim equivalence: api.train/serve == the Cluster path (acceptance)
# ----------------------------------------------------------------------------


@pytest.mark.slow
def test_api_shims_match_cluster_programs(tmp_path):
    from repro import api
    r_api = api.train("xlstm-125m", num_steps=3, batch=2, seq=16,
                      checkpoint_dir=str(tmp_path / "api"))
    cluster = Cluster("xlstm-125m-smoke")
    r_clu = cluster.compile(TrainProgram(
        num_steps=3, batch=2, seq=16,
        checkpoint_dir=str(tmp_path / "clu"))).run()
    assert sorted(r_api.keys()) == sorted(r_clu.keys())
    losses = lambda r: [m["loss"] for m in r["metrics"]]
    np.testing.assert_allclose(losses(r_api), losses(r_clu), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(r_api["params"]),
                    jax.tree.leaves(r_clu["params"])):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    s_api = api.serve("xlstm-125m", r_api["params"], batch=2, max_seq=16,
                      max_new=4)
    s_clu = cluster.compile(ServeSessionProgram(slots=2, max_seq=16,
                                                max_new=4, chunk=1)) \
        .run(params=r_clu["params"])
    assert sorted(s_api.keys()) == sorted(s_clu.keys())
    np.testing.assert_array_equal(s_api["tokens"], s_clu["tokens"])


@pytest.mark.slow
def test_train_program_report_and_plan(tmp_path):
    cluster = Cluster("xlstm-125m-smoke")
    prog = cluster.compile(TrainProgram(num_steps=2, batch=2, seq=16,
                                        checkpoint_dir=str(tmp_path)))
    assert prog.plan() == cluster.plan()
    rep = prog.report()
    assert rep["kind"] == "train" and rep["arch"] == "xlstm-125m-smoke"
    assert "result" not in rep                        # not run yet
    prog.run()
    rep = prog.report()
    assert rep["result"]["final_step"] == 2
    assert "params" not in rep["result"]              # arrays stripped


# ----------------------------------------------------------------------------
# The one-shot session result: EOS padding, finished_slots, emitted_per_slot
# ----------------------------------------------------------------------------


def _one_shot(monkeypatch, script, max_new, eos_id=None):
    """`CompiledServeSession.run()` with the model's decode step swapped
    for a scripted one: slot i emits script[pos, i] at position pos."""
    table = jnp.asarray(script, jnp.int32)

    def decode_step(params, cache, batch):
        pos = jnp.clip(batch["pos"], 0, table.shape[0] - 1)
        return cache, jnp.diagonal(jnp.take(table, pos, axis=0))[:, None]

    monkeypatch.setattr(steps, "make_decode_step",
                        lambda cfg, **kw: decode_step)
    program = Cluster("xlstm-125m-smoke").compile(ServeSessionProgram(
        slots=table.shape[1], max_seq=16, max_new=max_new, eos_id=eos_id,
        chunk=2))
    return program.run()


def test_serve_loop_eos_masks_and_stops(monkeypatch):
    # slot 0 hits EOS (=7) at step 0, slot 1 at step 1; slot 2 never does
    script = np.array([[7, 1, 2], [3, 7, 4], [5, 6, 8], [9, 9, 9]])
    out = _one_shot(monkeypatch, script, max_new=4, eos_id=7)
    # a finished row is padded with EOS
    np.testing.assert_array_equal(out["tokens"], [[0, 7, 7, 7, 7],
                                                  [0, 1, 7, 7, 7],
                                                  [0, 2, 4, 8, 9]])
    st = out["stats"]
    assert st["emitted_per_slot"] == [1, 2, 4]
    assert st["finished_slots"] == 2


def test_serve_loop_eos_early_stop(monkeypatch):
    script = np.array([[7, 7], [1, 1]])
    out = _one_shot(monkeypatch, script, max_new=10, eos_id=7)
    assert out["tokens"].shape == (2, 2)              # stopped after step 0
    st = out["stats"]
    assert st["stall"]["host_syncs"] == 1
    assert st["emitted_per_slot"] == [1, 1]
    assert st["finished_slots"] == 2


def test_serve_loop_no_eos_unchanged(monkeypatch):
    script = np.full((4, 2), 7)
    out = _one_shot(monkeypatch, script, max_new=4)
    assert out["tokens"].shape == (2, 5)              # eos disabled: full run
    assert out["stats"]["emitted_per_slot"] == [4, 4]
    assert "finished_slots" not in out["stats"]

"""End-to-end system behaviour: train -> checkpoint -> resume -> serve,
composed exactly as examples/ and the launcher wire it together."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.core import compat
from repro.data import Distributor, Splitter, SyntheticLMStream
from repro.data.pipeline import BatchSpec
from repro.models import steps
from repro.runtime import ServeLoop, TrainLoop, TrainLoopConfig


@pytest.mark.slow
def test_train_then_serve_roundtrip(tmp_path):
    """Train a smoke model a few steps, checkpoint, reload, decode."""
    cfg = get("qwen3-14b-smoke")
    S = 16
    key = jax.random.PRNGKey(0)
    state = steps.init_train_state(cfg, key, max_seq=S)
    ts = jax.jit(steps.make_train_step(cfg))

    spec = BatchSpec(global_batch=2, seq_len=S, vocab=cfg.vocab)
    stream = SyntheticLMStream(spec, seed=3)
    mesh = compat.make_mesh((1,), ("data",))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    dist = Distributor(mesh, Splitter(mesh, ("data",)))

    def batches():
        step = 0
        while True:
            yield dist.materialize(stream, step, sh)
            step += 1

    loop = TrainLoop(TrainLoopConfig(total_steps=4, checkpoint_every=2,
                                     checkpoint_dir=str(tmp_path)),
                     ts, state, batches())
    report = loop.run(start_step=0)
    assert report["final_step"] == 4
    assert all(np.isfinite(m["loss"]) for m in report["metrics"])

    # restore params and serve a batch of 2 greedily
    restored = loop.ckpt.restore(4, state)
    params = restored["params"]
    cache = steps.init_cache(cfg, 2, S)
    dec = jax.jit(steps.make_decode_step(cfg, max_seq=S))
    serve = ServeLoop(dec, params, cache, batch_size=2)
    out = serve.generate(np.zeros((2, 1), np.int32), max_new=5)
    assert out.shape == (2, 6)
    assert (out >= 0).all() and (out < cfg.vocab).all()
    stats = serve.stats()
    # decode_steps counts the warmup-dropped samples the percentiles use
    # (5 generated tokens, first step dropped as compile warmup)
    assert stats["decode_steps"] == 4
    assert stats["tokens_per_s_per_slot"] > 0


@pytest.mark.slow
def test_decode_consistent_with_prefill():
    """Greedy next-token from decode-with-cache must match prefill argmax
    when the cache was filled by decoding the same prompt."""
    cfg = get("xlstm-125m-smoke")
    S = 8
    key = jax.random.PRNGKey(1)
    params = steps.init_params(cfg, key, max_seq=S)
    prompt = jax.random.randint(key, (2, S), 0, cfg.vocab)

    pf = jax.jit(steps.make_prefill_step(cfg))
    want_next = np.asarray(pf(params, {"tokens": prompt}))

    cache = steps.init_cache(cfg, 2, S)
    dec = jax.jit(steps.make_decode_step(cfg, max_seq=S))
    tok = None
    for t in range(S):
        cache, tok = dec(params, cache,
                         {"tokens": prompt[:, t:t + 1],
                          "pos": jnp.asarray(t, jnp.int32)})
    np.testing.assert_array_equal(np.asarray(tok)[:, 0], want_next)


def test_region_plan_places_weights_and_state():
    """The hybrid addressing plan: weights INTERLEAVED (data x model),
    optimizer/activations SEQUENTIAL (batch axes), norms replicated."""
    from repro.core import addressing
    mesh = compat.abstract_mesh((2, 2), ("data", "model"))
    rules = addressing.default_rules(mesh)
    # an FFN weight: embed x ffn -> (data, model)
    spec = rules.spec_for(("embed", "ffn"), (64, 64), mesh)
    assert spec == jax.sharding.PartitionSpec("data", "model")
    # a norm scale: replicated
    assert rules.spec_for(("norm",), (64,), mesh) == jax.sharding.PartitionSpec()
    # a batch tensor: sequential region (owner-local)
    assert rules.spec_for(("batch", "seq"), (8, 64), mesh) == \
        jax.sharding.PartitionSpec("data")


def _chip_smoke_path():
    from pathlib import Path
    return Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _chip_smoke():
    import importlib.util

    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  _chip_smoke_path())
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    return cs


def test_chip_smoke_refuses_the_cpu(tmp_path):
    """chip_smoke.py has no CPU path: on the CPU platform it exits
    non-zero and prints no result line."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(_chip_smoke_path())],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU backend" in out.stderr


def test_chip_smoke_workload_guarantees_prefix_hits():
    """The smoke workload's shape: 16 requests, prompts of 32-256 tokens,
    answers of 32-64, half sharing a 128-token preamble; and the first
    wave's sharing requests finish a whole chunk before any other
    first-wave request, so their preamble pages are published before the
    second wave is admitted."""
    cs = _chip_smoke()
    reqs = cs.workload(151936)
    assert len(reqs) == cs.N_REQUESTS == 16
    assert all(32 <= p.size <= cs.MAX_PROMPT for p, _ in reqs)
    assert all(32 <= n <= 64 for _, n in reqs)
    pre = reqs[0][0][:cs.PREAMBLE]
    shares = [np.array_equal(p[:cs.PREAMBLE], pre) for p, _ in reqs]
    assert sum(shares) == len(reqs) // 2
    steps = lambda i: reqs[i][0].size + reqs[i][1] - 1
    first = range(cs.SLOTS)
    last_shared = max(steps(i) for i in first if shares[i])
    first_other = min(steps(i) for i in first if not shares[i])
    assert last_shared // cs.CHUNK < first_other // cs.CHUNK
    assert any(shares[cs.SLOTS:])
    assert cs.workload(151936)[5][0].tolist() == reqs[5][0].tolist()


@pytest.mark.parametrize("fault", [None, "lost_ffn_residual"])
def test_chip_smoke_fused_logits_check(monkeypatch, fault):
    """The tuned-vs-fused logits check passes on the intact fused decode
    path (rounding only) and fails on a wiring fault in it: a fused FFN
    down-projection that drops its residual."""
    from repro.models import blocks

    cs = _chip_smoke()
    cfg = get("qwen3-14b-smoke")
    monkeypatch.setattr(cs, "MAX_SEQ", 64)
    if fault:
        real = blocks.fused_matmul_residual
        monkeypatch.setattr(blocks, "fused_matmul_residual",
                            lambda a, w, res: real(a, w, res * 0))
    params = steps.init_params(cfg, jax.random.PRNGKey(0), max_seq=64)
    rng = np.random.default_rng(0)
    prompt, generated = (rng.integers(0, cfg.vocab, n) for n in (9, 3))
    if fault:
        with pytest.raises(cs.SmokeFailure, match="fused decode logits"):
            cs.fused_logits_check(cfg, params, prompt, generated)
    else:
        cs.fused_logits_check(cfg, params, prompt, generated)


def test_chip_smoke_first_difference():
    cs = _chip_smoke()
    a = [np.array([1, 2, 3]), np.array([4, 5, 6]), np.array([7, 8, 9])]
    b = [np.array([1, 2, 3]), np.array([4, 0, 6]), np.array([0, 8, 9])]
    assert cs.first_difference(a, b) == (1, 1)
    assert cs.first_difference(a, a) == (0, 0)


def test_decode_step_logits_match_greedy_token():
    """`make_decode_step(logits=True)` returns the logits the greedy step
    takes its argmax of, with the same cache update."""
    cfg = get("qwen3-14b-smoke")
    params = steps.init_params(cfg, jax.random.PRNGKey(1), max_seq=32)
    cache = steps.init_cache(cfg, 2, steps.decode_cache_len(cfg, 32))
    batch = {"tokens": jnp.array([[3], [5]], jnp.int32),
             "pos": jnp.asarray(0, jnp.int32)}
    c_tok, tok = steps.make_decode_step(cfg, max_seq=32)(params, cache, batch)
    c_log, logits = steps.make_decode_step(cfg, max_seq=32, logits=True)(
        params, cache, batch)
    assert logits.shape == (2, cfg.vocab) and logits.dtype == jnp.float32
    np.testing.assert_array_equal(np.argmax(logits, -1)[:, None], tok)
    for x, y in zip(jax.tree.leaves(c_tok), jax.tree.leaves(c_log)):
        np.testing.assert_array_equal(x, y)

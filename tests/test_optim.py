"""Optimizer + gradient compression correctness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import AdamConfig, adam_init, adam_update, warmup_cosine
from repro.optim.compress import (compress_decompress, compressed_psum,
                                  quantize_int8, dequantize_int8, wire_bytes)


def manual_adam(p, g, m, v, t, cfg):
    m = cfg.b1 * m + (1 - cfg.b1) * g
    v = cfg.b2 * v + (1 - cfg.b2) * g * g
    mh = m / (1 - cfg.b1 ** t)
    vh = v / (1 - cfg.b2 ** t)
    return p - cfg.lr * (mh / (np.sqrt(vh) + cfg.eps) + cfg.weight_decay * p), m, v


def test_adam_matches_reference():
    cfg = AdamConfig(lr=1e-2, grad_clip=0.0, weight_decay=0.1)
    params = {"w": jnp.asarray([1.0, -2.0, 3.0], jnp.float32)}
    grads = {"w": jnp.asarray([0.1, 0.2, -0.3], jnp.float32)}
    opt = adam_init(params, cfg)
    p_np = np.asarray(params["w"], np.float64)
    m_np = np.zeros(3)
    v_np = np.zeros(3)
    for t in range(1, 5):
        params, opt, _ = adam_update(params, grads, opt, cfg)
        p_np, m_np, v_np = manual_adam(p_np, np.asarray(grads["w"]), m_np,
                                       v_np, t, cfg)
        np.testing.assert_allclose(params["w"], p_np, rtol=1e-5, atol=1e-6)


def test_grad_clip_limits_update():
    cfg = AdamConfig(lr=1.0, grad_clip=1e-3, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    grads = {"w": jnp.full((4,), 100.0)}
    _, _, metrics = adam_update(params, grads, adam_init(params, cfg), cfg)
    assert float(metrics["grad_norm"]) > 100


def test_bf16_moments_roundtrip():
    cfg = AdamConfig(moment_dtype="bfloat16")
    params = {"w": jnp.ones((8,), jnp.bfloat16)}
    opt = adam_init(params, cfg)
    assert opt["m"]["w"].dtype == jnp.bfloat16
    new_p, new_opt, _ = adam_update(params, {"w": jnp.ones(8, jnp.bfloat16)},
                                    opt, cfg)
    assert new_opt["v"]["w"].dtype == jnp.bfloat16
    assert new_p["w"].dtype == jnp.bfloat16


def test_warmup_cosine_shape():
    assert float(warmup_cosine(0, warmup=10, total=100)) == 0.0
    assert float(warmup_cosine(10, warmup=10, total=100)) == pytest.approx(1.0)
    assert float(warmup_cosine(100, warmup=10, total=100)) == pytest.approx(
        0.1, abs=1e-3)


# ----------------------------------------------------------------------------
# compression
# ----------------------------------------------------------------------------

def test_int8_quantization_bounded_error():
    g = jax.random.normal(jax.random.PRNGKey(0), (1024,), jnp.float32)
    q, s = quantize_int8(g)
    err = np.abs(np.asarray(dequantize_int8(q, s) - g))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_error_feedback_unbiased_over_time():
    """With EF, the *accumulated* compressed sum tracks the true sum."""
    rng = np.random.default_rng(0)
    true_sum = np.zeros(64)
    sent_sum = np.zeros(64)
    err = jnp.zeros(64)
    for t in range(50):
        g = jnp.asarray(rng.normal(size=64) * 0.01, jnp.float32)
        corrected = g + err
        sent = compress_decompress(corrected, "int8_ef")
        err = corrected - sent
        true_sum += np.asarray(g)
        sent_sum += np.asarray(sent)
    # residual is bounded by one quantization step, not growing with t
    assert np.abs(true_sum - sent_sum).max() < 0.01


def test_compressed_psum_under_shard_map():
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.core.compat import make_mesh
    mesh = make_mesh((1,), ("data",))
    grads = {"w": jnp.ones((4,), jnp.float32)}

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
             check_vma=False)
    def run(g):
        return compressed_psum(g, "data", "bf16")

    red, err = run(grads)
    np.testing.assert_allclose(red["w"], grads["w"], rtol=1e-2)


def test_wire_bytes_accounting():
    grads = {"w": jnp.zeros((1000,)), "b": jnp.zeros((24,))}
    assert wire_bytes(grads, "none") == 4096.0
    assert wire_bytes(grads, "bf16") == 2048.0
    assert wire_bytes(grads, "int8_ef") == 1024.0

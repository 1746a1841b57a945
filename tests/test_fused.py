"""The fused producer–consumer kernel path (kernels/fused.py).

Numerics: every fused kernel against its unfused jnp composition from
kernels/ref.py, at fp32 (<= 1e-5) and bf16 (<= 2e-2), interpret mode.
Mechanics: check_fusable compatibility, saved-bytes accounting, the
autotune-on-miss path of tuned_call, the fused roofline, the model-stack
routing behind the "fused" KernelPolicy.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels import fused, ops, ref, pipeline as pp
from repro.launch.roofline import fused_roofline

TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def rand(seed, shape, dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


def _assert_close(got, want, dtype):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


# ----------------------------------------------------------------------------
# fused kernels vs unfused composition
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,bm,bn", [
    (128, 64, 128, 64, 64),
    (96, 256, 64, 32, 32),
])
def test_rmsnorm_matmul(dtype, m, k, n, bm, bn):
    x = rand(0, (m, k), dtype)
    s = rand(1, (k,)) * 0.1
    w = rand(2, (k, n), dtype)
    got = ops.rmsnorm_matmul(x, s.astype(dtype), w, bm=bm, bn=bn)
    want = ref.matmul(ref.rmsnorm(x, s.astype(dtype)), w)
    _assert_close(got, want, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["none", "gelu", "silu"])
def test_matmul_bias_act(dtype, act):
    m, k, n = 64, 96, 128
    a = rand(3, (m, k), dtype)
    b = rand(4, (k, n), dtype)
    bias = rand(5, (n,), dtype)
    got = ops.matmul_bias_act(a, b, bias, act=act, bm=32, bn=64, bk=32)
    h = jnp.dot(a, b, preferred_element_type=jnp.float32) \
        + bias.astype(jnp.float32)
    want = fused.ACTIVATIONS[act](h).astype(dtype)
    _assert_close(got, want, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_residual_add(dtype):
    m, k, n = 96, 64, 96
    a = rand(6, (m, k), dtype)
    b = rand(7, (k, n), dtype)
    res = rand(8, (m, n), dtype)
    got = ops.matmul_residual_add(a, b, res, bm=32, bn=32, bk=32)
    want = (jnp.dot(a, b, preferred_element_type=jnp.float32)
            + res.astype(jnp.float32)).astype(dtype)
    _assert_close(got, want, dtype)


def test_flash_attention_proj_smoke():
    """One small fp32 case in the fast lane; the dtype/GQA grid is slow."""
    _flash_attention_proj_case(jnp.float32, 1, 4, 2, 64, 16, 32)


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,s,hd,dm", [
    (2, 4, 4, 128, 32, 64),       # MHA
    (1, 4, 2, 128, 32, 48),       # GQA group 2
])
def test_flash_attention_proj(dtype, b, h, kv, s, hd, dm):
    _flash_attention_proj_case(dtype, b, h, kv, s, hd, dm)


def _flash_attention_proj_case(dtype, b, h, kv, s, hd, dm):
    q = rand(9, (b, h, s, hd), dtype)
    k = rand(10, (b, kv, s, hd), dtype)
    v = rand(11, (b, kv, s, hd), dtype)
    wo = rand(12, (h, hd, dm), dtype) * 0.1
    got = ops.flash_attention_proj(q, k, v, wo, bq=32, bk=32)
    g = h // kv
    o = ref.flash_attention(q, jnp.repeat(k, g, axis=1),
                            jnp.repeat(v, g, axis=1))
    want = jnp.einsum("bhsk,hkd->bsd", o.astype(jnp.float32),
                      wo.astype(jnp.float32)).astype(dtype)
    _assert_close(got, want, dtype)


@pytest.mark.slow
def test_fused_grads_match_reference():
    """The custom-VJP backward equals grads of the jnp composition."""
    x = rand(13, (32, 48))
    s = rand(14, (48,)) * 0.1
    w = rand(15, (48, 64))

    g = jax.grad(lambda *a: jnp.sum(ops.rmsnorm_matmul(*a) ** 2),
                 argnums=(0, 1, 2))(x, s, w)
    gr = jax.grad(lambda x, s, w: jnp.sum(
        jnp.dot(ref.rmsnorm(x, s), w) ** 2), argnums=(0, 1, 2))(x, s, w)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


# ----------------------------------------------------------------------------
# fusion mechanics
# ----------------------------------------------------------------------------


def test_check_fusable_rejects_mismatches():
    a = pp.TileSpec((64, 128), lambda i: (i, 0))
    b = pp.TileSpec((64, 64), lambda i: (i, 0))
    with pytest.raises(pp.FusionError):
        pp.check_fusable(a, b)
    smem = pp.TileSpec((64, 128), lambda i: (i, 0), memory_space="smem")
    with pytest.raises(pp.FusionError):
        pp.check_fusable(a, smem)
    # partial residency of a full-dim axis: producer tile not fully consumed
    with pytest.raises(pp.FusionError):
        pp.check_fusable(a, a, full_dims=(1,), dims=(256,))
    pp.check_fusable(a, a, full_dims=(1,), dims=(128,))   # ok


def test_fuse_hooks_compose():
    """Two epilogues stack (innermost first); prologues chain in order."""
    m = n = k = 64
    from repro.kernels import matmul as mm
    base = mm.build_pipeline(m, n, k, jnp.float32, bm=32, bn=32, bk=32)
    p1 = base.fuse(epilogue=lambda o: o + 1.0)
    p2 = p1.fuse(epilogue=lambda o: o * 2.0)
    a = rand(16, (m, k))
    b = rand(17, (k, n))
    got = p2(a, b, interpret=True)
    # composition order: new epilogue runs closest to the register tile
    want = (jnp.dot(a, b) * 2.0) + 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fuse_stacked_extras_are_isolated():
    """Two fusions each carrying extra tiles compose: every hook is bound
    to its own operand slice (norm prologue + residual epilogue stacked)."""
    m, k, n = 64, 48, 64
    pipe = fused.build_rmsnorm_matmul(m, n, k, jnp.float32, bm=32, bn=32)
    stacked = pipe.fuse(
        epilogue=lambda o, r_ref: o.astype(jnp.float32) + r_ref[...],
        extra_tiles=[pp.TileSpec((32, 32), lambda i, j, s: (i, j))])
    x = rand(26, (m, k))
    s = rand(27, (k,)) * 0.1
    w = rand(28, (k, n))
    r = rand(29, (m, n))
    got = stacked(x, w, s, r, interpret=True)
    want = jnp.dot(ref.rmsnorm(x, s), w) + r
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_traffic_saves_intermediate():
    for name, shapes in [
        ("rmsnorm_matmul", {"m": 512, "k": 512, "n": 512}),
        ("matmul_bias_act", {"m": 512, "k": 512, "n": 512}),
        ("matmul_residual_add", {"m": 512, "k": 512, "n": 512}),
        ("flash_attention_proj",
         {"b": 1, "h": 4, "kv": 2, "s": 512, "hd": 64, "dm": 256}),
    ]:
        defn = pp.KERNELS[name]
        t = defn.traffic(shapes, defn.default_blocks(shapes), 4)
        assert t.saved_bytes > 0, name
        assert t.hbm_bytes >= t.ideal_bytes - 1e-9, name
        model = fused.fused_vs_unfused(name, shapes)
        assert model["unfused_bytes"] == pytest.approx(
            t.hbm_bytes + t.saved_bytes)
        assert model["reduction"] > 1.0, name


def test_transformer_block_traffic_halved():
    """Acceptance: the fused transformer block moves >= 2x fewer modeled
    HBM bytes than the unfused composition."""
    t = fused.transformer_block_traffic(1, 4096, 4096, 32, 8, 128, 14336)
    assert t["reduction"] >= 2.0, t["reduction"]
    assert t["fused_bytes"] > 0


def test_fused_roofline_drops_saved_terms():
    r = fused_roofline(1e12, 1e9, 1e9)
    assert r["traffic_reduction"] == pytest.approx(2.0)
    assert r["unfused_memory_s"] == pytest.approx(2 * r["memory_s"])
    assert r["saved_s"] > 0


def test_autotune_registers_fused_record_with_saved_bytes():
    registry.KERNEL_TUNES.clear()
    shapes = {"m": 256, "k": 256, "n": 256}
    r = pp.autotune("rmsnorm_matmul", shapes)
    rec = registry.get_kernel_tune("rmsnorm_matmul", pp.shape_key(shapes))
    assert rec is not None
    assert rec.saved_bytes > 0
    assert dict(rec.blocks) == r.blocks


# ----------------------------------------------------------------------------
# tuned_call autotune-on-miss (satellite)
# ----------------------------------------------------------------------------


def test_tuned_call_autotunes_on_registry_miss():
    """A shape with no registry record must tune, register, and still be
    numerically correct — for an unfused and a fused kernel."""
    registry.KERNEL_TUNES.clear()
    x = rand(18, (72, 40))
    s = rand(19, (40,)) * 0.1
    w = rand(20, (40, 56))

    got = ops.tuned_call("rmsnorm_matmul", x, s, w)
    _assert_close(got, ref.matmul(ref.rmsnorm(x, s), w), jnp.float32)
    key = pp.shape_key({"m": 72, "k": 40, "n": 56})
    assert registry.get_kernel_tune("rmsnorm_matmul", key) is not None

    a = rand(21, (72, 40))
    b = rand(22, (40, 56))
    got = ops.tuned_call("matmul", a, b)
    _assert_close(got, ref.matmul(a, b), jnp.float32)
    assert registry.get_kernel_tune("matmul", key) is not None
    # second call is a registry hit returning the same blocks
    blocks = pp.tuned_blocks("matmul", {"m": 72, "k": 40, "n": 56})
    assert blocks == dict(
        registry.get_kernel_tune("matmul", key).blocks)


# ----------------------------------------------------------------------------
# model-stack routing behind the "fused" KernelPolicy
# ----------------------------------------------------------------------------


@pytest.mark.slow
def test_model_fused_route_matches_unfused():
    """Forward loss and greedy decode agree between the fused and unfused
    routes on a smoke config (rms norm + swiglu + GQA)."""
    from repro.cluster import use_policy
    from repro.models import steps
    cfg = dataclasses.replace(registry.get("yi-34b-smoke"), n_layers=2)
    params = steps.init_params(cfg, jax.random.PRNGKey(0), max_seq=32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    l0, _ = steps.loss_fn(cfg, params, batch)
    with use_policy("fused"):
        l1, _ = steps.loss_fn(cfg, params, batch)
    assert abs(float(l0) - float(l1)) < 2e-2

    dec_u = steps.make_decode_step(cfg, max_seq=16)
    dec_f = steps.make_decode_step(cfg, max_seq=16, policy="fused")
    cache = steps.init_cache(cfg, 2, 16)
    b1 = {"tokens": jnp.zeros((2, 1), jnp.int32),
          "pos": jnp.asarray(0, jnp.int32)}
    _, t_u = dec_u(params, cache, b1)
    _, t_f = dec_f(params, cache, b1)
    assert (np.asarray(t_u) == np.asarray(t_f)).all()


def test_pallas_attention_schedule_adapter():
    from repro.models import attention as attn_lib
    q = rand(23, (1, 64, 4, 16))
    k = rand(24, (1, 64, 2, 16))
    v = rand(25, (1, 64, 2, 16))
    got = attn_lib.attention(q, k, v, n_kv=2, causal=True, chunk=32,
                             schedule="pallas")
    want = attn_lib.attention(q, k, v, n_kv=2, causal=True, chunk=32,
                              schedule="direct")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)

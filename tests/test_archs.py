"""Per-architecture smoke tests: reduced same-family config, one forward /
train step and one decode step on CPU; asserts shapes + finite values."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get
from repro.models import steps

B, S = 2, 32
KEY = jax.random.PRNGKey(0)


def make_batch(cfg):
    batch = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab),
             "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab)}
    if cfg.family == "encdec":
        batch["enc_embeds"] = jax.random.normal(
            KEY, (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm":
        batch["img_embeds"] = jax.random.normal(
            KEY, (B, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16)
    return batch


# heaviest smoke configs (deep scan patterns / vision cross-attn); their
# prefill/decode smokes run only in the slow lane — the fast lane keeps one
# representative of every other family
_HEAVY = {"llama-3.2-vision-90b", "recurrentgemma-9b", "xlstm-125m"}
_SMOKE_PARAMS = [pytest.param(a, marks=pytest.mark.slow) if a in _HEAVY else a
                 for a in sorted(ARCHS)]


@pytest.mark.slow
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_train_step_smoke(arch):
    cfg = get(arch + "-smoke")
    state = steps.init_train_state(cfg, KEY, max_seq=S)
    batch = make_batch(cfg)
    ts = jax.jit(steps.make_train_step(cfg))
    new_state, metrics = ts(state, batch)
    assert np.isfinite(float(metrics["loss"])), metrics
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    before = jax.tree.leaves(state["params"])[1]
    after = jax.tree.leaves(new_state["params"])[1]
    assert not np.allclose(np.asarray(before, np.float32),
                           np.asarray(after, np.float32))


@pytest.mark.parametrize("arch", _SMOKE_PARAMS)
def test_decode_step_smoke(arch):
    cfg = get(arch + "-smoke")
    params = steps.init_params(cfg, KEY, max_seq=S)
    cl = steps.decode_cache_len(cfg, S)
    cache = steps.init_cache(cfg, B, cl)
    dec = jax.jit(steps.make_decode_step(cfg, max_seq=S))
    batch = {"tokens": jnp.zeros((B, 1), jnp.int32),
             "pos": jnp.asarray(3, jnp.int32)}
    new_cache, tok = dec(params, cache, batch)
    assert tok.shape == (B, 1)
    assert tok.dtype == jnp.int32
    assert (np.asarray(tok) >= 0).all() and (np.asarray(tok) < cfg.vocab).all()
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


@pytest.mark.parametrize("arch", _SMOKE_PARAMS)
def test_prefill_step_smoke(arch):
    cfg = get(arch + "-smoke")
    params = steps.init_params(cfg, KEY, max_seq=S)
    batch = make_batch(cfg)
    batch.pop("labels")
    pf = jax.jit(steps.make_prefill_step(cfg))
    tok = pf(params, batch)
    assert tok.shape == (B,)


@pytest.mark.slow
def test_train_loss_decreases():
    """A few steps on a fixed batch must reduce the loss (learning works)."""
    cfg = get("qwen3-14b-smoke")
    state = steps.init_train_state(cfg, KEY, max_seq=S)
    batch = make_batch(cfg)
    ts = jax.jit(steps.make_train_step(cfg))
    losses = []
    for _ in range(8):
        state, metrics = ts(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


def test_full_config_param_counts():
    """The exact assignment configs must hit their advertised scale."""
    expect = {"qwen1.5-32b": (30e9, 36e9), "yi-34b": (32e9, 37e9),
              "deepseek-67b": (63e9, 70e9), "qwen3-14b": (13e9, 16e9),
              "grok-1-314b": (300e9, 330e9), "mixtral-8x7b": (44e9, 50e9),
              "whisper-small": (0.1e9, 0.3e9), "xlstm-125m": (0.1e9, 0.2e9),
              "recurrentgemma-9b": (8e9, 11e9),
              "llama-3.2-vision-90b": (80e9, 95e9),
              "falcon-h1-34b": (33.5e9, 33.7e9)}
    for arch, (lo, hi) in expect.items():
        n = ARCHS[arch].n_params()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9},{hi/1e9}]"


@pytest.mark.slow
def test_moe_local_dispatch_matches_global():
    """With ample capacity (no drops), grouped-local dispatch must equal
    the global-flat dispatch bit-for-bit in routing semantics."""
    import dataclasses
    from repro.models.blocks import moe_apply, moe_specs
    from repro.models.layers import init_tree
    cfg = get("mixtral-8x7b-smoke")
    cfg_g = dataclasses.replace(cfg, capacity_factor=8.0)
    cfg_l = dataclasses.replace(cfg, capacity_factor=8.0,
                                moe_local_dispatch=True)
    p = init_tree(moe_specs(cfg), KEY)
    x = jax.random.normal(KEY, (3, 16, cfg.d_model), jnp.bfloat16)
    yg, ag = moe_apply(cfg_g, p, x)
    yl, al = moe_apply(cfg_l, p, x)
    np.testing.assert_allclose(np.asarray(yg, np.float32),
                               np.asarray(yl, np.float32),
                               rtol=2e-2, atol=2e-2)
    assert float(ag) == pytest.approx(float(al), rel=1e-5)


def test_moe_capacity_drop_and_combine():
    """MoE combine weights: sum over used experts <= 1, dropped -> partial."""
    from repro.models.blocks import moe_apply
    cfg = get("mixtral-8x7b-smoke")
    from repro.models.blocks import moe_specs
    from repro.models.layers import init_tree
    p = init_tree(moe_specs(cfg), KEY)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.bfloat16)
    y, aux = moe_apply(cfg, p, x)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert float(aux) > 0.0


@pytest.mark.slow
def test_mlstm_chunked_matches_decode_loop():
    """Chunkwise mLSTM (train path) == step-by-step recurrence (decode)."""
    from repro.models import blocks
    cfg = get("xlstm-125m-smoke")
    p = blocks.BLOCKS["mlstm"]["specs"](cfg)
    from repro.models.layers import init_tree
    params = init_tree(p, KEY)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.bfloat16)
    ctx = {"positions": jnp.broadcast_to(jnp.arange(16), (2, 16))}
    full, _ = blocks.mlstm_block_apply(cfg, params, x, ctx)
    cache = init_tree(blocks.mlstm_cache_specs(cfg, 2, 16), KEY)
    cache = jax.tree.map(jnp.zeros_like, cache)
    outs = []
    for t in range(16):
        o, cache = blocks.mlstm_block_decode(
            cfg, params, x[:, t:t + 1], cache, t, ctx)
        outs.append(o)
    stepwise = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full, np.float32),
                               np.asarray(stepwise, np.float32),
                               rtol=0.15, atol=0.15)


@pytest.mark.slow
def test_rglru_scan_matches_decode_loop():
    from repro.models import blocks
    cfg = get("recurrentgemma-9b-smoke")
    params_specs = blocks.BLOCKS["rglru"]["specs"](cfg)
    from repro.models.layers import init_tree
    params = init_tree(params_specs, KEY)
    x = jax.random.normal(KEY, (2, 12, cfg.d_model), jnp.bfloat16)
    ctx = {"positions": jnp.broadcast_to(jnp.arange(12), (2, 12))}
    full, _ = blocks.rglru_block_apply(cfg, params, x, ctx)
    cache = init_tree(blocks.rglru_cache_specs(cfg, 2, 12), KEY)
    cache = jax.tree.map(jnp.zeros_like, cache)
    outs = []
    for t in range(12):
        o, cache = blocks.rglru_block_decode(
            cfg, params, x[:, t:t + 1], cache, t, ctx)
        outs.append(o)
    stepwise = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full, np.float32),
                               np.asarray(stepwise, np.float32),
                               rtol=0.15, atol=0.15)


def test_attn_ssm_scan_matches_decode_loop():
    """Falcon-H1's block over a whole sequence (attention, and the Mamba-2
    recurrence as a scan) equals the block fed one position at a time
    through its decode state. In float32, with each branch shown to move
    the output, so that a branch dropped on one path cannot pass."""
    import dataclasses

    from repro.models import blocks
    from repro.models.layers import init_tree
    cfg = get("falcon-h1-34b-smoke")
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    params = f32(init_tree(blocks.attn_ssm_block_specs(cfg), KEY))
    ks = jax.random.split(KEY, 4)
    # away from the init's zeros and ones, so that every leaf counts
    params["ssm"] |= {k: jax.random.normal(kk, params["ssm"][k].shape)
                      for k, kk in zip(("dt_bias", "A_log", "D", "conv_b"),
                                       ks)}
    S = 12
    x = jax.random.normal(KEY, (2, S, cfg.d_model), jnp.float32)
    ctx = {"positions": jnp.broadcast_to(jnp.arange(S), (2, S)),
           "max_seq": S}
    full, _ = blocks.attn_ssm_block_apply(cfg, params, x, ctx)

    def stepwise(cfg, params):
        cache = f32(init_tree(blocks.attn_ssm_cache_specs(cfg, 2, S), KEY))
        outs = []
        for t in range(S):
            o, cache = blocks.attn_ssm_block_decode(
                cfg, params, x[:, t:t + 1], cache, t,
                dict(ctx, positions=jnp.full((2, 1), t)))
            outs.append(o)
        return jnp.concatenate(outs, axis=1)

    np.testing.assert_allclose(np.asarray(full), np.asarray(stepwise(
        cfg, params)), rtol=1e-4, atol=1e-5)
    # each branch moves the output well past that tolerance: the mixer's
    # input to its state (the x segment zeroed), the attention, the FFN
    frozen = dataclasses.replace(cfg, ssm_mults=(1.0, 0.0, 1.0, 1.0, 1.0))
    for other in (stepwise(frozen, params),
                  stepwise(dataclasses.replace(cfg, attn_out_mult=0.0),
                           params),
                  stepwise(dataclasses.replace(cfg, mlp_mults=(1.0, 0.0)),
                           params)):
        assert float(jnp.max(jnp.abs(other - full))) > 1e-3

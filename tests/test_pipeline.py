"""The unified tile-pipeline layer: correctness through KernelPipeline,
autotuner validity (divisibility + VMEM budget), cost-model sanity, and
registry round-trip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels import ops, ref, pipeline as pp

KEY = jax.random.PRNGKey(3)


def rand(seed, shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


# one smallish shape dict per kernel — the autotune sweep cases
SHAPES = {
    "axpy": {"m": 768, "n": 128},
    "dotp": {"m": 768, "n": 128},
    "matmul": {"m": 512, "n": 256, "k": 384},
    "conv2d": {"h": 96, "w": 256},
    "dct8x8": {"n": 1536},
    "rmsnorm": {"m": 384, "d": 256},
    "flash_attention": {"b": 1, "h": 4, "kv": 2, "s": 256, "hd": 64},
    "paged_decode_attention": {"b": 3, "h": 4, "kv": 2, "hd": 128, "ps": 4,
                               "npp": 5},
}

# which traffic dims each block size must divide
DIVIDES = {
    "axpy": {"block_rows": "m"},
    "dotp": {"block_rows": "m"},
    "matmul": {"bm": "m", "bn": "n", "bk": "k"},
    "conv2d": {"block_rows": "h"},
    "dct8x8": {"block_n": "n"},
    "rmsnorm": {"block_rows": "m"},
    "flash_attention": {"bq": "s", "bk": "s"},
    # a slot's last block may run past its table: nothing to divide
    "paged_decode_attention": {},
}


def make_operands(name, shapes):
    if name == "axpy":
        return (1.7, rand(0, (shapes["m"], shapes["n"])),
                rand(1, (shapes["m"], shapes["n"])))
    if name == "dotp":
        return (rand(2, (shapes["m"], shapes["n"])),
                rand(3, (shapes["m"], shapes["n"])))
    if name == "matmul":
        return (rand(4, (shapes["m"], shapes["k"])),
                rand(5, (shapes["k"], shapes["n"])))
    if name == "conv2d":
        return (rand(6, (shapes["h"], shapes["w"])), rand(7, (3, 3)))
    if name == "dct8x8":
        return (rand(8, (shapes["n"], 8, 8)),)
    if name == "rmsnorm":
        return (rand(9, (shapes["m"], shapes["d"])),
                rand(10, (shapes["d"],)) * 0.1)
    if name == "flash_attention":
        b, h, kv, s, hd = (shapes[k] for k in ("b", "h", "kv", "s", "hd"))
        return (rand(11, (b, h, s, hd)), rand(12, (b, kv, s, hd)),
                rand(13, (b, kv, s, hd)))
    if name == "paged_decode_attention":
        b, h, kv, hd, ps, npp = (shapes[k] for k in
                                 ("b", "h", "kv", "hd", "ps", "npp"))
        pool = (1 + b * npp, ps, kv, hd)
        pages = np.random.default_rng(0).permutation(np.arange(1, pool[0]))
        return (rand(14, (b, 1, h, hd)), rand(15, pool), rand(16, pool),
                jnp.asarray([1, 9, npp * ps], jnp.int32),
                jnp.asarray(pages.reshape(b, npp), jnp.int32))
    raise KeyError(name)


def reference(name, operands):
    if name == "axpy":
        return ref.axpy(*operands)
    if name == "dotp":
        return ref.dotp(*operands)
    if name == "matmul":
        return ref.matmul(*operands)
    if name == "conv2d":
        return ref.conv2d_3x3(*operands)
    if name == "dct8x8":
        return ref.dct8x8(*operands)
    if name == "rmsnorm":
        return ref.rmsnorm(*operands)
    if name == "flash_attention":
        q, k, v = operands
        g = q.shape[1] // k.shape[1]
        return ref.flash_attention(q, jnp.repeat(k, g, axis=1),
                                   jnp.repeat(v, g, axis=1))
    if name == "paged_decode_attention":
        from repro.models import attention
        q, k_pool, v_pool, lengths, pages = operands
        return attention.decode_attention(
            q, attention.paged_gather(k_pool, pages),
            attention.paged_gather(v_pool, pages), lengths,
            n_kv=k_pool.shape[2])
    raise KeyError(name)


ALL_KERNELS = sorted(SHAPES)

# the fused producer–consumer kernels (kernels/fused.py); numerics covered
# in tests/test_fused.py, registry membership checked here
FUSED_KERNELS = ["flash_attention_proj", "matmul_bias_act",
                 "matmul_residual_add", "rmsnorm_matmul"]


def test_all_kernels_registered():
    assert sorted(pp.KERNELS) == sorted(ALL_KERNELS + FUSED_KERNELS)
    assert sorted(ops.OPS) == sorted(ALL_KERNELS + FUSED_KERNELS)


@pytest.mark.parametrize("name", ALL_KERNELS)
def test_kernel_matches_reference_through_pipeline(name):
    """Every kernel routed through KernelPipeline == its jnp oracle."""
    operands = make_operands(name, SHAPES[name])
    got = ops.tuned_call(name, *operands)
    want = reference(name, operands)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("name", ALL_KERNELS)
def test_autotune_blocks_divide_and_fit(name):
    shapes = SHAPES[name]
    result = pp.autotune(name, shapes)
    for block_name, dim_name in DIVIDES[name].items():
        block = result.blocks[block_name]
        dim = shapes[dim_name]
        assert dim % block == 0, (name, block_name, block, dim)
        assert 1 <= block <= dim
    t = pp.KERNELS[name].traffic(shapes, result.blocks, 4)
    assert t.vmem_bytes <= pp.VMEM_BUDGET_BYTES
    assert result.cost.total_s <= result.default_cost.total_s * (1 + 1e-9)


@pytest.mark.parametrize("name", ALL_KERNELS)
def test_tune_space_is_all_divisors(name):
    """Every candidate the tuner may pick respects divisibility."""
    shapes = SHAPES[name]
    n_cands = 0
    for blocks in pp.KERNELS[name].tune_space(shapes):
        n_cands += 1
        for block_name, dim_name in DIVIDES[name].items():
            assert shapes[dim_name] % blocks[block_name] == 0, (name, blocks)
    assert n_cands >= 1


def test_autotune_registers_record():
    registry.KERNEL_TUNES.clear()
    r = pp.autotune("matmul", SHAPES["matmul"])
    rec = registry.get_kernel_tune("matmul", pp.shape_key(SHAPES["matmul"]))
    assert rec is not None
    assert dict(rec.blocks) == r.blocks
    assert rec.modeled_seconds == pytest.approx(r.cost.total_s)
    assert registry.kernel_tunes() == [rec]
    # tuned_blocks is registry-cached: same answer without re-tuning
    assert pp.tuned_blocks("matmul", SHAPES["matmul"]) == r.blocks


def test_tune_records_keyed_by_dtype():
    """Blocks tuned under bf16 VMEM footprints must not serve f32 calls."""
    registry.KERNEL_TUNES.clear()
    pp.autotune("matmul", SHAPES["matmul"], dtype_bytes=2)
    assert registry.get_kernel_tune(
        "matmul", pp.shape_key(SHAPES["matmul"], 2)) is not None
    assert registry.get_kernel_tune(
        "matmul", pp.shape_key(SHAPES["matmul"], 4)) is None


def test_default_blocks_are_divisors():
    """The modeled default must be the blocking that actually executes
    (snap_block applied), even when the nominal default doesn't divide."""
    for name, shapes in SHAPES.items():
        d = pp.KERNELS[name].default_blocks(shapes)
        for block_name, dim_name in DIVIDES[name].items():
            assert shapes[dim_name] % d[block_name] == 0, (name, d)
    # regression: axpy at m=768 used to model a phantom block_rows=512
    assert pp.KERNELS["axpy"].default_blocks({"m": 768, "n": 128}) == \
        {"block_rows": 384}


def test_traffic_streamed_at_least_ideal():
    for name, shapes in SHAPES.items():
        defn = pp.KERNELS[name]
        t = defn.traffic(shapes, defn.default_blocks(shapes), 4)
        assert t.hbm_bytes >= t.ideal_bytes - 1e-9, name
        assert t.flops > 0 and t.grid_steps >= 1, name


def test_locality_penalty_monotone():
    """Less reuse (more re-streaming) must never score better."""
    local = pp.Traffic(flops=1e9, hbm_bytes=1e6, ideal_bytes=1e6,
                       grid_steps=8, vmem_bytes=1 << 20)
    remote = pp.Traffic(flops=1e9, hbm_bytes=4e6, ideal_bytes=1e6,
                        grid_steps=8, vmem_bytes=1 << 20)
    f_local, p_local = pp.locality_factor(local)
    f_remote, p_remote = pp.locality_factor(remote)
    assert p_local == pytest.approx(1.0) and p_remote == pytest.approx(0.25)
    assert f_remote > f_local >= 1.0
    assert pp.score(remote).total_s > pp.score(local).total_s


def test_matmul_bigger_output_tile_raises_p_local():
    """MemPool's register-blocking story: bigger (bm, bn) -> fewer
    re-streams of A and B -> higher modeled p_local."""
    shapes = {"m": 1024, "n": 1024, "k": 1024}
    defn = pp.KERNELS["matmul"]
    small = pp.score(defn.traffic(shapes, {"bm": 128, "bn": 128, "bk": 128}, 4))
    big = pp.score(defn.traffic(shapes, {"bm": 512, "bn": 512, "bk": 128}, 4))
    assert big.p_local > small.p_local
    assert big.total_s < small.total_s


def test_vmem_budget_respected_by_autotuner():
    """With a tiny budget the tuner must fall back to small, valid blocks."""
    shapes = {"m": 1024, "n": 1024, "k": 1024}
    r = pp.autotune("matmul", shapes, vmem_budget=1 << 20,
                    register_record=False)
    t = pp.KERNELS["matmul"].traffic(shapes, r.blocks, 4)
    assert t.vmem_bytes <= 1 << 20
    for bname, dim in (("bm", "m"), ("bn", "n"), ("bk", "k")):
        assert shapes[dim] % r.blocks[bname] == 0


def test_pipeline_vmem_accounting_double_buffers():
    from repro.kernels import matmul as mm
    pipe = mm.build_pipeline(256, 256, 256, jnp.float32,
                             bm=128, bn=128, bk=128)
    # 2 slots x (a + b + out tiles) x 4B + f32 accumulator scratch
    expect = 2 * (128 * 128 * 3) * 4 + 128 * 128 * 4
    assert pipe.vmem_bytes(4) == expect
    assert pipe.grid_steps == 2 * 2 * 2
    assert pipe.dimension_semantics() == ("parallel", "parallel", "arbitrary")


def test_block_candidates_properties():
    cands = pp.block_candidates(1024, align=128, cap=5)
    assert len(cands) <= 5
    assert all(1024 % c == 0 and c % 128 == 0 for c in cands)
    assert pp.block_candidates(7, align=8) == [7]       # fallback: [dim]
    assert pp.block_candidates(1024, align=8, max_block=64)[-1] <= 64

"""Architecture configs — the assigned architectures + the paper's own.

Each config is exact per the assignment table; `smoke()` returns a reduced
same-family variant for CPU tests. `input_specs()` returns ShapeDtypeStruct
stand-ins for every model input of a given workload shape (the multi-pod
dry-run lowers against these; no allocation happens).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | encdec | ssm | hybrid | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0            # 0 -> d_model // n_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e4
    norm: str = "rms"            # rms | layer
    norm_eps: float = 1e-6       # RMSNorm epsilon
    ffn_kind: str = "swiglu"
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # --- attention window (SWA / local attention) ---
    window: int | None = None
    # --- enc-dec (whisper) ---
    n_enc_layers: int = 0
    enc_seq: int = 0             # frontend stub: precomputed frame embeddings
    # --- vlm ---
    cross_every: int = 0         # a cross-attn layer every N layers
    n_img_tokens: int = 0
    # --- hybrid/ssm block pattern, cycled over layers ---
    pattern: tuple[str, ...] = ("attn",)
    # --- recurrent dims ---
    lru_width: int = 0
    conv_width: int = 4
    # --- Mamba-2 mixer (the "attn_ssm" block): heads of ssm_head_dim
    # channels, each a (ssm_head_dim, ssm_state) state; B and C are shared
    # by the heads of each of ssm_groups groups ---
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    # --- published muP multipliers (Falcon-H1); 1.0 leaves a block as it
    # is. ssm_mults scale the in-projection's z, x, B, C and dt segments,
    # mlp_mults the FFN's gate pre-activation and its output ---
    embed_mult: float = 1.0
    lm_head_mult: float = 1.0
    attn_in_mult: float = 1.0
    attn_out_mult: float = 1.0
    key_mult: float = 1.0
    ssm_in_mult: float = 1.0
    ssm_out_mult: float = 1.0
    ssm_mults: tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_mults: tuple[float, float] = (1.0, 1.0)
    # --- numerics / memory policy ---
    param_dtype: str = "bfloat16"
    moment_dtype: str = "float32"
    remat: str = "nothing"  # save layer inputs only: O(S^2) score blocks
    # must never be checkpointed (checkpoint_dots would hold them to bwd)
    attn_chunk: int = 1024
    attn_schedule: str = "auto"   # auto | masked | folded | banded
    grad_accum: int = 1           # microbatch steps per train step
    sub_quadratic: bool = False   # can run long_500k
    # per-arch sharding-rule overrides applied on top of the hybrid
    # addressing defaults (tuple of (logical_axis, mesh_axes|None) pairs)
    rules_overrides: tuple = ()
    # MoE dispatch locality (False = global/baseline, True = GShard groups;
    # see models/blocks.moe_apply and EXPERIMENTS.md §Perf H2/H3)
    moe_local_dispatch: bool = False
    # NOTE: the fused producer–consumer kernel route (kernels/fused.py) is
    # no longer a config bool — it is steered by repro.cluster.KernelPolicy
    # (mode="fused"), scoped via `with cluster.policy(...)` or pinned with
    # the step factories' `policy=` argument.

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    def n_params(self) -> int:
        """Total parameter count (for MODEL_FLOPS = 6 N D)."""
        from repro.models import steps
        specs = steps.param_specs(self)
        leaves = jax.tree.leaves(
            specs, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "logical"))
        total = 0
        for s in leaves:
            n = 1
            for d in s.shape:
                n *= d
            total += n
        return total

    def n_active_params(self) -> int:
        """Active parameters per token (MoE discounts inactive experts)."""
        total = self.n_params()
        if self.n_experts and self.top_k:
            from repro.models import steps
            specs = steps.param_specs(self)
            expert = 0
            for s in jax.tree.leaves(
                    specs["blocks"],
                    is_leaf=lambda x: hasattr(x, "logical")):
                if "expert" in (s.logical or ()):
                    n = 1
                    for d in s.shape:
                        n *= d
                    expert += n
            total = total - expert + expert * self.top_k // self.n_experts
        return total


# ----------------------------------------------------------------------------
# Workload shapes (assignment)
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# ----------------------------------------------------------------------------
# The ten assigned architectures (exact per assignment table)
# ----------------------------------------------------------------------------

ARCHS: dict[str, ArchConfig] = {}


def _reg(cfg: ArchConfig) -> ArchConfig:
    ARCHS[cfg.name] = cfg
    return cfg


QWEN15_32B = _reg(ArchConfig(
    name="qwen1.5-32b", family="dense", n_layers=64, d_model=5120, n_heads=40,
    n_kv_heads=40, d_ff=27392, vocab=152064, qkv_bias=True, rope_theta=1e6,
    grad_accum=8))

YI_34B = _reg(ArchConfig(
    name="yi-34b", family="dense", n_layers=60, d_model=7168, n_heads=56,
    n_kv_heads=8, d_ff=20480, vocab=64000, rope_theta=5e6, grad_accum=8))

DEEPSEEK_67B = _reg(ArchConfig(
    name="deepseek-67b", family="dense", n_layers=95, d_model=8192, n_heads=64,
    n_kv_heads=8, d_ff=22016, vocab=102400, rope_theta=1e4, grad_accum=16))

QWEN3_14B = _reg(ArchConfig(
    name="qwen3-14b", family="dense", n_layers=40, d_model=5120, n_heads=40,
    n_kv_heads=8, d_ff=17408, vocab=151936, qk_norm=True, rope_theta=1e6,
    grad_accum=4))

GROK_1 = _reg(ArchConfig(
    name="grok-1-314b", family="moe", n_layers=64, d_model=6144, n_heads=48,
    n_kv_heads=8, d_ff=32768, vocab=131072, n_experts=8, top_k=2,
    pattern=("attn_moe",), moment_dtype="bfloat16", grad_accum=16,
    remat="nothing"))

MIXTRAL_8X7B = _reg(ArchConfig(
    name="mixtral-8x7b", family="moe", n_layers=32, d_model=4096, n_heads=32,
    n_kv_heads=8, d_ff=14336, vocab=32000, n_experts=8, top_k=2,
    window=4096, pattern=("attn_moe",), rope_theta=1e6, grad_accum=4,
    sub_quadratic=True))

WHISPER_SMALL = _reg(ArchConfig(
    name="whisper-small", family="encdec", n_layers=12, d_model=768,
    n_heads=12, n_kv_heads=12, d_ff=3072, vocab=51865, norm="layer",
    ffn_kind="gelu", n_enc_layers=12, enc_seq=1500, pattern=("attn_cross",)))

XLSTM_125M = _reg(ArchConfig(
    name="xlstm-125m", family="ssm", n_layers=12, d_model=768, n_heads=4,
    n_kv_heads=4, d_ff=0, vocab=50304, head_dim=192,
    pattern=("mlstm", "mlstm", "mlstm", "slstm"), sub_quadratic=True,
    # 125M model: TP over the recurrent width would insert per-timestep
    # collectives inside the sLSTM scan; run DP/FSDP-only (the MemPool
    # "keep private data in the local tile" choice for a tiny model).
    rules_overrides=(("ffn", None), ("heads", None), ("kv_heads", None))))

RECURRENTGEMMA_9B = _reg(ArchConfig(
    name="recurrentgemma-9b", family="hybrid", n_layers=38, d_model=4096,
    n_heads=16, n_kv_heads=1, d_ff=12288, vocab=256000, head_dim=256,
    ffn_kind="geglu", window=2048, lru_width=4096,
    pattern=("rglru", "rglru", "local_attn"), sub_quadratic=True,
    grad_accum=4))

LLAMA32_VISION_90B = _reg(ArchConfig(
    name="llama-3.2-vision-90b", family="vlm", n_layers=100, d_model=8192,
    n_heads=64, n_kv_heads=8, d_ff=28672, vocab=128256, rope_theta=5e5,
    cross_every=5, n_img_tokens=1601, pattern=("attn",), grad_accum=16))

FALCON_H1_34B = _reg(ArchConfig(
    name="falcon-h1-34b", family="hybrid", n_layers=72, d_model=5120,
    n_heads=20, n_kv_heads=4, head_dim=128, d_ff=21504, vocab=261120,
    rope_theta=1e11, norm_eps=1e-5, pattern=("attn_ssm",), conv_width=4,
    ssm_heads=32, ssm_head_dim=128, ssm_state=256, ssm_groups=2,
    embed_mult=5.656854249492381, lm_head_mult=0.0078125,
    attn_in_mult=1.0, attn_out_mult=0.0375,
    key_mult=0.011048543456039804, ssm_in_mult=0.25,
    ssm_out_mult=0.08838834764831845,
    ssm_mults=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
               0.3535533905932738),
    mlp_mults=(0.1767766952966369, 0.011160714285714284), grad_accum=8))


# the paper's own evaluation target: a 256-PE kernel cluster; used by the
# Table-1 benchmarks rather than the LM pipeline.
MEMPOOL_PAPER = dict(
    name="mempool-256", n_cores=256, l1_kib=1024, banks=1024,
    kernels=("matmul", "conv2d", "dct8x8", "axpy", "dotp"))


# ----------------------------------------------------------------------------
# Kernel tune records (written by kernels/pipeline.autotune)
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelTuneRecord:
    """One autotuned blocking for a (kernel, shape) cell.

    `blocks` / `default_blocks` are sorted (name, value) tuples so records
    stay hashable; `modeled_seconds` are the pipeline cost-model scores the
    autotuner *ranked* candidates with (roofline terms x interconnect
    locality penalty). The winner itself is picked by on-device timing:
    `measured_us` / `default_us` are the raced wall times (median of
    repeats) of the winning blocking and the hand-picked default, and
    `measured_speedup` is their ratio — the only speedup this record
    claims. `source` says how the record was produced: "timed" (raced),
    "modeled" (score-only fallback — frozen mode or no operand factory),
    or "db" (warm-started from a TuneDB written by an earlier timed run).
    """

    kernel: str
    shape_key: str
    blocks: tuple[tuple[str, int], ...]
    modeled_seconds: float
    default_blocks: tuple[tuple[str, int], ...] = ()
    default_modeled_seconds: float = 0.0
    # fused kernels only: the intermediate write+read the fusion removed
    # from HBM under the winning blocking (0.0 for unfused kernels)
    saved_bytes: float = 0.0
    # timed-race results (0.0 when source == "modeled": never raced)
    measured_us: float = 0.0
    default_us: float = 0.0
    source: str = "modeled"
    # which implementation won the race: "fused" = the Pallas kernel body
    # itself (with `blocks`), "unfused" = the op's unfused composition of
    # primitive kernels beat every blocking — tuned_call dispatches the
    # composition for this (kernel, shape) cell
    route: str = "fused"

    @property
    def timed(self) -> bool:
        return self.measured_us > 0.0

    @property
    def measured_speedup(self) -> float:
        """Real raced speedup of the tuned blocking over the default.

        >= 1.0 by construction for timed records (the default is always in
        the race, so the winner is never measurably slower); 1.0 for
        modeled-only records, which claim nothing.
        """
        if not self.timed:
            return 1.0
        return self.default_us / max(self.measured_us, 1e-30)


KERNEL_TUNES: dict[tuple[str, str], KernelTuneRecord] = {}


def register_kernel_tune(rec: KernelTuneRecord) -> KernelTuneRecord:
    KERNEL_TUNES[(rec.kernel, rec.shape_key)] = rec
    return rec


def get_kernel_tune(kernel: str, shape_key: str) -> KernelTuneRecord | None:
    return KERNEL_TUNES.get((kernel, shape_key))


def kernel_tunes() -> list[KernelTuneRecord]:
    return [KERNEL_TUNES[k] for k in sorted(KERNEL_TUNES)]


# ----------------------------------------------------------------------------
# Reduced same-family smoke variants
# ----------------------------------------------------------------------------

def smoke(cfg: ArchConfig) -> ArchConfig:
    """Tiny same-family config: few layers, narrow, tiny vocab."""
    period = len(cfg.pattern)
    n_layers = max(2 * period, 2)
    if cfg.cross_every:
        n_layers = 2 * cfg.cross_every          # keep one cross layer in scan
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        n_layers=n_layers,
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 4) if cfg.n_kv_heads > 1 else 1,
        head_dim=16,
        d_ff=128 if cfg.d_ff else 0,
        vocab=256,
        n_experts=min(cfg.n_experts, 4) if cfg.n_experts else 0,
        top_k=min(cfg.top_k, 2) if cfg.top_k else 0,
        n_enc_layers=2 if cfg.n_enc_layers else 0,
        enc_seq=16 if cfg.enc_seq else 0,
        n_img_tokens=8 if cfg.n_img_tokens else 0,
        window=min(cfg.window, 16) if cfg.window else None,
        lru_width=64 if cfg.lru_width else 0,
        ssm_heads=4 if cfg.ssm_heads else 0,
        ssm_head_dim=16 if cfg.ssm_head_dim else 0,
        ssm_state=16 if cfg.ssm_state else 0,
        ssm_groups=min(cfg.ssm_groups, 2),
        attn_chunk=8,
        grad_accum=1,
        moment_dtype="float32",
    )


def get(name: str) -> ArchConfig:
    if name.endswith("-smoke"):
        return smoke(ARCHS[name.removesuffix("-smoke")])
    return ARCHS[name]


# ----------------------------------------------------------------------------
# input_specs: abstract inputs per (arch x shape), no allocation
# ----------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict[str, Any]:
    """ShapeDtypeStructs for every model input of this workload."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        batch = {"tokens": sds((B, S), i32), "labels": sds((B, S), i32)}
        if cfg.family == "encdec":
            batch["enc_embeds"] = sds((B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
        if cfg.family == "vlm":
            batch["img_embeds"] = sds((B, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16)
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": sds((B, S), i32)}
        if cfg.family == "encdec":
            batch["enc_embeds"] = sds((B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
        if cfg.family == "vlm":
            batch["img_embeds"] = sds((B, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16)
        return batch
    if shape.kind == "decode":
        batch = {"tokens": sds((B, 1), i32), "pos": sds((), i32)}
        if cfg.family == "encdec":
            batch["enc_embeds"] = sds((B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
        if cfg.family == "vlm":
            batch["img_embeds"] = sds((B, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16)
        return batch
    raise ValueError(shape.kind)


def cell_supported(cfg: ArchConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Is this (arch x shape) cell runnable? (per assignment skip rules)."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, ("full quadratic attention at 524288 tokens; skipped per "
                       "assignment (noted in DESIGN.md §5)")
    return True, ""

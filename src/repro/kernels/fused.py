"""Fused producer–consumer kernels for the transformer hot loop.

Every kernel here is a producer stitched into a consumer's grid through the
tile-pipeline fusion hooks (kernels/pipeline.py): the producer's output tile
never exists in HBM — it is computed in VMEM in the same grid step that
consumes it, exactly the MemPool story of intermediate tiles living in
shared L1 until the whole cluster is done with them.

  rmsnorm_matmul       norm folded into the matmul A-tile *prologue*
                       (requires the full reduction dim resident per tile —
                       checked via check_fusable, the "producer tile fully
                       consumed per step" condition)
  matmul_bias_act      bias + GELU/SiLU applied in the output *epilogue*
                       after the K loop, before writeback
  matmul_residual_add  residual tile streamed in and added in the epilogue
  flash_attention_proj flash attention with the output projection fused:
                       per-head outputs are projected and accumulated across
                       heads in a VMEM register tile; the (B, H, S, hd)
                       attention output never touches HBM

Each registers a `KernelDef` so the autotuner scores fused candidates
directly; their `Traffic.saved_bytes` records the intermediate write+read
the fusion eliminated (the term the fused roofline drops).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa
from . import matmul as _mm
from . import pipeline as pp
from . import rmsnorm as _rn

F32 = jnp.float32

ACTIVATIONS = {
    "none": lambda x: x,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
}


# ----------------------------------------------------------------------------
# rmsnorm_matmul — norm in the A-tile prologue
# ----------------------------------------------------------------------------

def _norm_tile(a, scale, eps: float):
    """Row-normalize one (bm, k) tile; valid only when k is the full row."""
    af = a.astype(F32)
    var = jnp.mean(af * af, axis=-1, keepdims=True)
    out = af * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))
    return out.astype(a.dtype)


def build_rmsnorm_matmul(m: int, n: int, k: int, dtype, *, eps: float = 1e-6,
                         bm: int | None = None, bn: int | None = None,
                         dtype_bytes: int = 4) -> pp.KernelPipeline:
    bm = pp.resolve_block(m, bm, default=256)
    bn = pp.resolve_block(n, bn, default=256)
    # bk = k: the prologue normalizes whole rows, so the A tile must hold
    # the full reduction dim. check_fusable enforces it against the real
    # producer/consumer TileSpecs rather than trusting this constructor.
    consumer = _mm.build_pipeline(m, n, k, dtype, bm=bm, bn=bn, bk=k,
                                  dtype_bytes=dtype_bytes)
    producer = _rn.build_pipeline(m, k, dtype, eps=eps, block_rows=bm,
                                  dtype_bytes=dtype_bytes)
    pp.check_fusable(producer.out_tiles[0], consumer.in_tiles[0],
                     full_dims=(1,), dims=(k,))
    return consumer.fuse(
        name="rmsnorm_matmul",
        prologues={0: lambda a, s_ref: _norm_tile(a, s_ref[...], eps)},
        extra_tiles=[pp.TileSpec((k,), lambda i, j, s: (0,))],
        cost=traffic_rmsnorm_matmul({"m": m, "n": n, "k": k},
                                    {"bm": bm, "bn": bn}, dtype_bytes),
    )


def rmsnorm_matmul(x: jax.Array, scale: jax.Array, w: jax.Array, *,
                   eps: float = 1e-6, bm: int | None = None,
                   bn: int | None = None, interpret: bool = False) -> jax.Array:
    """matmul(rmsnorm(x, scale), w) in one HBM pass over x.

    x: (M, K); scale: (K,); w: (K, N). The normalized x never exists in HBM.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert scale.shape == (k,), scale.shape
    pipe = build_rmsnorm_matmul(m, n, k, x.dtype, eps=eps, bm=bm, bn=bn,
                                dtype_bytes=x.dtype.itemsize)
    return pipe(x, w, scale, interpret=interpret)


def traffic_rmsnorm_matmul(shapes: dict, blocks: dict,
                           dtype_bytes: int = 4) -> pp.Traffic:
    m, n, k = shapes["m"], shapes["n"], shapes["k"]
    bm = min(blocks["bm"], m)
    bn = min(blocks["bn"], n)
    consumer = _mm.traffic(shapes, {"bm": bm, "bn": bn, "bk": k}, dtype_bytes)
    producer = _rn.traffic({"m": m, "d": k}, {"block_rows": bm}, dtype_bytes)
    return pp.fused_traffic(consumer, producer,
                            intermediate_bytes=float(m * k * dtype_bytes),
                            # the scale, double-buffered, and the
                            # prologue's normalized (bm, k) A tile
                            extra_vmem=(2 * k + bm * k) * dtype_bytes,
                            refetch=n // bn)


def _tune_rmsnorm_matmul(shapes: dict):
    m, n = shapes["m"], shapes["n"]
    for bm in pp.block_candidates(m, align=pp.mxu_align(m), cap=6):
        for bn in pp.block_candidates(n, align=pp.mxu_align(n), cap=6):
            yield {"bm": bm, "bn": bn}


pp.register(pp.KernelDef(
    name="rmsnorm_matmul", traffic=traffic_rmsnorm_matmul,
    tune_space=_tune_rmsnorm_matmul,
    default_blocks=lambda s: {"bm": pp.snap_block(s["m"], 256),
                              "bn": pp.snap_block(s["n"], 256)}))


# ----------------------------------------------------------------------------
# matmul_bias_act — bias + activation in the output epilogue
# ----------------------------------------------------------------------------

def build_matmul_bias_act(m: int, n: int, k: int, dtype, *, act: str = "gelu",
                          bm: int | None = None, bn: int | None = None,
                          bk: int | None = None,
                          dtype_bytes: int = 4) -> pp.KernelPipeline:
    act_fn = ACTIVATIONS[act]
    consumer = _mm.build_pipeline(m, n, k, dtype, bm=bm, bn=bn, bk=bk,
                                  dtype_bytes=dtype_bytes)
    bn_r = consumer.out_tiles[0].block[1]
    return consumer.fuse(
        name="matmul_bias_act",
        epilogue=lambda o, b_ref: act_fn(o.astype(F32)
                                         + b_ref[...].astype(F32)),
        extra_tiles=[pp.TileSpec((bn_r,), lambda i, j, s: (j,))],
        cost=traffic_matmul_bias_act(
            {"m": m, "n": n, "k": k},
            {"bm": consumer.out_tiles[0].block[0], "bn": bn_r,
             "bk": consumer.in_tiles[0].block[1]},
            dtype_bytes, act=act),
    )


def matmul_bias_act(a: jax.Array, b: jax.Array, bias: jax.Array, *,
                    act: str = "gelu", bm: int | None = None,
                    bn: int | None = None, bk: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """act(a @ b + bias) without the pre-activation round-trip.

    a: (M, K); b: (K, N); bias: (N,); act in {"none", "gelu", "silu"}.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and bias.shape == (n,), (a.shape, b.shape, bias.shape)
    pipe = build_matmul_bias_act(m, n, k, a.dtype, act=act, bm=bm, bn=bn,
                                 bk=bk, dtype_bytes=a.dtype.itemsize)
    return pipe(a, b, bias, interpret=interpret)


def traffic_matmul_bias_act(shapes: dict, blocks: dict, dtype_bytes: int = 4,
                            *, act: str = "gelu") -> pp.Traffic:
    m, n = shapes["m"], shapes["n"]
    consumer = _mm.traffic(shapes, blocks, dtype_bytes)
    producer = pp.Traffic(
        flops=2.0 * m * n,                       # bias add + activation
        hbm_bytes=float((2 * m * n + n) * dtype_bytes),
        ideal_bytes=float((2 * m * n + n) * dtype_bytes),
        grid_steps=1, vmem_bytes=0,
        transcendentals=float(m * n) if act != "none" else 0.0)
    bn = min(blocks["bn"], n)
    return pp.fused_traffic(consumer, producer,
                            intermediate_bytes=float(m * n * dtype_bytes),
                            extra_vmem=2 * bn * dtype_bytes)


pp.register(pp.KernelDef(
    name="matmul_bias_act", traffic=traffic_matmul_bias_act,
    tune_space=_mm.tune_space,
    default_blocks=lambda s: {"bm": pp.snap_block(s["m"], 256),
                              "bn": pp.snap_block(s["n"], 256),
                              "bk": pp.snap_block(s["k"], 256)}))


# ----------------------------------------------------------------------------
# matmul_residual_add — residual tile streamed into the epilogue
# ----------------------------------------------------------------------------

def build_matmul_residual_add(m: int, n: int, k: int, dtype, *,
                              bm: int | None = None, bn: int | None = None,
                              bk: int | None = None,
                              dtype_bytes: int = 4) -> pp.KernelPipeline:
    consumer = _mm.build_pipeline(m, n, k, dtype, bm=bm, bn=bn, bk=bk,
                                  dtype_bytes=dtype_bytes)
    bm_r, bn_r = consumer.out_tiles[0].block
    # the residual tile must match the output tile exactly — same check the
    # prologue fusions make, from the consumer side
    pp.check_fusable(pp.TileSpec((bm_r, bn_r), lambda i, j, s: (i, j)),
                     consumer.out_tiles[0])
    return consumer.fuse(
        name="matmul_residual_add",
        epilogue=lambda o, r_ref: o.astype(F32) + r_ref[...].astype(F32),
        extra_tiles=[pp.TileSpec((bm_r, bn_r), lambda i, j, s: (i, j))],
        cost=traffic_matmul_residual_add(
            {"m": m, "n": n, "k": k},
            {"bm": bm_r, "bn": bn_r, "bk": consumer.in_tiles[0].block[1]},
            dtype_bytes),
    )


def matmul_residual_add(a: jax.Array, b: jax.Array, res: jax.Array, *,
                        bm: int | None = None, bn: int | None = None,
                        bk: int | None = None,
                        interpret: bool = False) -> jax.Array:
    """a @ b + res without the matmul output round-trip. res: (M, N)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and res.shape == (m, n), (a.shape, b.shape, res.shape)
    pipe = build_matmul_residual_add(m, n, k, a.dtype, bm=bm, bn=bn, bk=bk,
                                     dtype_bytes=a.dtype.itemsize)
    return pipe(a, b, res, interpret=interpret)


def traffic_matmul_residual_add(shapes: dict, blocks: dict,
                                dtype_bytes: int = 4) -> pp.Traffic:
    m, n = shapes["m"], shapes["n"]
    consumer = _mm.traffic(shapes, blocks, dtype_bytes)
    producer = pp.Traffic(
        flops=float(m * n),
        hbm_bytes=float(3 * m * n * dtype_bytes),   # read o + res, write out
        ideal_bytes=float(3 * m * n * dtype_bytes),
        grid_steps=1, vmem_bytes=0)
    bm = min(blocks["bm"], m)
    bn = min(blocks["bn"], n)
    return pp.fused_traffic(consumer, producer,
                            intermediate_bytes=float(m * n * dtype_bytes),
                            # the residual tile, double-buffered, and the
                            # epilogue's f32 (bm, bn) sum
                            extra_vmem=2 * bm * bn * dtype_bytes + 4 * bm * bn)


pp.register(pp.KernelDef(
    name="matmul_residual_add", traffic=traffic_matmul_residual_add,
    tune_space=_mm.tune_space,
    default_blocks=lambda s: {"bm": pp.snap_block(s["m"], 256),
                              "bn": pp.snap_block(s["n"], 256),
                              "bk": pp.snap_block(s["k"], 256)}))


# ----------------------------------------------------------------------------
# flash_attention_proj — output projection fused across heads
# ----------------------------------------------------------------------------
#
# The head axis moves *inside* the q-block axis and becomes sequential, so
# a (bq, d_model) projection accumulator in VMEM scratch can sum per-head
# contributions o_h @ Wo[h] across the whole head loop; only the final
# (B, S, d_model) projection result is written to HBM. This is the epilogue
# idea applied where the "epilogue" is itself a reduction over a grid axis.

def _fa_proj_kernel(q_ref, k_ref, v_ref, wo_ref, o_ref,
                    m_ref, l_ref, acc_ref, pacc_ref, *,
                    scale: float, n_k: int, n_h: int, bq: int, bk: int,
                    causal: bool):
    i = pl.program_id(1)
    h = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(jnp.logical_and(h == 0, j == 0))
    def _init_proj():
        pacc_ref[...] = jnp.zeros_like(pacc_ref)

    @pl.when(j == 0)
    def _init_head():
        m_ref[...] = jnp.full_like(m_ref, _fa.NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                # (bq, hd)
    k = k_ref[0, 0]                                # (bk, hd)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale
    if causal:
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, _fa.NEG)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=F32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == n_k - 1)
    def _project_head():
        o_head = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)   # (bq, hd) f32
        pacc_ref[...] += jax.lax.dot_general(
            o_head.astype(wo_ref.dtype), wo_ref[0],
            (((1,), (0,)), ((), ())), preferred_element_type=F32)

    @pl.when(jnp.logical_and(h == n_h - 1, j == n_k - 1))
    def _store():
        o_ref[0] = pacc_ref[...].astype(o_ref.dtype)


def build_flash_attention_proj(b: int, h: int, kv: int, s: int, hd: int,
                               dm: int, dtype, *, causal: bool = True,
                               bq: int | None = None, bk: int | None = None,
                               dtype_bytes: int = 4) -> pp.KernelPipeline:
    group = h // kv
    bq = pp.resolve_block(s, bq, default=512)
    bk = pp.resolve_block(s, bk, default=512)
    n_q, n_k = s // bq, s // bk
    body = functools.partial(_fa_proj_kernel, scale=hd ** -0.5, n_k=n_k,
                             n_h=h, bq=bq, bk=bk, causal=causal)
    return pp.KernelPipeline(
        name="flash_attention_proj",
        body=body,
        # heads sequential *inside* each q block so the projection
        # accumulator (the fused epilogue's register tile) carries across it
        grid=(pp.GridAxis("batch", b, "parallel"),
              pp.GridAxis("q", n_q, "parallel"),
              pp.GridAxis("heads", h, "arbitrary"),
              pp.GridAxis("kv", n_k, "arbitrary")),
        in_tiles=[
            pp.TileSpec((1, 1, bq, hd),
                        lambda b_, i, h_, j: (b_, h_, i, 0)),
            pp.TileSpec((1, 1, bk, hd),
                        lambda b_, i, h_, j: (b_, h_ // group, j, 0)),
            pp.TileSpec((1, 1, bk, hd),
                        lambda b_, i, h_, j: (b_, h_ // group, j, 0)),
            pp.TileSpec((1, hd, dm), lambda b_, i, h_, j: (h_, 0, 0)),
        ],
        out_tiles=pp.TileSpec((1, bq, dm), lambda b_, i, h_, j: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, dm), dtype),
        scratch=[
            pltpu.VMEM((bq, 1), F32),
            pltpu.VMEM((bq, 1), F32),
            pltpu.VMEM((bq, hd), F32),
            pltpu.VMEM((bq, dm), F32),             # projection accumulator
        ],
        cost=traffic_flash_attention_proj(
            {"b": b, "h": h, "kv": kv, "s": s, "hd": hd, "dm": dm},
            {"bq": bq, "bk": bk}, dtype_bytes, causal=causal),
    )


def flash_attention_proj(q, k, v, wo, *, causal: bool = True,
                         bq: int | None = None, bk: int | None = None,
                         interpret: bool = False):
    """einsum("bhsk,hkd->bsd", attention(q, k, v), wo) in one kernel.

    q: (B, H, S, hd); k/v: (B, KV, S, hd); wo: (H, hd, d_model). The
    (B, H, S, hd) attention output never exists in HBM.
    """
    b, h, s, hd = q.shape
    kv = k.shape[1]
    dm = wo.shape[-1]
    assert wo.shape == (h, hd, dm), wo.shape
    pipe = build_flash_attention_proj(b, h, kv, s, hd, dm, q.dtype,
                                      causal=causal, bq=bq, bk=bk,
                                      dtype_bytes=q.dtype.itemsize)
    return pipe(q, k, v, wo, interpret=interpret)


def traffic_flash_attention_proj(shapes: dict, blocks: dict,
                                 dtype_bytes: int = 4, *,
                                 causal: bool = True) -> pp.Traffic:
    b, h, s, hd = shapes["b"], shapes["h"], shapes["s"], shapes["hd"]
    dm = shapes["dm"]
    base = _fa.traffic(shapes, blocks, dtype_bytes, causal=causal)
    bq = min(blocks["bq"], s)
    bk = min(blocks["bk"], s)
    n_q = s // bq
    o_bytes = b * h * s * hd * dtype_bytes       # the eliminated intermediate
    wo_stream = b * n_q * h * hd * dm * dtype_bytes
    out = b * s * dm * dtype_bytes
    wo_ideal = h * hd * dm * dtype_bytes
    extra_vmem = (2 * hd * dm * dtype_bytes      # wo tile, double-buffered
                  + 4 * bq * dm                  # f32 projection accumulator
                  + 2 * bq * dm * dtype_bytes    # (bq, dm) out replaces o tile
                  - 2 * bq * hd * dtype_bytes)
    return pp.Traffic(
        flops=base.flops + 2.0 * b * s * h * hd * dm,
        hbm_bytes=base.hbm_bytes - o_bytes + wo_stream + out,
        ideal_bytes=base.ideal_bytes - o_bytes + wo_ideal + out,
        grid_steps=base.grid_steps,
        vmem_bytes=base.vmem_bytes + extra_vmem,
        transcendentals=base.transcendentals,
        saved_bytes=2.0 * o_bytes,
    )


def _tune_fa_proj(shapes: dict):
    s = shapes["s"]
    for bq in pp.block_candidates(s, align=pp.mxu_align(s), cap=6):
        for bk in pp.block_candidates(s, align=pp.mxu_align(s), cap=6):
            yield {"bq": bq, "bk": bk}


pp.register(pp.KernelDef(
    name="flash_attention_proj", traffic=traffic_flash_attention_proj,
    tune_space=_tune_fa_proj,
    default_blocks=lambda s: {"bq": pp.snap_block(s["s"], 512),
                              "bk": pp.snap_block(s["s"], 512)}))


# ----------------------------------------------------------------------------
# Fused-vs-unfused traffic accounting (the benchmark / acceptance model)
# ----------------------------------------------------------------------------

def fused_vs_unfused(name: str, shapes: dict, blocks: dict | None = None,
                     dtype_bytes: int = 4) -> dict:
    """Modeled HBM bytes of one fused kernel vs its unfused composition."""
    defn = pp.KERNELS[name]
    blocks = blocks or defn.default_blocks(shapes)
    t = defn.traffic(shapes, blocks, dtype_bytes)
    unfused = t.hbm_bytes + t.saved_bytes
    return {"fused_bytes": t.hbm_bytes, "unfused_bytes": unfused,
            "saved_bytes": t.saved_bytes,
            "reduction": unfused / max(t.hbm_bytes, 1.0)}


def transformer_block_traffic(b: int, s: int, d: int, h: int, kv: int,
                              hd: int, d_ff: int, *, dtype_bytes: int = 2,
                              attn_chunk: int = 512) -> dict:
    """Modeled HBM bytes of one transformer block, fused vs unfused.

    Unfused = today's model path composed of isolated ops: rmsnorm kernel
    round-trips the normed activations, each matmul round-trips its output,
    and attention is the chunked jnp baseline that crosses HBM ~3x per
    score block (the flash_attention.hbm_traffic_bytes baseline model).
    Fused = rmsnorm_matmul for qkv/gate/up, flash_attention_proj for
    attention + output projection, matmul_residual_add for the down
    projection; remaining elementwise traffic identical on both sides.
    """
    m = b * s
    db = dtype_bytes
    qkv_cols = (h + 2 * kv) * hd

    def mm_bytes(mm_m, mm_k, mm_n):
        # compulsory matmul traffic (blocking-independent terms only, so the
        # comparison isolates what fusion changes)
        return (mm_m * mm_k + mm_k * mm_n + mm_m * mm_n) * db

    # --- unfused composition -------------------------------------------------
    attn = _fa.hbm_traffic_bytes(b, h, kv, s, hd, db)
    unfused = {
        "norm_attn": 2 * m * d * db + d * db,
        "qkv": mm_bytes(m, d, qkv_cols) + 2 * m * d * db,  # normed x read 3x
        "attention": attn["baseline_bytes"],
        "out_proj": mm_bytes(m, h * hd, d),
        "residual_attn": 3 * m * d * db,
        "norm_ffn": 2 * m * d * db + d * db,
        "gate_up": mm_bytes(m, d, d_ff) * 2 + m * d * db,  # normed x read 2x
        "act_mult": 3 * m * d_ff * db,
        "down": mm_bytes(m, d_ff, d),
        "residual_ffn": 3 * m * d * db,
    }

    # --- fused path ----------------------------------------------------------
    fa_shapes = {"b": b, "h": h, "kv": kv, "s": s, "hd": hd, "dm": d}
    fa_blocks = {"bq": pp.snap_block(s, attn_chunk),
                 "bk": pp.snap_block(s, attn_chunk)}
    fused = {
        # norm recomputed in the prologue per consumer; x read per consumer
        "qkv": mm_bytes(m, d, qkv_cols) + 2 * m * d * db + 3 * d * db,
        "attention_proj": traffic_flash_attention_proj(
            fa_shapes, fa_blocks, db).ideal_bytes,
        "residual_attn": 3 * m * d * db,
        "gate_up": mm_bytes(m, d, d_ff) * 2 + m * d * db + 2 * d * db,
        "act_mult": 3 * m * d_ff * db,
        "down_residual": mm_bytes(m, d_ff, d) + m * d * db,
    }
    u_total = float(sum(unfused.values()))
    f_total = float(sum(fused.values()))
    return {"unfused": unfused, "fused": fused,
            "unfused_bytes": u_total, "fused_bytes": f_total,
            "reduction": u_total / max(f_total, 1.0)}

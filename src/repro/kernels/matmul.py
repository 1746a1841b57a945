"""Blocked MXU matmul — the paper's `matmul` kernel, TPU-native.

MemPool's matmul gives each core a 4x4 output tile in registers (8 loads per
16 MACs) to maximize compute intensity. The TPU translation on the shared
tile-pipeline layer: each grid cell owns a (bm, bn) output tile held in VMEM
scratch across the K loop (the "register tile"), streaming (bm, bk) /
(bk, bn) operand tiles from HBM (the "remote banks") — identical locality
story, MXU-aligned block shapes. This is the kernel where the autotuner's
locality term matters most: A is re-streamed N/bn times and B M/bm times, so
bigger output tiles raise p_local exactly like MemPool's register blocking.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import mesh as hw

from . import pipeline as pp


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def build_pipeline(m: int, n: int, k: int, dtype, *, bm: int | None = None,
                   bn: int | None = None, bk: int | None = None,
                   dtype_bytes: int = 4) -> pp.KernelPipeline:
    bm = pp.resolve_block(m, bm, default=256)
    bn = pp.resolve_block(n, bn, default=256)
    bk = pp.resolve_block(k, bk, default=256)
    n_k = k // bk
    return pp.KernelPipeline(
        name="matmul",
        body=functools.partial(_matmul_kernel, n_k=n_k),
        grid=(pp.GridAxis("m", m // bm, "parallel"),
              pp.GridAxis("n", n // bn, "parallel"),
              pp.GridAxis("k", n_k, "arbitrary")),
        in_tiles=[
            pp.TileSpec((bm, bk), lambda i, j, s: (i, s)),
            pp.TileSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_tiles=pp.TileSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), dtype),
        scratch=[pltpu.VMEM((bm, bn), jnp.float32)],
        cost=traffic({"m": m, "n": n, "k": k},
                     {"bm": bm, "bn": bn, "bk": bk}, dtype_bytes),
    )


def matmul(a: jax.Array, b: jax.Array, *, bm: int | None = None,
           bn: int | None = None, bk: int | None = None,
           interpret: bool = False) -> jax.Array:
    """a: (M, K) @ b: (K, N); M, N, K multiples of the block sizes."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    pipe = build_pipeline(m, n, k, a.dtype, bm=bm, bn=bn, bk=bk,
                          dtype_bytes=a.dtype.itemsize)
    return pipe(a, b, interpret=interpret)


# -- pipeline-layer contract --------------------------------------------------

def body_vmem(bm: int, bk: int, dtype_bytes: int) -> int:
    """VMEM the compiler adds for the kernel body beyond the pipeline's
    buffers: one more (bm, bk) A tile and an f32 (bm, 128) strip. Measured
    by compiling for v5e and bisecting `vmem_limit_bytes` (4096x5120x5120
    bf16 at four blockings); an upper bound at each, so a blocking the
    autotuner admits is one the compiler accepts."""
    return dtype_bytes * bm * bk + 4 * bm * hw.MXU_TILE


def traffic(shapes: dict, blocks: dict, dtype_bytes: int = 4) -> pp.Traffic:
    m, n, k = shapes["m"], shapes["n"], shapes["k"]
    bm = min(blocks["bm"], m)
    bn = min(blocks["bn"], n)
    bk = min(blocks["bk"], k)
    # A streamed once per N-block column, B once per M-block row
    streamed = dtype_bytes * (m * k * (n // bn) + k * n * (m // bm) + m * n)
    ideal = dtype_bytes * (m * k + k * n + m * n)
    vmem = (2 * dtype_bytes * (bm * bk + bk * bn)   # double-buffered operands
            + 2 * dtype_bytes * bm * bn             # output tile
            + 4 * bm * bn                           # f32 accumulator scratch
            + body_vmem(bm, bk, dtype_bytes))
    return pp.Traffic(
        flops=2.0 * m * n * k,
        hbm_bytes=float(streamed),
        ideal_bytes=float(ideal),
        grid_steps=(m // bm) * (n // bn) * (k // bk),
        vmem_bytes=vmem,
    )


def tune_space(shapes: dict):
    m, n, k = shapes["m"], shapes["n"], shapes["k"]
    for bm in pp.block_candidates(m, align=pp.mxu_align(m), cap=6):
        for bn in pp.block_candidates(n, align=pp.mxu_align(n), cap=6):
            for bk in pp.block_candidates(k, align=pp.mxu_align(k), cap=6):
                yield {"bm": bm, "bn": bn, "bk": bk}


def _defaults(shapes: dict) -> dict:
    return {"bm": pp.snap_block(shapes["m"], 256),
            "bn": pp.snap_block(shapes["n"], 256),
            "bk": pp.snap_block(shapes["k"], 256)}


pp.register(pp.KernelDef(
    name="matmul", traffic=traffic, tune_space=tune_space,
    default_blocks=_defaults))

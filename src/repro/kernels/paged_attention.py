"""Paged decode attention — one token per slot against the shared KV pool.

The decode step's attention reads each slot's K/V through its page table.
Gathering every slot's whole page list into a contiguous cache and masking
it (`models/attention.paged_gather` + `decode_attention`, this kernel's
reference) moves `max_seq` rows a slot, every layer, whatever the slot's
length. This kernel walks each slot's table instead and DMAs only the pages
below its length, `pages_per_block` pages at a time, double-buffered: the
next block's pages (the next slot's first block, after a slot's last) are
in flight while the current block computes. Pages past a slot's length
cost neither a DMA nor a loop step.

The pool keeps its layout, (n_pages, page_size, KV, hd): a page is one
contiguous DMA holding every KV head. The kernel views it as
(n_pages, page_size * KV, hd) — merging the two middle axes keeps the
tiled layout — so a block of K is a (rows, hd) matrix with row
`t * KV + k` for position t and KV head k. Scores come from one matmul of
all H query heads against all rows; a query head keeps only its own KV
head's rows (and rows below the length), so the softmax and PV matmul
see exactly the grouped-query terms. Scores and softmax are float32
(online over blocks); `p` is cast to the V dtype for PV, as in
`decode_attention`. Rows past the length inside the last live page get
exactly zero weight, and V rows past the length are zeroed before PV,
so whatever stale or uninitialised data sits there (NaN included) never
reaches the output.

One invocation covers the batch (a grid of one step): the loops over
slots and over each slot's blocks run inside the kernel, with the
lengths and the flattened page table as scalar-prefetch operands in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pipeline as pp

NEG = -1e30
# a block holds this many (position, KV head) rows of K, and as many of V:
# 512 KiB each at hd 128 in bf16, 16 pages of 16 at KV 8 and 32 at KV 4
# (on a v5e, 8 layers of 32 slots at a mean length of 457: at KV 8, 16
# pages beat 4 and 8 by 9-24% and tie 32; at KV 4, 32 beat 16 by 7%)
BLOCK_ROWS = 2048


def _kernel(lens_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *,
            ppb: int, ps: int, kv: int, npp: int, scale: float):
    n_slots, h, _ = q_ref.shape
    g = h // kv
    t_blk = ppb * ps                       # positions a block
    rows = t_blk * kv

    def length(b):
        return jnp.minimum(lens_ref[b], npp * ps)

    def for_block_pages(b, i, buf, act):
        """Start (or wait for) the DMAs of slot b's block i into `buf`:
        only its pages below the slot's length."""
        first = i * ppb
        n_live = (length(b) + ps - 1) // ps

        def page(j, carry):
            p = tbl_ref[b * npp + first + j]
            for src, dst, s in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                act(pltpu.make_async_copy(src.at[p], dst.at[buf, j],
                                          sems.at[s, buf]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(ppb, n_live - first), page, 0)

    for_block_pages(0, 0, 0, lambda c: c.start())
    head_of_row = jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0) // g
    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)

    def slot_body(b, buf):
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        n = length(b)
        n_blocks = (n + t_blk - 1) // t_blk
        q = q_ref[b]                                        # (H, hd)

        def block_body(i, buf):
            last = i + 1 == n_blocks
            nb, ni = jnp.where(last, b + 1, b), jnp.where(last, 0, i + 1)

            @pl.when(nb < n_slots)
            def _():
                for_block_pages(nb, ni, 1 - buf, lambda c: c.start())

            for_block_pages(b, i, buf, lambda c: c.wait())
            k = k_buf[buf].reshape(rows, -1)
            v = v_buf[buf].reshape(rows, -1)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            live = i * t_blk + col // kv < n
            s = jnp.where(live & (col % kv == head_of_row), s * scale, NEG)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
            v = jnp.where(i * t_blk + row // kv < n, v, jnp.zeros_like(v))
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new
            return 1 - buf

        buf = jax.lax.fori_loop(0, n_blocks, block_body, buf)
        o_ref[b] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        return buf

    jax.lax.fori_loop(0, n_slots, slot_body, 0)


def default_pages_per_block(ps: int, kv: int, npp: int) -> int:
    """The blocking read from the shapes: BLOCK_ROWS rows a block, at
    least one page and at most a slot's table."""
    return max(1, min(npp, BLOCK_ROWS // (ps * kv)))


def paged_decode_attention(q, k_pool, v_pool, lengths, pages, *,
                           pages_per_block: int | None = None,
                           interpret: bool = False):
    """q: (B, 1, H, hd); pools: (n_pages, page_size, KV, hd), read in
    place; lengths: (B,) int32, the positions each slot attends
    (`pos + 1`); pages: (B, pages_per_slot) int32 page table. Returns
    (B, 1, H, hd)."""
    b, _, h, hd = q.shape
    n_pages, ps, kv, _ = k_pool.shape
    npp = pages.shape[1]
    ppb = pages_per_block or default_pages_per_block(ps, kv, npp)
    body = functools.partial(_kernel, ppb=ppb, ps=ps, kv=kv, npp=npp,
                             scale=hd ** -0.5)
    whole = pl.BlockSpec((b, h, hd), lambda i, *_: (0, 0, 0))
    flat = (n_pages, ps * kv, hd)
    out = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, ppb, ps * kv, hd), k_pool.dtype),
                pltpu.VMEM((2, ppb, ps * kv, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        name="paged_decode_attention",
        interpret=interpret,
    )(jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,)),
      pages.astype(jnp.int32).reshape(-1), q[:, 0],
      k_pool.reshape(flat), v_pool.reshape(flat))
    return out[:, None]


# -- pipeline-layer contract --------------------------------------------------
# Shapes alone do not say how long the slots are: the model charges every
# slot its whole table, the gather's bytes, which the kernel reads only at
# full length.

def traffic(shapes: dict, blocks: dict, dtype_bytes: int = 4) -> pp.Traffic:
    b, h, kv, hd = shapes["b"], shapes["h"], shapes["kv"], shapes["hd"]
    ps, npp = shapes["ps"], shapes["npp"]
    ppb = min(blocks["pages_per_block"], npp)
    rows = ppb * ps * kv
    positions = npp * ps
    kv_bytes = 2 * b * positions * kv * hd * dtype_bytes
    qo_bytes = 2 * b * h * hd * dtype_bytes
    n_blocks = -(-npp // ppb)
    # both matmuls run every query head against every KV head's rows
    flops = 4.0 * b * h * positions * kv * hd
    vmem = (2 * 2 * rows * hd * dtype_bytes        # K and V, two buffers
            + 2 * qo_bytes                         # q in, out
            + 4 * (h * rows + 2 * h + h * hd))     # scores, m, l, acc
    return pp.Traffic(
        flops=flops,
        hbm_bytes=float(kv_bytes + qo_bytes),
        ideal_bytes=float(kv_bytes + qo_bytes),
        grid_steps=b * n_blocks,
        vmem_bytes=vmem,
        transcendentals=float(b * h * positions * kv),
    )


def _defaults(shapes: dict) -> dict:
    return {"pages_per_block": default_pages_per_block(
        shapes["ps"], shapes["kv"], shapes["npp"])}


def tune_space(shapes: dict):
    default = _defaults(shapes)["pages_per_block"]
    for ppb in sorted({1, 2, 4, 8, 16, 32, default}):
        if ppb <= shapes["npp"]:
            yield {"pages_per_block": ppb}


pp.register(pp.KernelDef(
    name="paged_decode_attention", traffic=traffic, tune_space=tune_space,
    default_blocks=_defaults))

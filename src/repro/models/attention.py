"""Attention with bounded memory, static shapes, and a flash-style VJP.

All variants are pure jnp (they must lower for the 512-chip CPU-hosted
dry-run; the Pallas flash kernel in kernels/flash_attention.py is the TPU
hot-spot implementation, validated against these in interpret mode).

Forward schedules (picked by `schedule=` or automatically):

  direct  — materialize (S x S) scores; only for small S (smoke tests).
  masked  — two-level scan over (q-chunk x kv-chunk) blocks with causal
            masking. Memory-bounded, but computes the full upper triangle
            and masks it: ~2x FLOP waste. This is the *baseline*.
  folded  — exact-causal balanced schedule: q-chunk i is folded with
            q-chunk nq-1-i so every fold processes exactly nq+1 kv blocks
            (the ring-attention load-balancing trick). ~0 wasted FLOPs.
            This is the §Perf "beyond-paper" optimization.
  banded  — sliding-window attention: each q chunk scans only the
            window/chunk + 1 kv blocks in its band. Exact for SWA and
            local attention; O(S*w) instead of O(S^2).

Backward: a shared custom_vjp in the FlashAttention style — only
(q, k, v, out, lse) are saved and score blocks are *recomputed* per (i, j)
pair. Without this, jax.lax.scan's backward stacks every block's scores
across iterations: O(S^2) residual memory (observed: 10 GiB buffers per
layer at S=4096), which no remat policy can prevent.

GQA is computed in grouped form (no materialized KV repetition).
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.overlap import shard_batch

NEG = -1e30
F32 = jnp.float32


def _group(q, n_kv: int):
    """(B, S, H, hd) -> (B, S, KV, G, hd)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, hd)


def _split_chunks(x, chunk: int):
    """(B, S, ...) -> (nc, B, chunk, ...)."""
    b, s = x.shape[:2]
    n = s // chunk
    x = x.reshape(b, n, chunk, *x.shape[2:])
    return jnp.moveaxis(x, 1, 0)


def _block_attn(q, k, v, bias, m, l, acc, scale):
    """One online-softmax block update.

    q: (B, c, KV, G, hd); k/v: (B, s, KV, hd); bias: (c, s) additive;
    m, l: (B, KV, G, c) fp32; acc: (B, KV, G, c, hd) fp32.
    """
    s_blk = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                       preferred_element_type=F32)
    s_blk = s_blk * scale + bias
    m_new = jnp.maximum(m, s_blk.max(axis=-1))
    p = jnp.exp(s_blk - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v.dtype), v,
                    preferred_element_type=F32)
    acc_new = acc * alpha[..., None] + pv
    return m_new, l_new, acc_new


def _finish(acc, m, l, dtype):
    """-> out (B, c, H, hd), lse (B, KV, G, c)."""
    out = acc / jnp.maximum(l, 1e-30)[..., None]   # (B, KV, G, c, hd)
    out = jnp.moveaxis(out, 3, 1)                  # (B, c, KV, G, hd)
    b, c = out.shape[:2]
    out = out.reshape(b, c, -1, out.shape[-1]).astype(dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out, lse


def _causal_bias(c: int, qi, kj, window: int | None):
    """(c, c) additive bias for q chunk index qi vs kv chunk index kj."""
    qpos = qi * c + jnp.arange(c)[:, None]
    kpos = kj * c + jnp.arange(c)[None, :]
    ok = kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, NEG).astype(F32)


# ----------------------------------------------------------------------------
# direct (small S) — plain autodiff
# ----------------------------------------------------------------------------

def direct_attention(q, k, v, *, n_kv: int, causal: bool = True,
                     window: int | None = None):
    b, s, h, hd = q.shape
    scale = hd ** -0.5
    qg = _group(q, n_kv)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=F32) * scale
    if causal:
        qpos = jnp.arange(s)[:, None]
        kpos = jnp.arange(s)[None, :]
        ok = kpos <= qpos
        if window is not None:
            ok &= kpos > qpos - window
        scores = jnp.where(ok, scores, NEG)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    return out.reshape(b, s, h, hd)


# ----------------------------------------------------------------------------
# chunked forward schedules (shared by the custom VJP)
# ----------------------------------------------------------------------------

def _fwd_masked(q, k, v, n_kv, chunk, window):
    b, s, h, hd = q.shape
    scale = hd ** -0.5
    nq = s // chunk
    qg = _split_chunks(_group(q, n_kv), chunk)   # (nq, B, c, KV, G, hd)
    kc = _split_chunks(k, chunk)                 # (nq, B, c, KV, hd)
    vc = _split_chunks(v, chunk)
    g = h // n_kv

    def q_step(_, qi_and_chunk):
        qi, q_blk = qi_and_chunk
        m0 = shard_batch(jnp.full((b, n_kv, g, chunk), NEG, F32))
        l0 = shard_batch(jnp.zeros((b, n_kv, g, chunk), F32))
        a0 = shard_batch(jnp.zeros((b, n_kv, g, chunk, hd), F32))

        def kv_step(carry, kj_and_kv):
            m, l, acc = carry
            kj, k_blk, v_blk = kj_and_kv
            bias = _causal_bias(chunk, qi, kj, window)
            m, l, acc = _block_attn(q_blk, k_blk, v_blk, bias, m, l, acc, scale)
            return (m, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nq), kc, vc))
        return None, _finish(acc, m, l, q.dtype)

    _, (out, lse) = jax.lax.scan(q_step, None, (jnp.arange(nq), qg))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)
    return out, lse                               # lse: (nq, B, KV, G, c)


def _fwd_banded(q, k, v, n_kv, chunk, window):
    b, s, h, hd = q.shape
    scale = hd ** -0.5
    nq = s // chunk
    nband = min(window // chunk + 1, nq)
    qg = _split_chunks(_group(q, n_kv), chunk)
    kc = _split_chunks(k, chunk)
    vc = _split_chunks(v, chunk)
    g = h // n_kv

    def q_step(_, qi_and_chunk):
        qi, q_blk = qi_and_chunk
        m0 = shard_batch(jnp.full((b, n_kv, g, chunk), NEG, F32))
        l0 = shard_batch(jnp.zeros((b, n_kv, g, chunk), F32))
        a0 = shard_batch(jnp.zeros((b, n_kv, g, chunk, hd), F32))

        def band_step(carry, t):
            m, l, acc = carry
            kj = jnp.clip(qi - nband + 1 + t, 0, nq - 1)
            k_blk = jax.lax.dynamic_index_in_dim(kc, kj, 0, keepdims=False)
            v_blk = jax.lax.dynamic_index_in_dim(vc, kj, 0, keepdims=False)
            bias = _causal_bias(chunk, qi, kj, window)
            dup = qi - nband + 1 + t < 0               # clipped duplicate
            bias = jnp.where(dup, NEG, bias)
            m, l, acc = _block_attn(q_blk, k_blk, v_blk, bias, m, l, acc, scale)
            return (m, l, acc), None

        (m, l, acc), _ = jax.lax.scan(band_step, (m0, l0, a0),
                                      jnp.arange(nband))
        return None, _finish(acc, m, l, q.dtype)

    _, (out, lse) = jax.lax.scan(q_step, None, (jnp.arange(nq), qg))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd), lse


def _fwd_folded(q, k, v, n_kv, chunk):
    """Exact-causal: fold q chunk i with q chunk nq-1-i; each fold scans
    exactly nq+1 kv blocks, none wasted. Requires nq even."""
    b, s, h, hd = q.shape
    scale = hd ** -0.5
    nq = s // chunk
    qg = _split_chunks(_group(q, n_kv), chunk)
    kc = _split_chunks(k, chunk)
    vc = _split_chunks(v, chunk)
    g = h // n_kv
    acc_shape = (b, n_kv, g, chunk)

    def fold_step(_, f):
        lo, hi = f, nq - 1 - f
        q_lo, q_hi = qg[lo], qg[hi]
        state = tuple(jnp.full(acc_shape, NEG, F32) for _ in range(2)) + \
                tuple(jnp.zeros(acc_shape, F32) for _ in range(2)) + \
                tuple(jnp.zeros(acc_shape + (hd,), F32) for _ in range(2))
        state = tuple(shard_batch(x) for x in state)

        def t_step(carry, t):
            m_lo, m_hi, l_lo, l_hi, a_lo, a_hi = carry
            use_lo = t <= lo
            kj = jnp.where(use_lo, t, t - lo - 1)
            k_blk = jax.lax.dynamic_index_in_dim(kc, kj, 0, keepdims=False)
            v_blk = jax.lax.dynamic_index_in_dim(vc, kj, 0, keepdims=False)
            q_blk = jnp.where(use_lo, q_lo, q_hi)
            qi = jnp.where(use_lo, lo, hi)
            bias = _causal_bias(chunk, qi, kj, None)
            m_in = jnp.where(use_lo, m_lo, m_hi)
            l_in = jnp.where(use_lo, l_lo, l_hi)
            a_in = jnp.where(use_lo, a_lo, a_hi)
            m, l, acc = _block_attn(q_blk, k_blk, v_blk, bias, m_in, l_in,
                                    a_in, scale)
            m_lo = jnp.where(use_lo, m, m_lo)
            l_lo = jnp.where(use_lo, l, l_lo)
            a_lo = jnp.where(use_lo, acc, a_lo)
            m_hi = jnp.where(use_lo, m_hi, m)
            l_hi = jnp.where(use_lo, l_hi, l)
            a_hi = jnp.where(use_lo, a_hi, acc)
            return (m_lo, m_hi, l_lo, l_hi, a_lo, a_hi), None

        (m_lo, m_hi, l_lo, l_hi, a_lo, a_hi), _ = jax.lax.scan(
            t_step, state, jnp.arange(nq + 1))
        return None, (_finish(a_lo, m_lo, l_lo, q.dtype),
                      _finish(a_hi, m_hi, l_hi, q.dtype))

    _, ((out_lo, lse_lo), (out_hi, lse_hi)) = jax.lax.scan(
        fold_step, None, jnp.arange(nq // 2))
    out = jnp.concatenate([out_lo, out_hi[::-1]], axis=0)   # (nq, B, c, H, hd)
    lse = jnp.concatenate([lse_lo, lse_hi[::-1]], axis=0)
    b_ = out.shape[1]
    out = jnp.moveaxis(out, 0, 1).reshape(b_, s, h, hd)
    return out, lse


# ----------------------------------------------------------------------------
# flash-style custom VJP shared by every causal chunked schedule
# ----------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash(n_kv: int, chunk: int, window, schedule: str, q, k, v):
    out, _ = _flash_fwd_inner(n_kv, chunk, window, schedule, q, k, v)
    return out


def _flash_fwd_inner(n_kv, chunk, window, schedule, q, k, v):
    if schedule == "folded":
        return _fwd_folded(q, k, v, n_kv, chunk)
    if schedule == "banded":
        return _fwd_banded(q, k, v, n_kv, chunk, window)
    return _fwd_masked(q, k, v, n_kv, chunk, window)


def _flash_fwd(n_kv, chunk, window, schedule, q, k, v):
    out, lse = _flash_fwd_inner(n_kv, chunk, window, schedule, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd(n_kv, chunk, window, schedule, res, dout):
    """FlashAttention-style backward: recompute score blocks per (i, j).

    Saves only linear-in-S residuals. Accumulates dk/dv into full-length
    fp32 buffers via in-place slice updates; dq is emitted per q chunk.
    """
    q, k, v, out, lse = res
    b, s, h, hd = q.shape
    scale = hd ** -0.5
    nq = s // chunk
    g = h // n_kv
    qg = _split_chunks(_group(q, n_kv), chunk)      # (nq, B, c, KV, G, hd)
    og = _split_chunks(_group(out, n_kv), chunk)
    dog = _split_chunks(_group(dout, n_kv), chunk)
    kc = _split_chunks(k, chunk)                    # (nq, B, c, KV, hd)
    vc = _split_chunks(v, chunk)
    if window is not None and schedule == "banded":
        nband = min(window // chunk + 1, nq)
    else:
        nband = nq

    dk0 = shard_batch(jnp.zeros((b, s, n_kv, hd), F32))
    dv0 = shard_batch(jnp.zeros((b, s, n_kv, hd), F32))

    def q_step(carry, xs):
        dk_full, dv_full = carry
        qi, q_blk, o_blk, do_blk, lse_blk = xs
        # D_i = rowsum(dout * out): (B, c, KV, G) -> (B, KV, G, c)
        D = jnp.einsum("bqkgd,bqkgd->bkgq", do_blk.astype(F32),
                       o_blk.astype(F32))
        dq0 = shard_batch(jnp.zeros((b, chunk, n_kv, g, hd), F32))

        def kv_step(inner, t):
            dq_acc, dk_full, dv_full = inner
            kj = jnp.clip(qi - nband + 1 + t, 0, nq - 1) if nband < nq else t
            k_blk = jax.lax.dynamic_index_in_dim(kc, kj, 0, keepdims=False)
            v_blk = jax.lax.dynamic_index_in_dim(vc, kj, 0, keepdims=False)
            bias = _causal_bias(chunk, qi, kj, window)
            if nband < nq:
                dup = qi - nband + 1 + t < 0
                bias = jnp.where(dup, NEG, bias)
            s_blk = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k_blk,
                               preferred_element_type=F32) * scale + bias
            p = jnp.exp(s_blk - lse_blk[..., None])          # (B,KV,G,c,s)
            dv_c = jnp.einsum("bkgqs,bqkgd->bskd", p,
                              do_blk.astype(F32))
            dp = jnp.einsum("bqkgd,bskd->bkgqs", do_blk, v_blk,
                            preferred_element_type=F32)
            ds = p * (dp - D[..., None]) * scale             # (B,KV,G,c,s)
            dq_acc = dq_acc + jnp.einsum("bkgqs,bskd->bqkgd",
                                         ds.astype(k.dtype), k_blk,
                                         preferred_element_type=F32)
            dk_c = jnp.einsum("bkgqs,bqkgd->bskd", ds,
                              q_blk.astype(F32))
            start = kj * chunk
            upd = lambda full, c_: jax.lax.dynamic_update_slice_in_dim(
                full, jax.lax.dynamic_slice_in_dim(full, start, chunk, 1)
                + c_, start, 1)
            return (dq_acc, upd(dk_full, dk_c), upd(dv_full, dv_c)), None

        (dq_acc, dk_full, dv_full), _ = jax.lax.scan(
            kv_step, (dq0, dk_full, dv_full), jnp.arange(nband))
        return (dk_full, dv_full), dq_acc

    (dk_full, dv_full), dq_chunks = jax.lax.scan(
        q_step, (dk0, dv0), (jnp.arange(nq), qg, og, dog, lse))
    dq = jnp.moveaxis(dq_chunks, 0, 1).reshape(b, s, h, hd).astype(q.dtype)
    return dq, dk_full.astype(k.dtype), dv_full.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ----------------------------------------------------------------------------
# public entry points
# ----------------------------------------------------------------------------

Schedule = Literal["auto", "direct", "masked", "folded", "banded", "pallas"]


def pallas_flash_attention(q, k, v, *, causal: bool = True):
    """Route model-layout attention through the Pallas flash kernel.

    q: (B, S, H, hd); k/v: (B, S, KV, hd) — transposed to the kernel's
    (B, H, S, hd) layout and back. Forward-only (no custom VJP): the serve
    path's schedule; training uses the jnp flash VJP or, under the "fused"
    kernel policy, the fused kernels' reference-composition backward.
    """
    from repro.kernels import ops
    o = ops.flash_attention(jnp.transpose(q, (0, 2, 1, 3)),
                            jnp.transpose(k, (0, 2, 1, 3)),
                            jnp.transpose(v, (0, 2, 1, 3)), causal=causal)
    return jnp.transpose(o, (0, 2, 1, 3))


def attention(q, k, v, *, n_kv: int, causal: bool = True,
              window: int | None = None, chunk: int = 1024,
              schedule: Schedule = "auto"):
    """Training/prefill attention. q: (B,S,H,hd); k/v: (B,S,KV,hd)."""
    s = q.shape[1]
    if schedule == "pallas" and causal and window is None:
        return pallas_flash_attention(q, k, v, causal=True)
    if schedule == "pallas":          # kernel has no SWA/bidirectional path
        schedule = "auto"
    if schedule == "auto":
        if s <= 2 * chunk or s % chunk or not causal:
            schedule = "direct"
        elif window is not None and window < s:
            schedule = "banded"
        else:
            schedule = "masked"
    if schedule == "folded" and ((s // chunk) % 2 or (window and window < s)):
        schedule = "masked"
    if schedule == "direct" or not causal:
        return direct_attention(q, k, v, n_kv=n_kv, causal=causal,
                                window=window)
    return _flash(n_kv, chunk, window, schedule, q, k, v)


def cross_attention(q, k, v, *, n_kv: int, chunk: int = 1024):
    """Non-causal attention of long q against a short kv context (cross-attn).

    Scans q in chunks so the (S_q x S_kv) scores never materialize at full
    S_q. kv (encoder output / image embeds) is small enough to keep whole.
    """
    b, s, h, hd = q.shape
    if s <= 2 * chunk or s % chunk:
        return direct_attention(q, k, v, n_kv=n_kv, causal=False)
    scale = hd ** -0.5
    qg = _split_chunks(_group(q, n_kv), chunk)   # (nq, B, c, KV, G, hd)

    def q_step(_, q_blk):
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k,
                            preferred_element_type=F32) * scale
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
        return None, out.reshape(b, chunk, h, hd)

    _, out = jax.lax.scan(q_step, None, qg)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)


def decode_attention(q, k_cache, v_cache, pos, *, n_kv: int,
                     window: int | None = None, rolling: bool = False):
    """Single-token decode. q: (B,1,H,hd); caches: (B, S_c, KV, hd);
    pos: scalar or (B,) current position (number of tokens already cached).

    With `rolling=True` the cache is a circular buffer of size S_c (used for
    SWA at long context) and every live slot is attendable.
    """
    b, sc, kv, hd = k_cache.shape
    h = q.shape[2]
    scale = hd ** -0.5
    qg = _group(q, n_kv)[:, 0]                       # (B, KV, G, hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                        preferred_element_type=F32) * scale
    idx = jnp.arange(sc)
    pos_b = jnp.asarray(pos)
    if pos_b.ndim == 0:
        pos_b = jnp.full((b,), pos_b)
    if rolling:
        n_live = jnp.minimum(pos_b, sc)
        ok = idx[None, :] < n_live[:, None]
    else:
        ok = idx[None, :] < pos_b[:, None]
        if window is not None:
            ok &= idx[None, :] >= (pos_b[:, None] - window)
    scores = jnp.where(ok[:, None, None, :], scores, NEG)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(b, 1, h, hd)


def update_cache(k_cache, v_cache, k_new, v_new, pos, *, rolling: bool = False):
    """Insert (B, 1, KV, hd) new keys/values at position `pos`.

    `pos` is a scalar (every slot writes the same row — the batch-program
    path) or a (B,) vector (each slot writes its own row — the continuous-
    batching session path, where slots sit at independent decode positions).
    """
    sc = k_cache.shape[1]
    slot = jnp.asarray(pos) % sc if rolling else jnp.asarray(pos)
    with jax.named_scope("kv_write"):
        if slot.ndim == 0:
            k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new,
                                                          slot, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new,
                                                          slot, axis=1)
            return k_cache, v_cache
        upd = jax.vmap(lambda c, n, s:
                       jax.lax.dynamic_update_slice_in_dim(c, n, s, axis=0))
        return upd(k_cache, k_new, slot), upd(v_cache, v_new, slot)


# ----------------------------------------------------------------------------
# Paged KV — the shared-pool routing of the two hooks above
# (runtime/kvpool.py owns the host-side allocator; these are the device ops)
# ----------------------------------------------------------------------------


def paged_update_cache(k_pool, v_pool, k_new, v_new, pos, pages):
    """Scatter (B, 1, KV, hd) new keys/values through per-slot page tables.

    Pools are (n_pages, page_size, KV, hd) — the whole session shares them
    (inside the decode step's layer scan, the flat view of every layer's
    pool, with `pages` offset into the layer's region: `steps.py`);
    `pages` is the (B, pages_per_slot) int32 table and `pos` the scalar or
    (B,) decode position. Slot b's token lands at
    `pool[pages[b, pos_b // page_size], pos_b % page_size]` — the paged
    analogue of `update_cache`'s per-slot dynamic-update-slice. Retired
    slots' tables point at the reserved trash page 0, so their frozen-pos
    writes can never corrupt pages reallocated to live requests.
    """
    ps = k_pool.shape[1]
    b = k_new.shape[0]
    pos_b = jnp.asarray(pos)
    if pos_b.ndim == 0:
        pos_b = jnp.full((b,), pos_b)
    page_idx = jnp.take_along_axis(pages, (pos_b // ps)[:, None],
                                   axis=1)[:, 0]            # (B,)
    off = pos_b % ps
    with jax.named_scope("kv_write"):
        k_pool = k_pool.at[page_idx, off].set(k_new[:, 0])
        v_pool = v_pool.at[page_idx, off].set(v_new[:, 0])
    return k_pool, v_pool


def paged_gather(pool, pages):
    """Gather each slot's pages into a contiguous (B, npp * ps, KV, hd)
    cache view. Positions past a slot's written length read stale pool
    data (or the trash page) — harmless for finite data, because
    `decode_attention`'s `idx < pos` mask gives them exactly-zero softmax
    weight. With `decode_attention`, the reference route of
    `paged_decode_attention`."""
    b, npp = pages.shape
    _, ps, kv, hd = pool.shape
    with jax.named_scope("kv_gather"):
        return pool[pages].reshape(b, npp * ps, kv, hd)


def paged_decode_attention(q, k_pool, v_pool, pos, pages):
    """`decode_attention` against the shared pool, through the paged
    decode kernel (`kernels/paged_attention.py`): each slot's pages below
    `pos` are read through its table, none past it. Under the
    "reference" policy the gather-then-mask composition runs instead."""
    from repro.kernels import ops
    with jax.named_scope("paged_attention"):
        return ops.paged_decode_attention(q, k_pool, v_pool, pos, pages)


def copy_page(pool, src, dst):
    """Device page copy (COW fork): pool[dst] = pool[src]."""
    return pool.at[dst].set(pool[src])


def zero_pages(pool, pages):
    """Scrub the listed pages (NaN-corruption recovery: masked attention
    zeroes stale *weights*, but 0 * NaN is still NaN, so pages freed from
    a corrupted slot must be cleaned before reuse)."""
    return pool.at[jnp.asarray(pages)].set(jnp.zeros((), pool.dtype))

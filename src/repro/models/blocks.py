"""Residual block kinds composing the ten architectures.

Each kind provides:
  <kind>_specs(cfg)                      -> ParamSpec tree
  <kind>_apply(cfg, p, x, ctx)           -> (x, aux)          full-sequence
  <kind>_cache_specs(cfg, B, cache_len)  -> ParamSpec tree    decode state
  <kind>_decode(cfg, p, x, cache, pos, ctx) -> (x, cache)     one token

A kind whose decode state carries the request's history beyond its K/V
rows (a recurrence, a conv tail) is registered `recurrent`: such state
cannot be rebuilt from shared prefix pages.

`ctx` carries positions and cross-attention context (encoder/image embeds).
Aux is the MoE load-balancing loss contribution (0.0 elsewhere).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.policy import current_policy
from repro.core.overlap import shard_batch

from . import attention as attn_lib
from .layers import (ParamSpec, apply_ffn, attn_specs, ffn_specs,
                     fused_attention_proj, fused_matmul_bias_act,
                     fused_matmul_residual, fused_norm_matmul, out_project,
                     qkv_postprocess, qkv_project, rms_norm, layer_norm)

F32 = jnp.float32


def _norm_specs(cfg, name: str) -> dict:
    if cfg.norm == "rms":
        return {name: ParamSpec((cfg.d_model,), ("norm",), init="zeros")}
    return {name + "_s": ParamSpec((cfg.d_model,), ("norm",), init="ones"),
            name + "_b": ParamSpec((cfg.d_model,), ("norm",), init="zeros")}


def _norm(cfg, p, name: str, x):
    if cfg.norm == "rms":
        return rms_norm(x, p[name], cfg.norm_eps)
    return layer_norm(x, p[name + "_s"], p[name + "_b"])


# ============================================================================
# Dense attention + FFN block ("attn"), with window variant ("local_attn")
# ============================================================================

def attn_block_specs(cfg) -> dict:
    s = {}
    s |= _norm_specs(cfg, "ln_attn")
    s["attn"] = attn_specs(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                           qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm)
    if cfg.d_ff:
        s |= _norm_specs(cfg, "ln_ffn")
        s["ffn"] = ffn_specs(cfg.d_model, cfg.d_ff, kind=cfg.ffn_kind)
    return s


def _fused_rms(cfg) -> bool:
    """Is the fused producer–consumer path applicable to this block's norm?

    The route is steered by the active KernelPolicy (mode "fused"), read at
    trace time — model code asks the policy, not the config."""
    return current_policy().fused and cfg.norm == "rms"


def _fused_qkv(cfg, p, x, ctx):
    """qkv with the pre-attention rmsnorm folded into each projection's
    A-tile prologue (norm recomputed per consumer; the normed activations
    never round-trip HBM)."""
    a = p["attn"]
    d = x.shape[-1]

    def proj(w):
        y = fused_norm_matmul(x, p["ln_attn"], w.reshape(d, -1))
        return y.reshape(*x.shape[:-1], w.shape[1], w.shape[2])

    return qkv_postprocess(a, proj(a["wq"]), proj(a["wk"]), proj(a["wv"]),
                           ctx["positions"], qkv_bias=cfg.qkv_bias,
                           qk_norm=cfg.qk_norm, rope=ctx.get("rope", True),
                           theta=cfg.rope_theta)


def _fused_out_residual(p, o, x):
    """x + out_project(o) with the residual added in the matmul epilogue."""
    wo = p["attn"]["wo"]
    flat = o.reshape(*o.shape[:-2], o.shape[-2] * o.shape[-1])
    return fused_matmul_residual(flat, wo.reshape(-1, wo.shape[-1]), x)


def _self_attention(cfg, p, x, ctx, *, window, causal=True):
    if _fused_rms(cfg):
        q, k, v = _fused_qkv(cfg, p, x, ctx)
        if causal and window is None:
            # the whole hot path in one kernel: flash attention with the
            # output projection accumulated across heads in VMEM (backward
            # recomputes via the reference composition — see kernels/ops.py)
            return x + fused_attention_proj(q, k, v, p["attn"]["wo"],
                                            causal=True)
        o = attn_lib.attention(q, k, v, n_kv=cfg.n_kv_heads,
                               causal=causal, window=window,
                               chunk=cfg.attn_chunk,
                               schedule=cfg.attn_schedule)
        return _fused_out_residual(p, o, x)
    q, k, v = qkv_project(p["attn"], _norm(cfg, p, "ln_attn", x),
                          ctx["positions"], n_heads=cfg.n_heads,
                          n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                          qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
                          rope=ctx.get("rope", True), theta=cfg.rope_theta)
    o = attn_lib.attention(q, k, v, n_kv=cfg.n_kv_heads,
                           causal=causal, window=window,
                           chunk=cfg.attn_chunk, schedule=cfg.attn_schedule)
    return x + out_project(p["attn"], o)


def _ffn_residual(cfg, p, x):
    """x + FFN(norm(x)), with the fused kernel routing when enabled:
    swiglu/geglu fold the norm into the gate/up prologues and the residual
    into the down-projection epilogue; gelu MLPs take the bias+activation
    epilogue. Falls back to the jnp composition per-site."""
    if current_policy().fused:
        f = p["ffn"]
        if cfg.norm == "rms" and cfg.ffn_kind in ("swiglu", "geglu"):
            g = fused_norm_matmul(x, p["ln_ffn"], f["w_gate"])
            u = fused_norm_matmul(x, p["ln_ffn"], f["w_up"])
            act = jax.nn.silu if cfg.ffn_kind == "swiglu" else jax.nn.gelu
            h = act(g.astype(F32)).astype(x.dtype) * u
            return fused_matmul_residual(h, f["w_down"], x)
        if cfg.ffn_kind == "gelu":
            h = fused_matmul_bias_act(_norm(cfg, p, "ln_ffn", x),
                                      f["w_in"], f["b_in"], "gelu")
            return x + fused_matmul_bias_act(h, f["w_out"], f["b_out"],
                                             "none")
    return x + apply_ffn(p["ffn"], _norm(cfg, p, "ln_ffn", x),
                         kind=cfg.ffn_kind)


def attn_block_apply(cfg, p, x, ctx, *, window=None):
    window = window if window is not None else cfg.window
    x = _self_attention(cfg, p, x, ctx, window=window,
                        causal=ctx.get("causal", True))
    if cfg.d_ff:
        x = _ffn_residual(cfg, p, x)
    return x, 0.0


def attn_cache_specs(cfg, B: int, cache_len: int) -> dict:
    return {
        "k": ParamSpec((B, cache_len, cfg.n_kv_heads, cfg.hd),
                       ("batch", "kv_seq", "kv_heads", None), init="zeros"),
        "v": ParamSpec((B, cache_len, cfg.n_kv_heads, cfg.hd),
                       ("batch", "kv_seq", "kv_heads", None), init="zeros"),
    }


def _paged(ctx, window) -> bool:
    """Route this block's K/V through the shared page pool? Only when the
    session threads a page table in `ctx` and the cache is positional
    (rolling SWA buffers stay private — their `pos % window` addressing is
    its own paging scheme)."""
    return ctx.get("pages") is not None and not window


def _attend_decode(cfg, q, k, v, cache, pos, ctx, window):
    """One token's self-attention over the slot's K/V: the new row is
    written (through the page table when the session pages, else into the
    private or rolling buffer), then positions 0..pos are attended.
    Returns (o, new k cache, new v cache)."""
    paged = _paged(ctx, window)
    rolling = (not paged and bool(window)
               and cache["k"].shape[1] < ctx["max_seq"])
    if paged:
        kc, vc = attn_lib.paged_update_cache(cache["k"], cache["v"], k, v,
                                             pos, ctx["pages"])
        o = attn_lib.paged_decode_attention(q, kc, vc, pos + 1, ctx["pages"])
    else:
        kc, vc = attn_lib.update_cache(cache["k"], cache["v"], k, v, pos,
                                       rolling=rolling)
        o = attn_lib.decode_attention(q, kc, vc, pos + 1, n_kv=cfg.n_kv_heads,
                                      window=window, rolling=rolling)
    return o, kc, vc


def attn_block_decode(cfg, p, x, cache, pos, ctx, *, window=None):
    window = window if window is not None else cfg.window
    with jax.named_scope("attention"):
        if _fused_rms(cfg):
            q, k, v = _fused_qkv(cfg, p, x, ctx)
        else:
            q, k, v = qkv_project(p["attn"], _norm(cfg, p, "ln_attn", x),
                                  ctx["positions"], n_heads=cfg.n_heads,
                                  n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                                  qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
                                  rope=ctx.get("rope", True),
                                  theta=cfg.rope_theta)
        o, kc, vc = _attend_decode(cfg, q, k, v, cache, pos, ctx, window)
        if _fused_rms(cfg):
            x = _fused_out_residual(p, o, x)
        else:
            x = x + out_project(p["attn"], o)
    if cfg.d_ff:
        with jax.named_scope("ffn"):
            x = _ffn_residual(cfg, p, x)
    return x, {"k": kc, "v": vc}


# ============================================================================
# MoE block ("attn_moe"): attention + top-k expert FFN (sort/scatter dispatch)
# ============================================================================

def moe_specs(cfg) -> dict:
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    return {
        "router": ParamSpec((d, E), ("embed", None), dtype=F32),
        "w_gate": ParamSpec((E, d, f), ("expert", "embed", "ffn")),
        "w_up": ParamSpec((E, d, f), ("expert", "embed", "ffn")),
        "w_down": ParamSpec((E, f, d), ("expert", "ffn", "embed")),
    }


def moe_block_specs(cfg) -> dict:
    s = {}
    s |= _norm_specs(cfg, "ln_attn")
    s["attn"] = attn_specs(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                           qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm)
    s |= _norm_specs(cfg, "ln_ffn")
    s["moe"] = moe_specs(cfg)
    return s


def moe_apply(cfg, p, x):
    """Top-k MoE with capacity; dispatch via scatter/gather (no one-hot GEMM,
    so cost_analysis reflects true expert FLOPs).

    Two dispatch modes (cfg.moe_local_dispatch):
      global (baseline) — capacity over the *flattened global* token set.
        The cumsum/scatter then run along a sharded dimension, which GSPMD
        lowers to cross-shard collectives: the MoE equivalent of MemPool's
        all-remote interleaved accesses.
      local — GShard-style groups: the batch dim stays the group dim, so
        routing/cumsum/scatter/gather are shard-local (capacity per
        sequence). This is the hybrid addressing scheme applied to MoE:
        dispatch traffic moves from the interconnect into the local tile.
    """
    if getattr(cfg, "moe_local_dispatch", False):
        return _moe_apply_local(cfg, p, x)
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    C = max(int(K * T * cfg.capacity_factor / E), 1)
    xt = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xt.astype(F32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    top_p, top_e = jax.lax.top_k(probs, K)                       # (T, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # position of each (token, k) slot within its expert's capacity
    e_flat = top_e.reshape(-1)                                   # (T*K,)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)                  # exclusive
    pos = jnp.take_along_axis(pos, e_flat[:, None], axis=1)[:, 0]
    keep = pos < C
    tok_idx = jnp.repeat(jnp.arange(T), K)

    # dispatch table (E, C) of token ids; overflow slots dropped by OOB scatter
    dispatch = jnp.full((E, C), T, jnp.int32)
    dispatch = dispatch.at[e_flat, pos].set(tok_idx, mode="drop")
    xp = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)])      # pad row
    xe = xp[dispatch]                                            # (E, C, d)

    # expert FFN (SwiGLU), batched over experts
    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    h = jax.nn.silu(g.astype(F32)).astype(xe.dtype) * u
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])              # (E, C, d)

    # combine: gather each slot's output back, weight, scatter-add per token
    ys = ye[e_flat, jnp.minimum(pos, C - 1)]                     # (T*K, d)
    w_slot = (top_p.reshape(-1) * keep).astype(ys.dtype)
    y = jnp.zeros((T, d), x.dtype).at[tok_idx].add(ys * w_slot[:, None])

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    f_e = jnp.mean(jax.nn.one_hot(top_e, E, dtype=F32).sum(1), axis=0)  # frac routed
    p_e = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f_e / K * p_e)
    return y.reshape(B, S, d), aux


def _moe_apply_local(cfg, p, x):
    """Grouped dispatch: everything batched over B (the sharded group dim)."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = max(int(K * S * cfg.capacity_factor / E), 1)

    logits = jnp.einsum("bsd,de->bse", x.astype(F32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                      # (B, S, E)
    top_p, top_e = jax.lax.top_k(probs, K)                       # (B, S, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    e_flat = top_e.reshape(B, S * K)                             # (B, S*K)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)          # (B, S*K, E)
    pos = jnp.cumsum(onehot, axis=1) - onehot                    # local cumsum
    pos = jnp.take_along_axis(pos, e_flat[..., None], axis=2)[..., 0]
    keep = pos < C
    tok_idx = jnp.broadcast_to(
        jnp.repeat(jnp.arange(S), K)[None], (B, S * K))

    def dispatch_one(e_b, pos_b, tok_b, x_b):
        table = jnp.full((E, C), S, jnp.int32)
        table = table.at[e_b, pos_b].set(tok_b, mode="drop")
        xp = jnp.concatenate([x_b, jnp.zeros((1, d), x_b.dtype)])
        return table, xp[table]                                  # (E,C),(E,C,d)

    table, xe = jax.vmap(dispatch_one)(e_flat, pos, tok_idx, x)  # batch-local

    g = jnp.einsum("becd,edf->becf", xe, p["w_gate"])
    u = jnp.einsum("becd,edf->becf", xe, p["w_up"])
    h = jax.nn.silu(g.astype(F32)).astype(xe.dtype) * u
    ye = jnp.einsum("becf,efd->becd", h, p["w_down"])            # (B,E,C,d)

    def combine_one(ye_b, e_b, pos_b, w_b, tok_b):
        ys = ye_b[e_b, jnp.minimum(pos_b, C - 1)]                # (S*K, d)
        return jnp.zeros((S, d), ye_b.dtype).at[tok_b].add(
            ys * w_b[:, None])

    w_slot = (top_p.reshape(B, S * K) * keep).astype(ye.dtype)
    y = jax.vmap(combine_one)(ye, e_flat, pos, w_slot, tok_idx)

    f_e = jnp.mean(jax.nn.one_hot(top_e, E, dtype=F32).sum(2), axis=(0, 1))
    p_e = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f_e / K * p_e)
    return y.astype(x.dtype), aux


def moe_block_apply(cfg, p, x, ctx):
    x = _self_attention(cfg, p, x, ctx, window=cfg.window)
    y, aux = moe_apply(cfg, p["moe"], _norm(cfg, p, "ln_ffn", x))
    return x + y, aux


def moe_block_decode(cfg, p, x, cache, pos, ctx):
    with jax.named_scope("attention"):
        q, k, v = qkv_project(p["attn"], _norm(cfg, p, "ln_attn", x),
                              ctx["positions"], n_heads=cfg.n_heads,
                              n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                              qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
                              theta=cfg.rope_theta)
        o, kc, vc = _attend_decode(cfg, q, k, v, cache, pos, ctx, cfg.window)
        x = x + out_project(p["attn"], o)
    with jax.named_scope("ffn"):
        y, _ = moe_apply(cfg, p["moe"], _norm(cfg, p, "ln_ffn", x))
    return x + y, {"k": kc, "v": vc}


# ============================================================================
# Cross-attention block ("cross") — llama-3.2-vision image layers
# ============================================================================

def cross_block_specs(cfg) -> dict:
    s = {}
    s |= _norm_specs(cfg, "ln_attn")
    s["attn"] = attn_specs(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                           qk_norm=True)   # llama-3.2 uses q/k norm on cross
    s["gate_attn"] = ParamSpec((1,), ("norm",), dtype=F32, init="zeros")
    s["gate_ffn"] = ParamSpec((1,), ("norm",), dtype=F32, init="zeros")
    s |= _norm_specs(cfg, "ln_ffn")
    s["ffn"] = ffn_specs(cfg.d_model, cfg.d_ff, kind=cfg.ffn_kind)
    return s


def _cross_kv(cfg, p, embeds):
    k = jnp.einsum("bsd,dhk->bshk", embeds, p["attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", embeds, p["attn"]["wv"])
    k = rms_norm(k, p["attn"]["k_norm"])
    return k, v


def cross_block_apply(cfg, p, x, ctx):
    h = _norm(cfg, p, "ln_attn", x)
    q = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wq"])
    q = rms_norm(q, p["attn"]["q_norm"])
    k, v = _cross_kv(cfg, p, ctx["cross_embeds"])
    o = attn_lib.cross_attention(q, k, v, n_kv=cfg.n_kv_heads,
                                 chunk=cfg.attn_chunk)
    ga = jnp.tanh(p["gate_attn"]).astype(x.dtype)
    gf = jnp.tanh(p["gate_ffn"]).astype(x.dtype)
    x = x + ga * out_project(p["attn"], o)
    y = apply_ffn(p["ffn"], _norm(cfg, p, "ln_ffn", x), kind=cfg.ffn_kind)
    x = x + gf * y
    return x, 0.0


def cross_cache_specs(cfg, B: int, cache_len: int) -> dict:
    n_ctx = cfg.n_img_tokens or cfg.enc_seq
    return {
        "k": ParamSpec((B, n_ctx, cfg.n_kv_heads, cfg.hd),
                       ("batch", None, "kv_heads", None), init="zeros"),
        "v": ParamSpec((B, n_ctx, cfg.n_kv_heads, cfg.hd),
                       ("batch", None, "kv_heads", None), init="zeros"),
    }


def cross_block_decode(cfg, p, x, cache, pos, ctx):
    h = _norm(cfg, p, "ln_attn", x)
    q = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wq"])
    q = rms_norm(q, p["attn"]["q_norm"])
    kc, vc = cache["k"], cache["v"]
    n_ctx = kc.shape[1]
    o = attn_lib.decode_attention(q, kc, vc, n_ctx, n_kv=cfg.n_kv_heads)
    ga = jnp.tanh(p["gate_attn"]).astype(x.dtype)
    gf = jnp.tanh(p["gate_ffn"]).astype(x.dtype)
    x = x + ga * out_project(p["attn"], o)
    y = apply_ffn(p["ffn"], _norm(cfg, p, "ln_ffn", x), kind=cfg.ffn_kind)
    x = x + gf * y
    return x, cache


# ============================================================================
# Whisper decoder block ("attn_cross"): self + cross + MLP
# ============================================================================

def attn_cross_block_specs(cfg) -> dict:
    s = {}
    s |= _norm_specs(cfg, "ln_self")
    s["self"] = attn_specs(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                           qkv_bias=cfg.qkv_bias)
    s |= _norm_specs(cfg, "ln_cross")
    s["cross"] = attn_specs(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                            qkv_bias=cfg.qkv_bias)
    s |= _norm_specs(cfg, "ln_ffn")
    s["ffn"] = ffn_specs(cfg.d_model, cfg.d_ff, kind=cfg.ffn_kind)
    return s


def _ln(cfg, p, stem, x):
    return layer_norm(x, p[stem + "_s"], p[stem + "_b"]) if cfg.norm == "layer" \
        else rms_norm(x, p[stem])


def attn_cross_block_apply(cfg, p, x, ctx):
    # self attention (causal, no rope — whisper uses learned positions)
    q, k, v = qkv_project(p["self"], _ln(cfg, p, "ln_self", x),
                          ctx["positions"], n_heads=cfg.n_heads,
                          n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                          qkv_bias=cfg.qkv_bias, rope=False)
    o = attn_lib.attention(q, k, v, n_kv=cfg.n_kv_heads, causal=True,
                           chunk=cfg.attn_chunk, schedule=cfg.attn_schedule)
    x = x + out_project(p["self"], o)
    # cross attention to encoder output
    h = _ln(cfg, p, "ln_cross", x)
    qc = jnp.einsum("bsd,dhk->bshk", h, p["cross"]["wq"])
    if cfg.qkv_bias:
        qc = qc + p["cross"]["bq"]
    kc = jnp.einsum("bsd,dhk->bshk", ctx["cross_embeds"], p["cross"]["wk"])
    vc = jnp.einsum("bsd,dhk->bshk", ctx["cross_embeds"], p["cross"]["wv"])
    if cfg.qkv_bias:
        kc, vc = kc + p["cross"]["bk"], vc + p["cross"]["bv"]
    o = attn_lib.cross_attention(qc, kc, vc, n_kv=cfg.n_kv_heads,
                                 chunk=cfg.attn_chunk)
    x = x + out_project(p["cross"], o)
    x = x + apply_ffn(p["ffn"], _ln(cfg, p, "ln_ffn", x), kind=cfg.ffn_kind)
    return x, 0.0


def attn_cross_cache_specs(cfg, B: int, cache_len: int) -> dict:
    self_c = attn_cache_specs(cfg, B, cache_len)
    return {"self_k": self_c["k"], "self_v": self_c["v"],
            "cross_k": ParamSpec((B, cfg.enc_seq, cfg.n_kv_heads, cfg.hd),
                                 ("batch", None, "kv_heads", None), init="zeros"),
            "cross_v": ParamSpec((B, cfg.enc_seq, cfg.n_kv_heads, cfg.hd),
                                 ("batch", None, "kv_heads", None), init="zeros")}


def attn_cross_block_decode(cfg, p, x, cache, pos, ctx):
    q, k, v = qkv_project(p["self"], _ln(cfg, p, "ln_self", x),
                          ctx["positions"], n_heads=cfg.n_heads,
                          n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                          qkv_bias=cfg.qkv_bias, rope=False)
    if _paged(ctx, None):
        kc, vc = attn_lib.paged_update_cache(cache["self_k"], cache["self_v"],
                                             k, v, pos, ctx["pages"])
        o = attn_lib.paged_decode_attention(q, kc, vc, pos + 1, ctx["pages"])
    else:
        kc, vc = attn_lib.update_cache(cache["self_k"], cache["self_v"],
                                       k, v, pos)
        o = attn_lib.decode_attention(q, kc, vc, pos + 1, n_kv=cfg.n_kv_heads)
    x = x + out_project(p["self"], o)
    h = _ln(cfg, p, "ln_cross", x)
    qc = jnp.einsum("bsd,dhk->bshk", h, p["cross"]["wq"])
    if cfg.qkv_bias:
        qc = qc + p["cross"]["bq"]
    o = attn_lib.decode_attention(qc, cache["cross_k"], cache["cross_v"],
                                  cfg.enc_seq, n_kv=cfg.n_kv_heads)
    x = x + out_project(p["cross"], o)
    x = x + apply_ffn(p["ffn"], _ln(cfg, p, "ln_ffn", x), kind=cfg.ffn_kind)
    return x, {"self_k": kc, "self_v": vc,
               "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}


# ============================================================================
# Encoder block ("enc_attn") — bidirectional (whisper encoder)
# ============================================================================

def enc_attn_block_specs(cfg) -> dict:
    s = {}
    s |= _norm_specs(cfg, "ln_attn")
    s["attn"] = attn_specs(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                           qkv_bias=cfg.qkv_bias)
    s |= _norm_specs(cfg, "ln_ffn")
    s["ffn"] = ffn_specs(cfg.d_model, cfg.d_ff, kind=cfg.ffn_kind)
    return s


def enc_attn_block_apply(cfg, p, x, ctx):
    q, k, v = qkv_project(p["attn"], _norm(cfg, p, "ln_attn", x),
                          ctx["positions"], n_heads=cfg.n_heads,
                          n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                          qkv_bias=cfg.qkv_bias, rope=False)
    o = attn_lib.attention(q, k, v, n_kv=cfg.n_kv_heads, causal=False,
                           schedule="direct")
    x = x + out_project(p["attn"], o)
    x = _ffn_residual(cfg, p, x)
    return x, 0.0


# ============================================================================
# RG-LRU recurrent block ("rglru") — RecurrentGemma / Griffin
# ============================================================================

def rglru_block_specs(cfg) -> dict:
    d, r = cfg.d_model, cfg.lru_width
    s = {}
    s |= _norm_specs(cfg, "ln_rec")
    s["w_x"] = ParamSpec((d, r), ("embed", "ffn"))
    s["w_gate"] = ParamSpec((d, r), ("embed", "ffn"))
    s["conv_w"] = ParamSpec((cfg.conv_width, r), ("conv", "ffn"), scale=0.5)
    s["w_ra"] = ParamSpec((r, r), ("ffn", None))       # recurrence gate
    s["b_ra"] = ParamSpec((r,), ("ffn",), init="zeros")
    s["w_ix"] = ParamSpec((r, r), ("ffn", None))       # input gate
    s["b_ix"] = ParamSpec((r,), ("ffn",), init="zeros")
    s["lam"] = ParamSpec((r,), ("ffn",), dtype=F32, init="ones", scale=1.0)
    s["w_out"] = ParamSpec((r, d), ("ffn", "embed"))
    s |= _norm_specs(cfg, "ln_ffn")
    s["ffn"] = ffn_specs(cfg.d_model, cfg.d_ff, kind=cfg.ffn_kind)
    return s


def _causal_conv(x, w, state=None):
    """Depthwise causal conv. x: (B,S,r); w: (W,r); state: (B,W-1,r)|None."""
    W = w.shape[0]
    if state is None:
        pads = [jnp.pad(x, ((0, 0), (W - 1 - i, 0), (0, 0)))[:, :x.shape[1]]
                for i in range(W)]
    else:
        ext = jnp.concatenate([state, x], axis=1)
        pads = [ext[:, i:i + x.shape[1]] for i in range(W)]
    y = sum(p * w[i] for i, p in enumerate(pads))
    new_state = (jnp.concatenate([state, x], axis=1)[:, -(W - 1):]
                 if state is not None else None)
    return y, new_state


def _rglru_gates(p, u):
    r = jax.nn.sigmoid(jnp.einsum("...r,rs->...s", u, p["w_ra"]).astype(F32)
                       + p["b_ra"])
    i = jax.nn.sigmoid(jnp.einsum("...r,rs->...s", u, p["w_ix"]).astype(F32)
                       + p["b_ix"])
    log_a = -8.0 * jax.nn.softplus(p["lam"]) * r          # log a_t  (< 0)
    return log_a, i


def rglru_block_apply(cfg, p, x, ctx):
    h = _norm(cfg, p, "ln_rec", x)
    gate = jax.nn.gelu(jnp.einsum("bsd,dr->bsr", h, p["w_gate"]).astype(F32))
    u = jnp.einsum("bsd,dr->bsr", h, p["w_x"])
    u, _ = _causal_conv(u, p["conv_w"])
    log_a, i_gate = _rglru_gates(p, u)                    # (B,S,r) fp32
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-9)) \
        * (i_gate * u.astype(F32))
    # linear recurrence h_t = a_t h_{t-1} + b_t via associative scan over S
    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, a2 * b1 + b2
    _, states = jax.lax.associative_scan(combine, (a, b), axis=1)
    y = (gate * states).astype(x.dtype)
    x = x + jnp.einsum("bsr,rd->bsd", y, p["w_out"])
    x = _ffn_residual(cfg, p, x)
    return x, 0.0


def rglru_cache_specs(cfg, B: int, cache_len: int) -> dict:
    r = cfg.lru_width
    return {"h": ParamSpec((B, r), ("batch", "ffn"), dtype=F32, init="zeros"),
            "conv": ParamSpec((B, cfg.conv_width - 1, r),
                              ("batch", None, "ffn"), init="zeros")}


def rglru_block_decode(cfg, p, x, cache, pos, ctx):
    h = _norm(cfg, p, "ln_rec", x)                         # (B,1,d)
    gate = jax.nn.gelu(jnp.einsum("bsd,dr->bsr", h, p["w_gate"]).astype(F32))
    u = jnp.einsum("bsd,dr->bsr", h, p["w_x"])
    u, conv_state = _causal_conv(u, p["conv_w"], cache["conv"])
    log_a, i_gate = _rglru_gates(p, u)
    a = jnp.exp(log_a)[:, 0]
    b = (jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-9))
         * (i_gate * u.astype(F32)))[:, 0]
    h_new = a * cache["h"] + b                             # (B,r)
    y = (gate[:, 0] * h_new).astype(x.dtype)[:, None]
    x = x + jnp.einsum("bsr,rd->bsd", y, p["w_out"])
    x = _ffn_residual(cfg, p, x)
    return x, {"h": h_new, "conv": conv_state}


# ============================================================================
# mLSTM block — xLSTM matrix-memory (chunked parallel form)
# ============================================================================

def mlstm_block_specs(cfg) -> dict:
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.hd
    di = H * hd
    s = {}
    s |= _norm_specs(cfg, "ln")
    s["w_up"] = ParamSpec((d, 2 * di), ("embed", "ffn"))
    s["conv_w"] = ParamSpec((cfg.conv_width, di), ("conv", "ffn"), scale=0.5)
    s["wq"] = ParamSpec((di, H, hd), ("ffn", "heads", None))
    s["wk"] = ParamSpec((di, H, hd), ("ffn", "heads", None))
    s["wv"] = ParamSpec((di, H, hd), ("ffn", "heads", None))
    s["w_i"] = ParamSpec((di, H), ("ffn", "heads"), dtype=F32)
    s["b_i"] = ParamSpec((H,), ("heads",), dtype=F32, init="zeros")
    s["w_f"] = ParamSpec((di, H), ("ffn", "heads"), dtype=F32)
    s["b_f"] = ParamSpec((H,), ("heads",), dtype=F32, init="ones", scale=1.0)
    s["ogate_ln"] = ParamSpec((H, hd), ("heads", None), init="zeros")
    s["w_down"] = ParamSpec((di, d), ("ffn", "embed"))
    return s


def _mlstm_qkvif(cfg, p, x):
    """Shared projections. x: (B,S,d) -> q,k,v (B,S,H,hd); li,lf (B,S,H) f32."""
    h = _norm(cfg, p, "ln", x)
    up = jnp.einsum("bsd,de->bse", h, p["w_up"])
    gate, main = jnp.split(up, 2, axis=-1)
    main, _ = _causal_conv(main, p["conv_w"])
    main = jax.nn.silu(main.astype(F32)).astype(x.dtype)
    q = jnp.einsum("bse,ehk->bshk", main, p["wq"])
    k = jnp.einsum("bse,ehk->bshk", main, p["wk"])
    v = jnp.einsum("bse,ehk->bshk", main, p["wv"])
    li = jnp.einsum("bse,eh->bsh", main.astype(F32), p["w_i"]) + p["b_i"]
    lf = jax.nn.log_sigmoid(
        jnp.einsum("bse,eh->bsh", main.astype(F32), p["w_f"]) + p["b_f"])
    return gate, q, k, v, li, lf


def _mlstm_chunk(q, k, v, li, lf, C0, n0, m0, scale):
    """One chunk of the stabilized chunkwise mLSTM.

    q,k,v: (B,c,H,hd); li,lf: (B,c,H) log gates; carried state
    C0: (B,H,hd,hd), n0: (B,H,hd), m0: (B,H). Returns (h, C1, n1, m1).
    """
    B, c, H, hd = q.shape
    F = jnp.cumsum(lf, axis=1)                                  # (B,c,H)
    # intra-chunk decay matrix D[t,s] = F_t - F_s + li_s for s<=t
    Ft = F[:, :, None, :]
    Fs = F[:, None, :, :]
    D = Ft - Fs + li[:, None, :, :]                             # (B,t,s,H)
    tri = jnp.tril(jnp.ones((c, c), bool))
    D = jnp.where(tri[None, :, :, None], D, -jnp.inf)
    m_intra = D.max(axis=2)                                     # (B,t,H)
    m_inter = m0[:, None, :] + F                                # (B,t,H)
    m_t = jnp.maximum(m_intra, m_inter)
    m_t = jnp.maximum(m_t, -1e30)                               # keep finite

    qs = q.astype(F32) * scale
    sc = jnp.einsum("bthd,bshd->btsh", qs, k.astype(F32))       # (B,t,s,H)
    # D: (B,t,s,H); m_t: (B,t,H) -> broadcast over s
    w = jnp.exp(D - m_t[:, :, None, :])
    scw = sc * w   # explicit pairwise product: a 3-operand einsum here can
    #                materialize a (B,t,s,H,hd) intermediate (hundreds of GB)
    h_intra = jnp.einsum("btsh,bshd->bthd", scw, v.astype(F32))
    n_intra = jnp.einsum("btsh,bshd->bthd", scw, k.astype(F32))

    dec = jnp.exp(m_inter - m_t)                                # (B,t,H)
    h_inter = jnp.einsum("bthd,bhde->bthe", qs, C0) * dec[..., None]
    n_inter = jnp.einsum("bthd,bhd->bth", qs, n0) * dec

    num = h_intra + h_inter                                     # (B,t,H,hd)
    qn = jnp.einsum("bthd,bthd->bth", qs, n_intra) + n_inter
    den = jnp.maximum(jnp.abs(qn), jnp.exp(-m_t))
    h = num / den[..., None]

    # chunk-end state
    F_tot = F[:, -1, :]                                         # (B,H)
    m_kv = (F_tot[:, None, :] - F + li)                         # (B,s,H)
    m1 = jnp.maximum(m0 + F_tot, m_kv.max(axis=1))
    w_kv = jnp.exp(m_kv - m1[:, None, :])
    C1 = (jnp.exp(m0 + F_tot - m1)[:, :, None, None] * C0
          + jnp.einsum("bsh,bshd,bshe->bhde", w_kv, k.astype(F32), v.astype(F32)))
    n1 = (jnp.exp(m0 + F_tot - m1)[:, :, None] * n0
          + jnp.einsum("bsh,bshd->bhd", w_kv, k.astype(F32)))
    return h, C1, n1, m1


def mlstm_block_apply(cfg, p, x, ctx):
    B, S, d = x.shape
    H, hd = cfg.n_heads, cfg.hd
    gate, q, k, v, li, lf = _mlstm_qkvif(cfg, p, x)
    scale = hd ** -0.5
    c = min(cfg.attn_chunk, S)
    nc = S // c

    def chunk_step(carry, blk):
        C0, n0, m0 = carry
        qb, kb, vb, lib, lfb = blk
        h, C1, n1, m1 = _mlstm_chunk(qb, kb, vb, lib, lfb, C0, n0, m0, scale)
        return (C1, n1, m1), h

    split = lambda a: jnp.moveaxis(
        a.reshape(B, nc, c, *a.shape[2:]), 1, 0)
    C0 = shard_batch(jnp.zeros((B, H, hd, hd), F32))
    n0 = shard_batch(jnp.zeros((B, H, hd), F32))
    m0 = shard_batch(jnp.full((B, H), -1e30, F32))
    _, hs = jax.lax.scan(chunk_step, (C0, n0, m0),
                         (split(q), split(k), split(v), split(li), split(lf)))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, H, hd)             # fp32
    h = rms_norm(h.astype(x.dtype), p["ogate_ln"])
    h = h.reshape(B, S, H * hd) * jax.nn.silu(gate.astype(F32)).astype(x.dtype)
    return x + jnp.einsum("bse,ed->bsd", h, p["w_down"]), 0.0


def mlstm_cache_specs(cfg, B: int, cache_len: int) -> dict:
    H, hd = cfg.n_heads, cfg.hd
    di = H * hd
    return {"C": ParamSpec((B, H, hd, hd), ("batch", "heads", None, None),
                           dtype=F32, init="zeros"),
            "n": ParamSpec((B, H, hd), ("batch", "heads", None), dtype=F32,
                           init="zeros"),
            "m": ParamSpec((B, H), ("batch", "heads"), dtype=F32, init="zeros"),
            "conv": ParamSpec((B, cfg.conv_width - 1, di),
                              ("batch", None, "ffn"), init="zeros")}


def mlstm_block_decode(cfg, p, x, cache, pos, ctx):
    B = x.shape[0]
    H, hd = cfg.n_heads, cfg.hd
    h0 = _norm(cfg, p, "ln", x)
    up = jnp.einsum("bsd,de->bse", h0, p["w_up"])
    gate, main = jnp.split(up, 2, axis=-1)
    main, conv_state = _causal_conv(main, p["conv_w"], cache["conv"])
    main = jax.nn.silu(main.astype(F32)).astype(x.dtype)
    q = jnp.einsum("bse,ehk->bshk", main, p["wq"])[:, 0]
    k = jnp.einsum("bse,ehk->bshk", main, p["wk"])[:, 0]
    v = jnp.einsum("bse,ehk->bshk", main, p["wv"])[:, 0]
    li = (jnp.einsum("bse,eh->bsh", main.astype(F32), p["w_i"]) + p["b_i"])[:, 0]
    lf = jax.nn.log_sigmoid(
        jnp.einsum("bse,eh->bsh", main.astype(F32), p["w_f"]) + p["b_f"])[:, 0]
    m1 = jnp.maximum(lf + cache["m"], li)
    fd = jnp.exp(lf + cache["m"] - m1)
    idc = jnp.exp(li - m1)
    C1 = fd[..., None, None] * cache["C"] + idc[..., None, None] * \
        jnp.einsum("bhd,bhe->bhde", k.astype(F32), v.astype(F32))
    n1 = fd[..., None] * cache["n"] + idc[..., None] * k.astype(F32)
    qs = q.astype(F32) * (hd ** -0.5)
    num = jnp.einsum("bhd,bhde->bhe", qs, C1)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qs, n1)),
                      jnp.exp(-m1))
    h = (num / den[..., None])[:, None]                          # (B,1,H,hd)
    h = rms_norm(h.astype(x.dtype), p["ogate_ln"])
    h = h.reshape(B, 1, H * hd) * jax.nn.silu(gate.astype(F32)).astype(x.dtype)
    x = x + jnp.einsum("bse,ed->bsd", h, p["w_down"])
    return x, {"C": C1, "n": n1, "m": m1, "conv": conv_state}


# ============================================================================
# sLSTM block — xLSTM scalar-memory (sequential scan; not parallelizable)
# ============================================================================

def slstm_block_specs(cfg) -> dict:
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.hd
    di = H * hd
    s = {}
    s |= _norm_specs(cfg, "ln")
    s["w_in"] = ParamSpec((d, 4 * di), ("embed", "ffn"))       # i,f,z,o
    s["r_h"] = ParamSpec((4, H, hd, hd), (None, "heads", None, None))
    s["b"] = ParamSpec((4 * di,), ("ffn",), init="zeros")
    s["w_out"] = ParamSpec((di, d), ("ffn", "embed"))
    return s


def _slstm_scan(cfg, p, z_in, c0, n0, m0, h0):
    """z_in: (B,S,4*di). Sequential over S. Returns (h_seq, final_state)."""
    B, S, _ = z_in.shape
    H, hd = cfg.n_heads, cfg.hd

    def step(carry, zt):
        c, n, m, h = carry                                  # (B,H,hd) each; m too
        rec = jnp.einsum("bhd,ghde->bghe", h, p["r_h"].astype(F32))
        zt = zt.reshape(B, 4, H, hd).astype(F32) + rec
        i_r, f_r, z_r, o_r = zt[:, 0], zt[:, 1], zt[:, 2], zt[:, 3]
        lf = jax.nn.log_sigmoid(f_r)
        m1 = jnp.maximum(lf + m, i_r)
        fd = jnp.exp(lf + m - m1)
        idc = jnp.exp(i_r - m1)
        c1 = fd * c + idc * jnp.tanh(z_r)
        n1 = fd * n + idc
        h1 = jax.nn.sigmoid(o_r) * c1 / jnp.maximum(n1, 1e-6)
        return (c1, n1, m1, h1), h1

    zs = jnp.moveaxis(z_in, 1, 0)                           # (S,B,4di)
    (c, n, m, h), hs = jax.lax.scan(step, (c0, n0, m0, h0), zs)
    return jnp.moveaxis(hs, 0, 1), (c, n, m, h)             # (B,S,H,hd)


def slstm_block_apply(cfg, p, x, ctx):
    B, S, d = x.shape
    H, hd = cfg.n_heads, cfg.hd
    z_in = jnp.einsum("bsd,de->bse", _norm(cfg, p, "ln", x), p["w_in"]) + p["b"]
    zero = shard_batch(jnp.zeros((B, H, hd), F32))
    hs, _ = _slstm_scan(cfg, p, z_in, zero, zero, zero - 1e30, zero)
    y = hs.reshape(B, S, H * hd).astype(x.dtype)
    return x + jnp.einsum("bse,ed->bsd", y, p["w_out"]), 0.0


def slstm_cache_specs(cfg, B: int, cache_len: int) -> dict:
    H, hd = cfg.n_heads, cfg.hd
    mk = lambda: ParamSpec((B, H, hd), ("batch", "heads", None), dtype=F32,
                           init="zeros")
    return {"c": mk(), "n": mk(), "m": mk(), "h": mk()}


def slstm_block_decode(cfg, p, x, cache, pos, ctx):
    B = x.shape[0]
    z_in = jnp.einsum("bsd,de->bse", _norm(cfg, p, "ln", x), p["w_in"]) + p["b"]
    hs, (c, n, m, h) = _slstm_scan(cfg, p, z_in, cache["c"], cache["n"],
                                   cache["m"], cache["h"])
    y = hs[:, -1:].reshape(B, 1, -1).astype(x.dtype)
    x = x + jnp.einsum("bse,ed->bsd", y, p["w_out"])
    return x, {"c": c, "n": n, "m": m, "h": h}


# ============================================================================
# Parallel attention + Mamba-2 block ("attn_ssm") — Falcon-H1
# ============================================================================
#
# Every layer runs GQA attention and a Mamba-2 mixer side by side on one
# normed input, then a SwiGLU FFN (transformers' modeling_falcon_h1), with
# the published muP multipliers where the model applies them:
#
#   h = rmsnorm(x)
#   x = x + attn(h * attn_in) * attn_out + mamba(h) * ssm_out
#       (attn's keys scaled by key_mult)
#   x = x + down(silu(gate(n) * mlp_mults[0]) * up(n)) * mlp_mults[1],
#       n = rmsnorm(x)
#
# mamba: zxbcdt = in_proj(h * ssm_in) * mup, the five ssm_mults laid over
# the z, x, B, C and dt segments; xBC through a causal depthwise conv with
# bias, then SiLU; dt = softplus(dt + dt_bias), A = -exp(A_log); for head h
# of group g = h // (heads / groups), with S_h of (head_dim, state):
#
#   S_h <- exp(dt_h A_h) S_h + dt_h x_h (outer) B_g
#   y_h  = S_h C_g + D_h x_h
#
# then y = rmsnorm per group of (y * silu(z)) (the gate before the norm)
# and out_proj(y). The recurrent state S and the conv tail are float32.

def attn_ssm_block_specs(cfg) -> dict:
    d, di = cfg.d_model, cfg.d_ssm
    gn, hs = cfg.ssm_groups * cfg.ssm_state, cfg.ssm_heads
    s = {}
    s |= _norm_specs(cfg, "ln_in")
    s["attn"] = attn_specs(d, cfg.n_heads, cfg.n_kv_heads, cfg.hd)
    s["ssm"] = {
        "w_in": ParamSpec((d, 2 * di + 2 * gn + hs), ("embed", "ffn")),
        "conv_w": ParamSpec((cfg.conv_width, di + 2 * gn), ("conv", "ffn"),
                            scale=0.5),
        "conv_b": ParamSpec((di + 2 * gn,), ("ffn",), init="zeros"),
        "dt_bias": ParamSpec((hs,), (None,), init="zeros"),
        "A_log": ParamSpec((hs,), (None,), init="zeros"),
        "D": ParamSpec((hs,), (None,), init="ones"),
        "norm": ParamSpec((di,), ("norm",), init="zeros"),
        "w_out": ParamSpec((di, d), ("ffn", "embed")),
    }
    s |= _norm_specs(cfg, "ln_ffn")
    s["ffn"] = ffn_specs(d, cfg.d_ff)
    return s


def attn_ssm_cache_specs(cfg, B: int, cache_len: int) -> dict:
    conv_dim = cfg.d_ssm + 2 * cfg.ssm_groups * cfg.ssm_state
    return attn_cache_specs(cfg, B, cache_len) | {
        "ssm": ParamSpec((B, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                         ("batch", None, None, None), dtype=F32,
                         init="zeros"),
        # the conv's last conv_width - 1 inputs, oldest first, side by
        # side in one row a slot: lane-aligned slices, no padded axis
        "conv": ParamSpec((B, (cfg.conv_width - 1) * conv_dim),
                          ("batch", None), dtype=F32, init="zeros")}


def _conv_step(xbc, w, tail):
    """The causal conv at one position: xbc (B,1,C), w (W,C), tail
    (B,(W-1)*C) the last W-1 inputs. Returns y (B,1,C) and the new tail."""
    c = xbc.shape[-1]
    ext = jnp.concatenate([tail, xbc[:, 0]], axis=1)
    y = sum(ext[:, i * c:(i + 1) * c] * w[i] for i in range(w.shape[0]))
    return y[:, None], ext[:, c:]


def _ssm_inputs(cfg, p, h, conv_tail=None):
    """in_proj, the conv over xBC and the discretisation, in float32:
    z (B,S,di), x (B,S,H,P), B and C per head (B,S,H,N), dt (B,S,H), and
    the conv's new tail. With `conv_tail`, h is one position."""
    di, g, n, hs = cfg.d_ssm, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    sizes = (di, di, g * n, g * n, hs)
    mup = jnp.asarray(np.repeat(np.asarray(cfg.ssm_mults, np.float32),
                                sizes))
    zxbcdt = jnp.einsum("bsd,de->bse", h * cfg.ssm_in_mult,
                        p["w_in"]).astype(F32) * mup
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * g * n], axis=-1)
    w = p["conv_w"].astype(F32)
    if conv_tail is None:
        xbc, _ = _causal_conv(xbc, w)
    else:
        xbc, conv_tail = _conv_step(xbc, w, conv_tail)
    xbc = jax.nn.silu(xbc + p["conv_b"].astype(F32))
    x, bm, cm = jnp.split(xbc, [di, di + g * n], axis=-1)
    lead = x.shape[:2]
    rep = hs // g                       # head h reads group h // rep

    def per_head(a):
        return jnp.repeat(a.reshape(*lead, g, n), rep, axis=2)

    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    return (z, x.reshape(*lead, hs, cfg.ssm_head_dim), per_head(bm),
            per_head(cm), dt, conv_tail)


def _ssm_step(rate, state, x, bm, cm, dt):
    """One position of the recurrence for every head: rate = A (H,), state
    (B,H,P,N), x (B,H,P), bm/cm (B,H,N), dt (B,H). Returns the new state
    and S C (B,H,P)."""
    state = (jnp.exp(dt * rate)[..., None, None] * state
             + (dt[..., None] * x)[..., None] * bm[:, :, None, :])
    return state, jnp.sum(state * cm[:, :, None, :], axis=-1)


def _ssm_out(cfg, p, z, x, sc, dtype):
    """D skip, gate, grouped RMSNorm, out_proj and the output multiplier."""
    y = sc + p["D"].astype(F32)[:, None] * x               # (B,S,H,P)
    y = y.reshape(*z.shape) * jax.nn.silu(z)
    g = cfg.ssm_groups
    y = y.reshape(*z.shape[:-1], g, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    y = y.reshape(z.shape) * (1.0 + p["norm"].astype(F32))
    return jnp.einsum("bse,ed->bsd", y.astype(dtype),
                      p["w_out"]) * cfg.ssm_out_mult


def _ssm_ffn(cfg, p, x):
    """x + SwiGLU(rmsnorm(x)) with the gate and output multipliers."""
    n = _norm(cfg, p, "ln_ffn", x)
    f = p["ffn"]
    g = jnp.einsum("bsd,df->bsf", n, f["w_gate"]).astype(F32)
    u = jnp.einsum("bsd,df->bsf", n, f["w_up"])
    hid = jax.nn.silu(g * cfg.mlp_mults[0]).astype(x.dtype) * u
    return x + jnp.einsum("bsf,fd->bsd", hid, f["w_down"]) * cfg.mlp_mults[1]


def _ssm_qkv(cfg, p, h, ctx):
    q, k, v = qkv_project(p["attn"], h * cfg.attn_in_mult, ctx["positions"],
                          n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                          head_dim=cfg.hd, rope=ctx.get("rope", True),
                          theta=cfg.rope_theta)
    return q, k * cfg.key_mult, v


def attn_ssm_block_apply(cfg, p, x, ctx):
    h = _norm(cfg, p, "ln_in", x)
    with jax.named_scope("attention"):
        q, k, v = _ssm_qkv(cfg, p, h, ctx)
        o = attn_lib.attention(q, k, v, n_kv=cfg.n_kv_heads, causal=True,
                               chunk=cfg.attn_chunk,
                               schedule=cfg.attn_schedule)
        a = out_project(p["attn"], o) * cfg.attn_out_mult
    with jax.named_scope("ssm"):
        s = p["ssm"]
        z, xs, bm, cm, dt, _ = _ssm_inputs(cfg, s, h)
        rate = -jnp.exp(s["A_log"].astype(F32))
        state0 = shard_batch(jnp.zeros(
            (x.shape[0], cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), F32))
        seq = lambda t: jnp.moveaxis(t, 1, 0)
        _, sc = jax.lax.scan(
            lambda st, inp: _ssm_step(rate, st, *inp), state0,
            (seq(xs), seq(bm), seq(cm), seq(dt)))
        m = _ssm_out(cfg, s, z, xs, jnp.moveaxis(sc, 0, 1), x.dtype)
    x = x + a + m
    with jax.named_scope("ffn"):
        x = _ssm_ffn(cfg, p, x)
    return x, 0.0


def attn_ssm_block_decode(cfg, p, x, cache, pos, ctx):
    h = _norm(cfg, p, "ln_in", x)
    with jax.named_scope("attention"):
        q, k, v = _ssm_qkv(cfg, p, h, ctx)
        o, kc, vc = _attend_decode(cfg, q, k, v, cache, pos, ctx, None)
        a = out_project(p["attn"], o) * cfg.attn_out_mult
    with jax.named_scope("ssm"):
        s = p["ssm"]
        z, xs, bm, cm, dt, conv = _ssm_inputs(cfg, s, h, cache["conv"])
        state, sc = _ssm_step(-jnp.exp(s["A_log"].astype(F32)), cache["ssm"],
                              xs[:, 0], bm[:, 0], cm[:, 0], dt[:, 0])
        m = _ssm_out(cfg, s, z, xs, sc[:, None], x.dtype)
    x = x + a + m
    with jax.named_scope("ffn"):
        x = _ssm_ffn(cfg, p, x)
    return x, {"k": kc, "v": vc, "ssm": state, "conv": conv}


# ============================================================================
# Kind registry
# ============================================================================

BLOCKS: dict[str, dict[str, Any]] = {
    "attn": dict(specs=attn_block_specs, apply=attn_block_apply,
                 cache=attn_cache_specs, decode=attn_block_decode),
    "local_attn": dict(
        specs=attn_block_specs,
        apply=lambda cfg, p, x, ctx: attn_block_apply(cfg, p, x, ctx,
                                                      window=cfg.window),
        cache=lambda cfg, B, L: attn_cache_specs(
            cfg, B, min(L, cfg.window or L)),
        decode=lambda cfg, p, x, c, pos, ctx: attn_block_decode(
            cfg, p, x, c, pos, ctx, window=cfg.window)),
    "attn_moe": dict(specs=moe_block_specs, apply=moe_block_apply,
                     cache=lambda cfg, B, L: attn_cache_specs(
                         cfg, B, min(L, cfg.window or L)),
                     decode=moe_block_decode),
    "cross": dict(specs=cross_block_specs, apply=cross_block_apply,
                  cache=cross_cache_specs, decode=cross_block_decode),
    "attn_cross": dict(specs=attn_cross_block_specs,
                       apply=attn_cross_block_apply,
                       cache=attn_cross_cache_specs,
                       decode=attn_cross_block_decode),
    "enc_attn": dict(specs=enc_attn_block_specs, apply=enc_attn_block_apply,
                     cache=None, decode=None),
    "rglru": dict(specs=rglru_block_specs, apply=rglru_block_apply,
                  cache=rglru_cache_specs, decode=rglru_block_decode,
                  recurrent=True),
    "mlstm": dict(specs=mlstm_block_specs, apply=mlstm_block_apply,
                  cache=mlstm_cache_specs, decode=mlstm_block_decode,
                  recurrent=True),
    "slstm": dict(specs=slstm_block_specs, apply=slstm_block_apply,
                  cache=slstm_cache_specs, decode=slstm_block_decode,
                  recurrent=True),
    "attn_ssm": dict(specs=attn_ssm_block_specs, apply=attn_ssm_block_apply,
                     cache=attn_ssm_cache_specs, decode=attn_ssm_block_decode,
                     recurrent=True),
}

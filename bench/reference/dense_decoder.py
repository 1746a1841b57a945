"""Plain float32 reference of a dense decoder, written from the published
description of the Qwen3 and Llama families (Yi is a Llama): pre-norm
blocks of grouped-query attention and a SwiGLU FFN, RMSNorm, rotary
position embeddings (rotate-half, inverse frequency theta^(-2i/head_dim)),
untied unembedding; Qwen3 adds an RMSNorm over each head of q and k before
the rotation.

It imports nothing of the program and takes nothing the program made: it
draws the weights again from the seed, layer by layer, through the
benchmark's own generator. Every matrix product runs in float32 at
`Precision.HIGHEST`. Sequences are padded to one length so that one
compiled program serves every request and layer, and are run one layer
and one sequence at a time so that the reference fits beside nothing else.

`precision="fp8"` is the control: the same computation with every matrix
product's inputs rounded to float8 (e4m3), weights scaled per output
channel and activations per row, as an fp8 deployment would.

Stored weights follow the program's checkpoint layout: a norm's scale is
stored as its offset from 1, q/k/v projections as (d_model, heads,
head_dim), the output projection as (heads, head_dim, d_model).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                 # largest finite float8_e4m3fn

# How each stored leaf is drawn; see harness/weights.leaf_std.
INIT = {
    "tok_embed": {"std": 1.0},
    "unembed": {"fan_in_axes": 1},
    "wq": {"fan_in_axes": 1}, "wk": {"fan_in_axes": 1},
    "wv": {"fan_in_axes": 1}, "wo": {"fan_in_axes": 2},
    "w_gate": {"fan_in_axes": 1}, "w_up": {"fan_in_axes": 1},
    "w_down": {"fan_in_axes": 1},
    "ln_attn": {"std": 0.1}, "ln_ffn": {"std": 0.1}, "ln_f": {"std": 0.1},
    "q_norm": {"std": 0.1}, "k_norm": {"std": 0.1},
}

BLOCK = "blocks/sub0/"


def _sizes(cfg: dict):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (d, h, cfg["num_key_value_heads"], cfg.get("head_dim") or d // h,
            cfg["intermediate_size"], cfg["vocab_size"])


def layer_shapes(cfg: dict) -> dict:
    d, h, kv, hd, f, _ = _sizes(cfg)
    out = {BLOCK + "ln_attn": (d,), BLOCK + "attn/wq": (d, h, hd),
           BLOCK + "attn/wk": (d, kv, hd), BLOCK + "attn/wv": (d, kv, hd),
           BLOCK + "attn/wo": (h, hd, d), BLOCK + "ln_ffn": (d,),
           BLOCK + "ffn/w_gate": (d, f), BLOCK + "ffn/w_up": (d, f),
           BLOCK + "ffn/w_down": (f, d)}
    if cfg["bench"].get("qk_norm"):
        out[BLOCK + "attn/q_norm"] = (hd,)
        out[BLOCK + "attn/k_norm"] = (hd,)
    return out


def top_shapes(cfg: dict) -> dict:
    d, *_, v = _sizes(cfg)
    return {"tok_embed": (v, d), "unembed": (d, v), "ln_f": (d,)}


# ----------------------------------------------------------------------------
# The computation
# ----------------------------------------------------------------------------


def _fp8(x, axes):
    """Round to float8 e4m3 with one scale per slice over `axes`."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(spec, x, w, fp8: bool, x_axes, w_axes):
    if fp8:
        x, w = _fp8(x, x_axes), _fp8(w, w_axes)
    return jnp.einsum(spec, x, w, precision=HI)


def _rmsnorm(x, offset, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + offset)


def _rope(x, theta):
    """x: (S, heads, hd), rotate-half rotary embedding at positions 0..S-1."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _layer(cfg: dict, fp8: bool, w: dict, x):
    """One block over one sequence x: (S, d)."""
    d, h, kv, hd, f, _ = _sizes(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    w = {k.removeprefix(BLOCK): v.astype(F32) for k, v in w.items()}
    a = _rmsnorm(x, w["ln_attn"], eps)
    q = _mm("sd,dhk->shk", a, w["attn/wq"], fp8, -1, 0)
    k = _mm("sd,dhk->shk", a, w["attn/wk"], fp8, -1, 0)
    v = _mm("sd,dhk->shk", a, w["attn/wv"], fp8, -1, 0)
    if "attn/q_norm" in w:
        q = _rmsnorm(q, w["attn/q_norm"], eps)
        k = _rmsnorm(k, w["attn/k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = h // kv                       # query head i reads kv head i // rep
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = x.shape[0]
    scores = _mm("qhk,shk->hqs", q, k, fp8, -1, -1) / np.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = _mm("hqs,shk->qhk", p, v, fp8, -1, 0)
    x = x + _mm("qhk,hkd->qd", o, w["attn/wo"], fp8, (-2, -1), (0, 1))
    b = _rmsnorm(x, w["ln_ffn"], eps)
    g = _mm("sd,df->sf", b, w["ffn/w_gate"], fp8, -1, 0)
    u = _mm("sd,df->sf", b, w["ffn/w_up"], fp8, -1, 0)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, w["ffn/w_down"], fp8,
                   -1, 0)


def _logits(cfg: dict, fp8: bool, top: dict, x, idx):
    """Next-token logits at rows `idx` of the final hidden states x."""
    hn = _rmsnorm(x[idx], top["ln_f"].astype(F32), cfg["rms_norm_eps"])
    return _mm("sd,dv->sv", hn, top["unembed"].astype(F32), fp8, -1, 0)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key: str, fp8: bool):
    import json
    cfg = json.loads(cfg_key)
    embed = jax.jit(lambda table, toks: table.astype(F32)[toks])
    layer = jax.jit(functools.partial(_layer, cfg, fp8))
    logits = jax.jit(functools.partial(_logits, cfg, fp8))
    return embed, layer, logits


@jax.jit
def _gaps(ref, target, mask):
    """max logit - logit of `target`, per row; 0 where masked out."""
    tgt = jnp.take_along_axis(ref, target[:, None], axis=1)[:, 0]
    return jnp.where(mask, jnp.max(ref, axis=1) - tgt, 0.0)


def logit_gaps(cfg: dict, draw_layer, draw_top, seed: int, requests,
               length: int, control: bool = False):
    """Per request, the gap by which each served token's reference logit
    lies below the reference's best at that position, as an array.

    `requests` are (prompt, served) pairs of int arrays; the request is fed
    prompt + served[:-1], so position len(prompt)-1+j predicts served[j].
    Every sequence is padded to `length` tokens. With `control`, the
    gaps are instead those of the token the fp8 control puts first at each
    position, read on the float32 reference's logits."""
    cfg_key = _cfg_key(cfg)
    modes = [False, True] if control else [False]
    seqs, idxs, tgts, masks = [], [], [], []
    for prompt, served in requests:
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        toks = np.concatenate([prompt, served[:-1]])
        if toks.size > length:
            raise ValueError(f"sequence of {toks.size} > {length} tokens")
        pad = np.zeros(length, np.int32)
        pad[:toks.size] = toks
        n = served.size
        idx = np.zeros(length, np.int32)
        idx[:n] = prompt.size - 1 + np.arange(n)
        tgt = np.zeros(length, np.int32)
        tgt[:n] = served
        seqs.append(pad)
        idxs.append(idx)
        tgts.append(tgt)
        masks.append(np.arange(length) < n)
    top = draw_top(seed, 0)
    hidden = {}
    for fp8 in modes:
        embed, _, _ = _programs(cfg_key, fp8)
        hidden[fp8] = [embed(top["tok_embed"], s) for s in seqs]
    for layer in range(cfg["num_hidden_layers"]):
        w = draw_layer(seed, layer)
        for fp8 in modes:
            _, run, _ = _programs(cfg_key, fp8)
            hidden[fp8] = [run(w, x) for x in hidden[fp8]]
        del w
    out = []
    for i, n_mask in enumerate(masks):
        _, _, logits = _programs(cfg_key, False)
        ref = logits(top, hidden[False][i], idxs[i])
        target = tgts[i]
        if control:
            _, _, ctl_logits = _programs(cfg_key, True)
            target = jnp.argmax(ctl_logits(top, hidden[True][i], idxs[i]),
                                axis=1).astype(jnp.int32)
        g = np.asarray(_gaps(ref, target, n_mask))
        out.append(g[:int(n_mask.sum())])
        del ref
    return out


def _cfg_key(cfg: dict) -> str:
    import json
    return json.dumps(cfg, sort_keys=True)

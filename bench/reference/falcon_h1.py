"""Plain float32 reference of Falcon-H1, written from the published
description (transformers' `modeling_falcon_h1.py`, model type
`falcon_h1`): every layer runs grouped-query attention and a Mamba-2 mixer
in parallel on one normed input, then a SwiGLU MLP, with the published muP
multipliers:

    h = rmsnorm(x)
    x = x + attn(h * attention_in_multiplier) * attention_out_multiplier
          + mamba(h) * ssm_out_multiplier
    x = x + down(silu(gate(n) * mlp_multipliers[0]) * up(n))
              * mlp_multipliers[1],                        n = rmsnorm(x)

Attention's keys are k_proj(.) * key_multiplier; rotary embeddings are
rotate-half with inverse frequency theta^(-2i/head_dim). The mixer:
zxbcdt = in_proj(h * ssm_in_multiplier) * mup, mup the five
ssm_multipliers laid over the z, x, B, C and dt segments; a causal
depthwise conv of width mamba_d_conv with bias over xBC, then SiLU;
dt = softplus(dt + dt_bias), A = -exp(A_log); for each head h of group
g = h // (heads / groups), with a state S_h of (mamba_d_head,
mamba_d_state), at every position in turn:

    S_h <- exp(dt_h A_h) S_h + dt_h x_h (outer) B_g
    y_h  = S_h C_g + D_h x_h

then y = rmsnorm per group of (y * silu(z)) (mamba_norm_before_gate is
false: the gate comes first), out_proj(y). The embedding is scaled by
embedding_multiplier and the logits by lm_head_multiplier; the
unembedding is untied. Departures: none in the mathematics; the time step
is not clipped (the published time_step_limit is (0, inf)).

It imports nothing of the program and takes nothing the program made: it
draws the weights again from the seed, layer by layer, through the
benchmark's own generator. Every matrix product runs in float32 at
`Precision.HIGHEST`; the recurrence is a plain scan over positions, in
float32. Sequences are padded to one length so that one compiled program
serves every request and layer, and are run one layer and one sequence at
a time so that the reference fits beside nothing else.

`precision="fp8"` is the control: every matrix product's inputs rounded
to float8 (e4m3), weights scaled per output channel and activations per
row, as the dense reference's control does; the conv and the recurrence
stay float32, as an fp8 deployment keeps its state.

Stored weights follow the program's checkpoint layout: a norm's scale is
stored as its offset from 1, q/k/v projections as (d_model, heads,
head_dim), the output projection as (heads, head_dim, d_model), the
mixer's in_proj as (d_model, z + x + B + C + dt), its conv as (width,
channels).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                 # largest finite float8_e4m3fn

# The published Falcon-H1-34B sizes and multipliers the weights are drawn
# for (config.json of tiiuae/Falcon-H1-34B-Instruct).
_D, _D_SSM, _HEADS_X_HD, _FF = 5120, 4096, 20 * 128, 21504
_MULT = {"embedding": 5.656854249492381, "lm_head": 0.0078125,
         "key": 0.011048543456039804, "attention_out": 0.0375,
         "ssm_in": 0.25, "ssm_out": 0.08838834764831845,
         "mlp_gate": 0.1767766952966369, "mlp_down": 0.011160714285714284}

# How each stored leaf is drawn; see harness/weights.leaf_std. Random
# weights drawn 1/sqrt(fan-in) would leave the multipliers scaling every
# branch and the logits down: the logits would spread over about 1/128,
# where bf16 rounding and the fp8 control look alike to the check, and a
# mixer scaled by 0.088 on an embedding scaled by 5.66 would hardly move
# a token. So a matrix that a multiplier scales is drawn 1/(multiplier *
# sqrt(fan-in)), the embedding 1/embedding_multiplier: at these widths
# every branch adds O(1) to the residual stream and the logits spread by
# about 1, as the dense configurations' do. The mixer's D and dt_bias are
# drawn O(1) and A_log from N(0, 1) (decay rates from e^-2 to e^2), so
# that the state and the skip path both count.
INIT = {
    "tok_embed": {"std": 1.0 / _MULT["embedding"]},
    "unembed": {"std": 1.0 / (_MULT["lm_head"] * math.sqrt(_D))},
    "wq": {"fan_in_axes": 1},
    "wk": {"std": 1.0 / (_MULT["key"] * math.sqrt(_D))},
    "wv": {"fan_in_axes": 1},
    "wo": {"std": 1.0 / (_MULT["attention_out"] * math.sqrt(_HEADS_X_HD))},
    "w_in": {"std": 1.0 / (_MULT["ssm_in"] * math.sqrt(_D))},
    "conv_w": {"std": 0.5}, "conv_b": {"std": 0.1},
    "dt_bias": {"std": 1.0}, "A_log": {"std": 1.0}, "D": {"std": 1.0},
    "norm": {"std": 0.1},
    "w_out": {"std": 1.0 / (_MULT["ssm_out"] * math.sqrt(_D_SSM))},
    "w_gate": {"std": 1.0 / (_MULT["mlp_gate"] * math.sqrt(_D))},
    "w_up": {"fan_in_axes": 1},
    "w_down": {"std": 1.0 / (_MULT["mlp_down"] * math.sqrt(_FF))},
    "ln_in": {"std": 0.1}, "ln_ffn": {"std": 0.1}, "ln_f": {"std": 0.1},
}

BLOCK = "blocks/sub0/"


def _sizes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    di = cfg["mamba_d_ssm"]
    return dict(d=d, h=h, kv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // h,
                f=cfg["intermediate_size"], v=cfg["vocab_size"], di=di,
                hs=cfg["mamba_n_heads"], p=cfg["mamba_d_head"], n=n, g=g,
                w=cfg["mamba_d_conv"], conv=di + 2 * g * n,
                e=2 * di + 2 * g * n + cfg["mamba_n_heads"])


def layer_shapes(cfg: dict) -> dict:
    s = _sizes(cfg)
    d = s["d"]
    return {BLOCK + "ln_in": (d,),
            BLOCK + "attn/wq": (d, s["h"], s["hd"]),
            BLOCK + "attn/wk": (d, s["kv"], s["hd"]),
            BLOCK + "attn/wv": (d, s["kv"], s["hd"]),
            BLOCK + "attn/wo": (s["h"], s["hd"], d),
            BLOCK + "ssm/w_in": (d, s["e"]),
            BLOCK + "ssm/conv_w": (s["w"], s["conv"]),
            BLOCK + "ssm/conv_b": (s["conv"],),
            BLOCK + "ssm/dt_bias": (s["hs"],),
            BLOCK + "ssm/A_log": (s["hs"],),
            BLOCK + "ssm/D": (s["hs"],),
            BLOCK + "ssm/norm": (s["di"],),
            BLOCK + "ssm/w_out": (s["di"], d),
            BLOCK + "ln_ffn": (d,),
            BLOCK + "ffn/w_gate": (d, s["f"]),
            BLOCK + "ffn/w_up": (d, s["f"]),
            BLOCK + "ffn/w_down": (s["f"], d)}


def top_shapes(cfg: dict) -> dict:
    s = _sizes(cfg)
    return {"tok_embed": (s["v"], s["d"]), "unembed": (s["d"], s["v"]),
            "ln_f": (s["d"],)}


# ----------------------------------------------------------------------------
# The computation
# ----------------------------------------------------------------------------


def _fp8(x, axes):
    """Round to float8 e4m3 with one scale per slice over `axes`."""
    sc = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / FP8_MAX
    sc = jnp.where(sc > 0, sc, 1.0)
    return (x / sc).astype(jnp.float8_e4m3fn).astype(F32) * sc


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


def _attention(cfg: dict, fp8: bool, w: dict, h):
    s = _sizes(cfg)
    hin = h * cfg["attention_in_multiplier"]
    q = _mm("sd,dhk->shk", hin, w["attn/wq"], fp8, -1, 0)
    k = _mm("sd,dhk->shk", hin, w["attn/wk"], fp8, -1, 0) \
        * cfg["key_multiplier"]
    v = _mm("sd,dhk->shk", hin, w["attn/wv"], fp8, -1, 0)
    theta = float(cfg["rope_theta"])
    q, k = _rope(q, theta), _rope(k, theta)
    rep = s["h"] // s["kv"]             # query head i reads kv head i // rep
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    n = h.shape[0]
    scores = _mm("qhk,shk->hqs", q, k, fp8, -1, -1) / np.sqrt(s["hd"])
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = _mm("hqs,shk->qhk", p, v, fp8, -1, 0)
    return _mm("qhk,hkd->qd", o, w["attn/wo"], fp8, (-2, -1), (0, 1)) \
        * cfg["attention_out_multiplier"]


def _mamba(cfg: dict, fp8: bool, w: dict, h):
    s = _sizes(cfg)
    di, gn, hs = s["di"], s["g"] * s["n"], s["hs"]
    mup = np.repeat(np.asarray(cfg["ssm_multipliers"], np.float32),
                    [di, di, gn, gn, hs])
    zxbcdt = _mm("sd,de->se", h * cfg["ssm_in_multiplier"], w["ssm/w_in"],
                 fp8, -1, 0) * mup
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * gn], \
        zxbcdt[:, 2 * di + 2 * gn:]
    n_pos, width = h.shape[0], s["w"]
    padded = jnp.concatenate([jnp.zeros((width - 1, s["conv"]), F32), xbc])
    conv = sum(padded[i:i + n_pos] * w["ssm/conv_w"][i]
               for i in range(width)) + w["ssm/conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(n_pos, hs, s["p"])
    rep = hs // s["g"]                   # head h reads group h // rep
    bm = jnp.repeat(xbc[:, di:di + gn].reshape(n_pos, s["g"], s["n"]), rep,
                    axis=1)
    cm = jnp.repeat(xbc[:, di + gn:].reshape(n_pos, s["g"], s["n"]), rep,
                    axis=1)
    dt = jax.nn.softplus(dt + w["ssm/dt_bias"])
    a = -jnp.exp(w["ssm/A_log"])

    def step(state, t):
        x_t, b_t, c_t, dt_t = t
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((hs, s["p"], s["n"]), F32),
                        (x, bm, cm, dt))
    y = y + w["ssm/D"][:, None] * x
    y = y.reshape(n_pos, di) * jax.nn.silu(z)
    y = y.reshape(n_pos, s["g"], di // s["g"])
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    y = y.reshape(n_pos, di) * (1.0 + w["ssm/norm"])
    return _mm("se,ed->sd", y, w["ssm/w_out"], fp8, -1, 0) \
        * cfg["ssm_out_multiplier"]


def _layer(cfg: dict, fp8: bool, w: dict, x):
    """One block over one sequence x: (S, d)."""
    eps = cfg["rms_norm_eps"]
    w = {k.removeprefix(BLOCK): v.astype(F32) for k, v in w.items()}
    h = _rmsnorm(x, w["ln_in"], eps)
    x = x + _attention(cfg, fp8, w, h) + _mamba(cfg, fp8, w, h)
    n = _rmsnorm(x, w["ln_ffn"], eps)
    gate_mult, down_mult = cfg["mlp_multipliers"]
    g = _mm("sd,df->sf", n, w["ffn/w_gate"], fp8, -1, 0) * gate_mult
    u = _mm("sd,df->sf", n, w["ffn/w_up"], fp8, -1, 0)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, w["ffn/w_down"], fp8,
                   -1, 0) * down_mult


VOCAB_BLOCK = 16384     # unembedding columns cast to float32 at a time


def _scan_vocab(cfg: dict, fp8: bool, top: dict, x, idx, target):
    """The next-token logits at rows `idx` of the final hidden states x,
    over the vocabulary a block of columns at a time (a float32 copy of
    the whole unembedding would not fit beside the weights), per row: the
    largest logit, the logit at `target`, and the first id that holds the
    largest."""
    hn = _rmsnorm(x[idx], top["ln_f"].astype(F32), cfg["rms_norm_eps"])
    w = top["unembed"]
    rows = hn.shape[0]
    best = jnp.full((rows,), -jnp.inf, F32)
    arg = jnp.zeros((rows,), jnp.int32)
    at = jnp.zeros((rows,), F32)
    for lo in range(0, w.shape[1], VOCAB_BLOCK):
        blk = w[:, lo:lo + VOCAB_BLOCK].astype(F32)
        lg = _mm("sd,dv->sv", hn, blk, fp8, -1, 0) * cfg["lm_head_multiplier"]
        m, a = jnp.max(lg, axis=1), jnp.argmax(lg, axis=1).astype(jnp.int32)
        better = m > best
        best, arg = jnp.where(better, m, best), jnp.where(better, a + lo, arg)
        inside = (target >= lo) & (target < lo + blk.shape[1])
        here = jnp.take_along_axis(
            lg, jnp.clip(target - lo, 0, blk.shape[1] - 1)[:, None], 1)[:, 0]
        at = jnp.where(inside, here, at)
    return best, at, arg


@functools.lru_cache(maxsize=None)
def _programs(cfg_key: str, fp8: bool):
    import json
    cfg = json.loads(cfg_key)
    mult = cfg["embedding_multiplier"]
    embed = jax.jit(lambda table, toks: table[toks].astype(F32) * mult)
    layer = jax.jit(functools.partial(_layer, cfg, fp8))
    vocab = jax.jit(functools.partial(_scan_vocab, cfg, fp8))
    return embed, layer, vocab


def logit_gaps(cfg: dict, draw_layer, draw_top, seed: int, requests,
               length: int, control: bool = False):
    """Per request, the gap by which each served token's reference logit
    lies below the reference's best at that position, as an array.

    `requests` are (prompt, served) pairs of int arrays; the request is fed
    prompt + served[:-1], so position len(prompt)-1+j predicts served[j].
    Every sequence is padded to `length` tokens (the padding comes after
    the tokens, and attention and the recurrence are causal). With
    `control`, the gaps are instead those of the token the fp8 control
    puts first at each position, read on the float32 reference's
    logits."""
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
    _, _, vocab = _programs(cfg_key, False)
    out = []
    for i, n_mask in enumerate(masks):
        target = jnp.asarray(tgts[i])
        if control:
            _, _, ctl_vocab = _programs(cfg_key, True)
            target = ctl_vocab(top, hidden[True][i], idxs[i], target)[2]
        best, at, _ = vocab(top, hidden[False][i], idxs[i], target)
        g = np.asarray(jnp.where(n_mask, best - at, 0.0))
        out.append(g[:int(n_mask.sum())])
    return out


def _cfg_key(cfg: dict) -> str:
    import json
    return json.dumps(cfg, sort_keys=True)

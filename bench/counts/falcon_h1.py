"""Operations and bytes of one decode step of Falcon-H1 (GQA attention
and a Mamba-2 mixer in parallel in every layer, a SwiGLU FFN, untied
unembedding), from the configuration's published sizes and never from
the compiled program, so that every implementation is held to the same
work.

A slot-step is one slot advancing one position: it reads the K/V rows of
every position it attends to (its own included) and writes one row, and
it reads and writes each layer's recurrent state once: the SSM state
(heads x head_dim x d_state) and the conv tail ((d_conv - 1) x conv
channels), both float32. A step reads every weight once, however many
slots are busy in it.
"""

from __future__ import annotations

BF16 = 2        # bytes per element, weights and KV cache
F32 = 4         # bytes per element, recurrent state


def sizes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    di = cfg["mamba_d_ssm"]
    return dict(d=d, h=h, kv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // h,
                f=cfg["intermediate_size"], v=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"], di=di,
                hs=cfg["mamba_n_heads"], p=cfg["mamba_d_head"], n=n, g=g,
                w=cfg["mamba_d_conv"], conv=di + 2 * g * n)


def layer_matmul_params(cfg: dict) -> int:
    s = sizes(cfg)
    attn = s["d"] * s["hd"] * (2 * s["h"] + 2 * s["kv"])      # q, o, k, v
    ssm_in = s["d"] * (2 * s["di"] + 2 * s["g"] * s["n"] + s["hs"])
    ssm_out = s["di"] * s["d"]
    return attn + ssm_in + ssm_out + 3 * s["d"] * s["f"]     # gate, up, down


def layer_params(cfg: dict) -> int:
    """Matrices, the conv's weights and bias, dt_bias, A_log and D, the
    mixer's gated norm and the two block norms."""
    s = sizes(cfg)
    return (layer_matmul_params(cfg) + (s["w"] + 1) * s["conv"]
            + 3 * s["hs"] + s["di"] + 2 * s["d"])


def model_params(cfg: dict) -> int:
    """Every layer, the final norm, the embedding and the unembedding."""
    s = sizes(cfg)
    return s["layers"] * layer_params(cfg) + s["d"] + 2 * s["d"] * s["v"]


def step_weight_bytes(cfg: dict) -> int:
    """Weights one step reads: every layer, the final norm and the
    unembedding. The embedding is read by row (see `slot_step_bytes`)."""
    s = sizes(cfg)
    return BF16 * (s["layers"] * layer_params(cfg) + s["d"]
                   + s["d"] * s["v"])


def kv_bytes_per_position(cfg: dict) -> int:
    s = sizes(cfg)
    return BF16 * s["layers"] * 2 * s["kv"] * s["hd"]


def state_bytes_per_slot_step(cfg: dict) -> int:
    """Every layer's SSM state and conv tail, read once and written once."""
    s = sizes(cfg)
    per_layer = s["hs"] * s["p"] * s["n"] + (s["w"] - 1) * s["conv"]
    return 2 * F32 * s["layers"] * per_layer


def state_bytes_needed(cfg: dict, slot_steps: int) -> float:
    """HBM bytes of the recurrent state over `slot_steps` busy slot-steps."""
    return slot_steps * state_bytes_per_slot_step(cfg)


def slot_step_bytes(cfg: dict) -> int:
    """Per slot-step, besides its attended positions: its embedding row,
    the K/V row it writes and its recurrent state."""
    s = sizes(cfg)
    return (BF16 * s["d"] + kv_bytes_per_position(cfg)
            + state_bytes_per_slot_step(cfg))


def bytes_needed(cfg: dict, steps: int, slot_steps: int,
                 attended: int) -> float:
    """HBM bytes of `steps` steps holding `slot_steps` busy slot-steps that
    attend to `attended` positions in all (the sum over slot-steps of the
    position plus one)."""
    return (steps * step_weight_bytes(cfg) + slot_steps * slot_step_bytes(cfg)
            + attended * kv_bytes_per_position(cfg))


def flops_needed(cfg: dict, slot_steps: int, attended: int) -> float:
    """Model FLOPs: 2 per matmul parameter per slot-step, unembedding
    included, the recurrence (decay, outer product and sum, and the read
    against C: 5 per state element) per slot-step, plus QK^T and PV over
    the attended positions."""
    s = sizes(cfg)
    per_token = 2 * (s["layers"] * layer_matmul_params(cfg)
                     + s["d"] * s["v"])
    ssm = s["layers"] * 5 * s["hs"] * s["p"] * s["n"]
    attn = s["layers"] * 4 * s["h"] * s["hd"]
    return slot_steps * (per_token + ssm) + attended * attn

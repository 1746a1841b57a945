"""Operations and bytes of one decode step of a dense decoder (GQA
attention, SwiGLU FFN, untied unembedding), from the configuration's
published sizes and never from the compiled program, so that every
implementation is held to the same work.

A slot-step is one slot advancing one position: it reads the K/V rows of
every position it attends to (its own included) and writes one row. A
step reads every weight once, however many slots are busy in it.
"""

from __future__ import annotations

BF16 = 2        # bytes per element, weights and KV cache


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    return dict(d=d, h=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // cfg["num_attention_heads"],
                f=cfg["intermediate_size"], v=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"],
                qk_norm=bool(cfg["bench"].get("qk_norm", False)))


def layer_matmul_params(cfg: dict) -> int:
    s = sizes(cfg)
    attn = s["d"] * s["hd"] * (2 * s["h"] + 2 * s["kv"])      # q, o, k, v
    return attn + 3 * s["d"] * s["f"]                        # gate, up, down


def layer_params(cfg: dict) -> int:
    s = sizes(cfg)
    norms = 2 * s["d"] + (2 * s["hd"] if s["qk_norm"] else 0)
    return layer_matmul_params(cfg) + norms


def step_weight_bytes(cfg: dict) -> int:
    """Weights one step reads: every layer, the final norm and the
    unembedding. The embedding is read by row (see `slot_step_bytes`)."""
    s = sizes(cfg)
    return BF16 * (s["layers"] * layer_params(cfg) + s["d"]
                   + s["d"] * s["v"])


def kv_bytes_per_position(cfg: dict) -> int:
    s = sizes(cfg)
    return BF16 * s["layers"] * 2 * s["kv"] * s["hd"]


def slot_step_bytes(cfg: dict) -> int:
    """Per slot-step, besides its attended positions: its embedding row and
    the K/V row it writes."""
    s = sizes(cfg)
    return BF16 * s["d"] + kv_bytes_per_position(cfg)


def bytes_needed(cfg: dict, steps: int, slot_steps: int,
                 attended: int) -> float:
    """HBM bytes of `steps` steps holding `slot_steps` busy slot-steps that
    attend to `attended` positions in all (the sum over slot-steps of the
    position plus one)."""
    return (steps * step_weight_bytes(cfg) + slot_steps * slot_step_bytes(cfg)
            + attended * kv_bytes_per_position(cfg))


def flops_needed(cfg: dict, slot_steps: int, attended: int) -> float:
    """Model FLOPs: 2 per matmul parameter per slot-step, unembedding
    included, plus QK^T and PV over the attended positions."""
    s = sizes(cfg)
    per_token = 2 * (s["layers"] * layer_matmul_params(cfg)
                     + s["d"] * s["v"])
    attn = s["layers"] * 4 * s["h"] * s["hd"]
    return slot_steps * per_token + attended * attn

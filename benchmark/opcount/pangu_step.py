"""A served openPangu Ultra MoE token's operations on ONE expert-parallel
rank (models/pangu_ultra_moe.py; the published equations are in
reference/pangu_ultra_moe.py). A multiply-add is 2.

Matmuls of one token in a layer: the latent attention's W_DQ (hidden x
q_lora_rank), W_UQ (q_lora_rank x heads * 192), W_DKV (hidden x 576), W_UKV
(kv_lora_rank x heads * 256) and W_O (heads * 128 x hidden); then the dense
SwiGLU (3 matrices of hidden x intermediate_size) or the router (hidden x
the published experts), the shared expert and the routed experts THIS RANK
computes: a token's top k land here k * held / router-width times on
average (0.25 of an expert at the cell's 8 of 256), each 3 matrices of
hidden x moe_intermediate_size. Attention of a token that sees c keys:
QK^T over 192 channels and PV over 128, 2 * heads * (192 + 128) * c,
counted in the expanded form whichever form runs. The head (hidden x the
vocabulary slice) once per sampled token: every decoded token, a prompt's
last position."""

from __future__ import annotations


def router_width(cfg: dict) -> int:
    return (cfg.get("published") or {}).get("n_routed_experts",
                                           cfg["n_routed_experts"])


def attention_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rot, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    qr, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * qr + qr * h * (nope + rot) + d * (r + rot)
            + r * h * (nope + vd) + h * vd * d)


def layer_matmul_flops(cfg: dict, dense: bool) -> float:
    d = cfg["hidden_size"]
    if dense:
        ffn = 3 * d * cfg["intermediate_size"]
    else:
        f = cfg["moe_intermediate_size"]
        here = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                / router_width(cfg))
        ffn = (d * router_width(cfg) + 3 * d * f * cfg["n_shared_experts"]
               + 3 * d * f * here)
    return 2.0 * (attention_params(cfg) + ffn)


def _mats(cfg: dict) -> float:
    n, k = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return sum(layer_matmul_flops(cfg, i < k) for i in range(n))


def _score(cfg: dict) -> float:
    """Attention operations per (query, key) pair, over the layers."""
    return (2.0 * cfg["num_attention_heads"]
            * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
               + cfg["v_head_dim"]) * cfg["num_hidden_layers"])


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def token_flops(cfg: dict, context: int) -> float:
    """One decoded token whose position is `context` (it sees context + 1
    keys)."""
    return head_flops(cfg) + _mats(cfg) + _score(cfg) * (context + 1)


def prefill_flops(cfg: dict, n: int) -> float:
    """A prompt of n tokens: every position through every layer, the head
    at the last one."""
    return head_flops(cfg) + n * _mats(cfg) + _score(cfg) * n * (n + 1) / 2

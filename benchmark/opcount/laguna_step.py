"""A served Laguna token's operations, by layer kind (models/laguna.py; the
published equations are in reference/laguna.py). A multiply-add is 2.

Matmuls of one token in layer l: q, k, v, the per-head gate and the output
projection at the layer's own head count; then either the dense SwiGLU
(3 matrices of hidden x intermediate_size) or the router (hidden x
num_experts), num_experts_per_tok experts and the shared expert (3 matrices
of hidden x width each). Attention of one token that sees c keys: QK^T and
PV, 4 * heads * head_dim * c; a full layer's token at context n sees n + 1
keys, a sliding layer's min(n + 1, sliding_window). The head (hidden x
vocabulary) once per sampled token: every decoded token, and a prompt's
last position only."""

from __future__ import annotations

FULL = "full_attention"


def layers(cfg: dict):
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n], cfg["mlp_layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n]))


def layer_matmul_flops(cfg: dict, heads: int, mlp: str) -> float:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * hd
    attn = d * (heads * hd + 2 * kv + heads) + heads * hd * d
    if mlp == "dense":
        ffn = 3 * d * cfg["intermediate_size"]
    else:
        ffn = (d * cfg["num_experts"]
               + 3 * d * cfg["moe_intermediate_size"]
               * cfg["num_experts_per_tok"]
               + 3 * d * cfg["shared_expert_intermediate_size"])
    return 2.0 * (attn + ffn)


def keys_seen(cfg: dict, kind: str, position: int) -> int:
    """Keys the token at `position` (from 0) sees in a layer of `kind`."""
    seen = position + 1
    return seen if kind == FULL else min(seen, cfg["sliding_window"])


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def token_flops(cfg: dict, context: int) -> float:
    """One decoded token whose position is `context`."""
    hd = cfg["head_dim"]
    return head_flops(cfg) + sum(
        layer_matmul_flops(cfg, heads, mlp)
        + 4.0 * heads * hd * keys_seen(cfg, kind, context)
        for kind, mlp, heads in layers(cfg))


def prefill_flops(cfg: dict, n: int) -> float:
    """A prompt of n tokens: every position through every layer, the head
    at the last one."""
    hd, w = cfg["head_dim"], cfg["sliding_window"]
    full_keys = n * (n + 1) / 2.0
    m = min(n, w)              # positions that see fewer keys than a window
    window_keys = m * (m + 1) / 2.0 + (n - m) * w
    return head_flops(cfg) + sum(
        n * layer_matmul_flops(cfg, heads, mlp)
        + 4.0 * heads * hd * (full_keys if kind == FULL else window_keys)
        for kind, mlp, heads in layers(cfg))

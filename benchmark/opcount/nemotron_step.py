"""A served Nemotron-H token's operations on ONE expert-parallel rank
(models/nemotron_h.py; the published equations are in
reference/nemotron_h.py). A multiply-add is 2.

Matmuls of one token: a Mamba-2 layer's W_in (hidden x (2 heads head_dim +
2 groups state + heads)) and W_out (heads head_dim x hidden); the
attention layer's W_q, W_k, W_v and W_o; an expert layer's router (hidden
x the published experts), the latent down- and up-projections (hidden x
moe_latent_size each), the shared expert (2 x hidden x its width) and the
routed experts THIS RANK computes: a token's top k land here k * held /
router-width times on average (5.5 of 22 at the cell's 128 of 512), each 2
x latent x moe_intermediate_size. The state-space layers' own work: a
prompt token the chunked scan's (opcount/ssd.py), a decoded token one step
of the recurrence (2 heads head_dim state multiply-adds: the state's
update and y). Attention of a token that sees c keys: 2 heads head_dim c
multiply-adds (QK^T and PV). The head (hidden x the vocabulary slice) once
per sampled token: every decoded token, a prompt's last position. The
conv's 4 taps and the norms are not counted."""

from __future__ import annotations

from opcount import ssd


def router_width(cfg: dict) -> int:
    return (cfg.get("published") or {}).get("n_routed_experts",
                                           cfg["n_routed_experts"])


def _count(cfg: dict, c: str) -> int:
    return cfg["hybrid_override_pattern"].count(c)


def matmul_flops(cfg: dict) -> float:
    """One token through every layer's matmuls."""
    d, m = cfg["hidden_size"], ssd.dims(cfg)
    di = m["h"] * m["p"]
    mamba = d * (2 * di + 2 * m["g"] * m["n"] + m["h"]) + di * d
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    attn = 2 * d * qd + 2 * d * kvd
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    here = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / router_width(cfg))
    moe = (d * router_width(cfg) + 2 * d * lat
           + 2 * d * cfg["moe_shared_expert_intermediate_size"]
           + 2 * lat * f * here)
    return 2.0 * (_count(cfg, "M") * mamba + _count(cfg, "*") * attn
                  + _count(cfg, "E") * moe)


def _score(cfg: dict) -> float:
    """Attention operations per (query, key) pair, over the layers."""
    return (4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
            * _count(cfg, "*"))


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def token_flops(cfg: dict, context: int) -> float:
    """One decoded token whose position is `context` (it sees context + 1
    keys)."""
    m = ssd.dims(cfg)
    step = 4.0 * m["h"] * m["p"] * m["n"] * _count(cfg, "M")
    return head_flops(cfg) + matmul_flops(cfg) + step + _score(cfg) * (
        context + 1)


def prefill_flops(cfg: dict, n: int) -> float:
    """A prompt of n tokens: every position through every layer, the head
    at the last one."""
    scan = ssd.scan_token_cost(cfg)[0] * _count(cfg, "M")
    return (head_flops(cfg) + n * (matmul_flops(cfg) + scan)
            + _score(cfg) * n * (n + 1) / 2)

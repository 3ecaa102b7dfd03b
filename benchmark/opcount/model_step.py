"""Operations a dense GQA + SwiGLU decoder needs, from the configuration's
sizes. Convention, stated once: a multiply-add is 2 operations; causal
attention counts only the keys a query may see (so a whole sequence costs
S(S+1)/2 key visits, half the square); the output head is counted only at
positions whose logits are needed (every target in training, the last row of
a prompt and every decoded token in serving); padding, recomputation under
remat and the optimizer are not counted. Backward = 2 x forward.
"""

from __future__ import annotations


def sizes(cfg: dict) -> dict:
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return dict(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                nh=cfg["num_attention_heads"], nkv=cfg["num_key_value_heads"],
                hd=hd, v=cfg["vocab_size"], L=cfg["num_hidden_layers"])


def layer_matmul_params(cfg: dict) -> int:
    s = sizes(cfg)
    return (s["d"] * s["nh"] * s["hd"] + 2 * s["d"] * s["nkv"] * s["hd"]
            + s["nh"] * s["hd"] * s["d"] + 3 * s["d"] * s["f"])


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul (the embedding is a gather)."""
    s = sizes(cfg)
    return s["L"] * layer_matmul_params(cfg) + s["d"] * s["v"]


def token_flops(cfg: dict, keys_seen: int, head: bool = True) -> float:
    """Forward operations of one token that attends `keys_seen` keys."""
    s = sizes(cfg)
    attn = 2 * 2 * s["nh"] * s["hd"] * keys_seen      # QK^T and PV
    return (s["L"] * (2 * layer_matmul_params(cfg) + attn)
            + (2 * s["d"] * s["v"] if head else 0))


def prefill_flops(cfg: dict, n: int) -> float:
    """Forward over a prompt of n tokens; the head on its last row only."""
    s = sizes(cfg)
    attn = 2 * 2 * s["nh"] * s["hd"] * n * (n + 1) / 2
    return (s["L"] * (2 * layer_matmul_params(cfg) * n + attn)
            + 2 * s["d"] * s["v"])


def train_flops_per_row(cfg: dict, seq: int) -> float:
    """Forward + backward of one row of `seq` tokens (seq - 1 targets)."""
    s = sizes(cfg)
    attn = 2 * 2 * s["nh"] * s["hd"] * seq * (seq + 1) / 2
    fwd = (s["L"] * (2 * layer_matmul_params(cfg) * seq + attn)
           + 2 * s["d"] * s["v"] * (seq - 1))
    return 3 * fwd

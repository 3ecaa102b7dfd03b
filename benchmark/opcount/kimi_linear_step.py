"""Operations one training step of the Kimi-Linear cut needs ON THIS RANK,
from the configuration's sizes and the step's own count of routed rows. The
repo's conventions (opcount/model_step.py): a multiply-add is 2, causal
attention counts only the keys a query may see, the head is counted at the
targets, recomputation under remat, padding and the optimizer are not
counted, backward = 2 x forward. Per position and layer:

  KDA layer      Wq, Wk, Wv, Wo (d x H dk each), the decay's and the output
                 gate's low-rank pairs (d x dk + dk x H dk each), Wb (d x H),
                 the three depthwise convolutions (K taps), and the chunked
                 rule itself (opcount/kda_chunk.py, per chunk of 64)
  latent layer   Wq (d x H qk), Wkva (d x (rank + rope)), Wkvb (rank x H (nope
                 + dv)), Wo (H dv x d), and causal QK^T (qk) and PV (dv)
  dense FFN      3 d f
  expert FFN     the shared expert 3 d fe, the router d x E_all, and for every
                 ROW an expert here takes 3 d fe (the step's `moe_rows_here`)
"""

from __future__ import annotations

from opcount import kda_chunk


def sizes(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    L = cfg["num_hidden_layers"]
    return dict(
        d=cfg["hidden_size"], v=cfg["vocab_size"], L=L,
        h=lin["num_heads"], dk=lin["head_dim"],
        conv=lin["short_conv_kernel_size"],
        n_kda=sum(i in lin["kda_layers"] for i in range(1, L + 1)),
        nh=cfg["num_attention_heads"],
        qk=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
        f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        n_dense=min(cfg["first_k_dense_replace"], L),
        held=cfg["num_experts"],
        experts=cfg.get("published", {}).get("num_experts",
                                             cfg["num_experts"]))


def kda_layer_macs(s: dict) -> float:
    """Multiply-adds per position of a KDA layer's projections and convs."""
    d, hd, dk = s["d"], s["h"] * s["dk"], s["dk"]
    return (4 * d * hd + 2 * (d * dk + dk * hd) + d * s["h"]
            + 3 * s["conv"] * hd)


def latent_layer_macs(s: dict) -> float:
    return (s["d"] * s["nh"] * s["qk"] + s["d"] * (s["rank"] + s["rope"])
            + s["rank"] * s["nh"] * (s["nope"] + s["dv"])
            + s["nh"] * s["dv"] * s["d"])


def train_flops_per_step(cfg: dict, batch: int, seq: int,
                         routed_rows: float) -> float:
    """Forward + backward of one step of `batch` rows of `seq` positions;
    `routed_rows` is the step's count of rows the experts held here took,
    over all expert layers."""
    s = sizes(cfg)
    tokens = batch * seq
    n_latent = s["L"] - s["n_kda"]
    n_moe = s["L"] - s["n_dense"]
    per_token = (s["n_kda"] * kda_layer_macs(s)
                 + n_latent * latent_layer_macs(s)
                 + s["n_dense"] * 3 * s["d"] * s["f"]
                 + n_moe * (3 * s["d"] * s["fe"] + s["d"] * s["experts"]))
    kda_ops, _ = kda_chunk.forward_cost(batch * s["h"], seq, s["dk"], s["dk"])
    attn = 2.0 * batch * s["nh"] * (s["qk"] + s["dv"]) * seq * (seq + 1) / 2
    fwd = (2.0 * tokens * per_token + s["n_kda"] * kda_ops + n_latent * attn
           + 2.0 * routed_rows * 3 * s["d"] * s["fe"]
           + 2.0 * s["d"] * s["v"] * batch * (seq - 1))
    return 3 * fwd

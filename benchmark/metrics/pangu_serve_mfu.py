"""Model step: operations of every prompt whose first token came inside
the window and of every output token delivered inside it
(opcount/pangu_step.py: the rank's share of the routed experts, every
head of the latent attention counted expanded), over the window times the
chip's bf16 peak; in percent."""

from opcount import pangu_step


def read(run):
    cfg, w = run["config"], run["window"]
    if cfg.get("family") != "pangu_ultra_moe":
        return None
    lo, hi = w["t_open"], w["t_open"] + w["seconds"]
    flops = 0.0
    for r in run["requests"]:
        n = len(r["prompt"])
        for j, t in enumerate(r["token_at"]):
            if not lo <= t <= hi:
                continue
            flops += (pangu_step.prefill_flops(cfg, n) if j == 0
                      else pangu_step.token_flops(cfg, n + j - 1))
    if not flops:
        return None
    return 100.0 * flops / (w["seconds"] * run["peaks"]["bf16_flops_per_s"])

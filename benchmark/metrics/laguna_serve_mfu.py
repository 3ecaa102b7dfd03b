"""Model step: operations of every prompt whose first token came inside
the window and of every output token delivered inside it
(opcount/laguna_step.py: attention by context and window, the chosen
experts and the shared one), over the window times the chip's bf16 peak; in
percent. Decode reads most of the experts for a few rows each, so this is
small by nature; it bounds any claim whatever implements the kernels."""

from opcount import laguna_step


def read(run):
    cfg, w = run["config"], run["window"]
    if cfg.get("family") != "laguna":
        return None
    lo, hi = w["t_open"], w["t_open"] + w["seconds"]
    flops = 0.0
    for r in run["requests"]:
        n = len(r["prompt"])
        for j, t in enumerate(r["token_at"]):
            if not lo <= t <= hi:
                continue
            flops += (laguna_step.prefill_flops(cfg, n) if j == 0
                      else laguna_step.token_flops(cfg, n + j - 1))
    if not flops:
        return None
    return 100.0 * flops / (w["seconds"] * run["peaks"]["bf16_flops_per_s"])

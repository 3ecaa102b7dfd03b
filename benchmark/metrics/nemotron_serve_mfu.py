"""Model step: operations of every prompt whose first token came inside
the window and of every output token delivered inside it
(opcount/nemotron_step.py: the rank's share of the routed experts, the
state-space layers' scans and steps, the attention over its context), over
the window times the chip's bf16 peak; in percent."""

from opcount import nemotron_step


def read(run):
    cfg, w = run["config"], run["window"]
    if cfg.get("family") != "nemotron_h":
        return None
    lo, hi = w["t_open"], w["t_open"] + w["seconds"]
    flops = 0.0
    for r in run["requests"]:
        n = len(r["prompt"])
        for j, t in enumerate(r["token_at"]):
            if not lo <= t <= hi:
                continue
            flops += (nemotron_step.prefill_flops(cfg, n) if j == 0
                      else nemotron_step.token_flops(cfg, n + j - 1))
    if not flops:
        return None
    return 100.0 * flops / (w["seconds"] * run["peaks"]["bf16_flops_per_s"])

"""Model step: forward + backward operations this rank's steps of the window
need (opcount/kimi_linear_step.py: the routed rows from each step's own
`moe_rows_here`, causal attention halved, recomputation under remat not
counted) over the window's whole time and the chip's bf16 peak; in percent."""

from opcount import kimi_linear_step


def read(run):
    mix, w = run["traffic"], run["window"]
    steps = [r for r in run["steps"] if "moe_rows_here" in r]
    if not steps or not w["span_s"]:
        return None
    flops = sum(kimi_linear_step.train_flops_per_step(
        run["config"], mix["batch_size"], mix["seq_len"], r["moe_rows_here"])
        for r in steps)
    return 100.0 * flops / (w["span_s"] * run["chips"]
                            * run["peaks"]["bf16_flops_per_s"])

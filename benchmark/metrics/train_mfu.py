"""Model step: forward + backward operations of the window's steps
(opcount/model_step.py: causal attention halved, recomputation under remat
not counted) over the window's whole time (drivers/jaxjob_window.py),
the chips and the chip's bf16 peak; in
percent."""

from opcount import model_step


def read(run):
    mix, w = run["traffic"], run["window"]
    if not run["steps"] or not w["span_s"]:
        return None
    flops = (len(run["steps"]) * mix["batch_size"]
             * model_step.train_flops_per_row(run["config"], mix["seq_len"]))
    return 100.0 * flops / (w["span_s"] * run["chips"]
                            * run["peaks"]["bf16_flops_per_s"])

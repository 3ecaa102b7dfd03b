"""Kernels: the routed experts' grouped matmuls' (megablox gmm and tgmm)
share of their roofline over the traced window, in percent. Operations and
bytes from the rows the steps counted (opcount/grouped_matmul.py; the median
`moe_rows_here` of the window's steps), the steps traced counted by the
weight-gradient kernel's calls (three a layer and step), the time that of
every grouped-matmul call, the forward's second run under remat included."""

import statistics

from opcount import grouped_matmul as gm
from opcount import kimi_linear_step


def read(run):
    trace = run.get("trace")
    rows = [r["moe_rows_here"] for r in run["steps"] if "moe_rows_here" in r]
    took = tgmm_calls = 0.0
    for name, seconds, calls in (trace or {}).get("ops", []):
        back = gm.TGMM.match(name)
        if not (back or gm.GMM.match(name)):
            continue
        took += seconds
        if back:
            tgmm_calls += calls
    if not (took and tgmm_calls and rows):
        return None
    s = kimi_linear_step.sizes(run["config"])
    layers = s["L"] - s["n_dense"]
    steps = tgmm_calls / (3 * layers)
    ops, nbytes = gm.step_cost(statistics.median(rows), layers, s["held"],
                               s["d"], s["fe"])
    least = steps * max(ops / run["peaks"]["bf16_flops_per_s"],
                        nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / took

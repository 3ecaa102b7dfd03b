"""Expert routing: the step's own counter (the most-loaded expert held here
over the mean of the experts held, worst expert layer), median over the
window's steps. 1 is balance; the buffer's first slice holds 8."""

import statistics


def read(run):
    loads = [r["moe_expert_load_max_over_mean"] for r in run["steps"]
             if "moe_expert_load_max_over_mean" in r]
    return statistics.median(loads) if loads else None

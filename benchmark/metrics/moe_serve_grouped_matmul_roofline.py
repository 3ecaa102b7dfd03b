"""Kernels: the served experts' grouped matmuls' share of their roofline
over the traced window, in percent. Per call, the larger of operations over
the bf16 peak and bytes over the HBM rate (opcount/moe_serve.py); the rows
are in the call's name; the experts a DECODE call touched are the engine's
own count (`moe_expert_visits` over the layer-steps counted, between the
first and the last request that finished inside the traced window), a prefill
chunk's are what its rows touch spread evenly (all of them). Over the
device time of every such call."""

from metrics._moe_serve import decode_rows, touched_per_decode_call
from opcount import moe_serve


def read(run):
    trace = run.get("trace")
    touched = trace and touched_per_decode_call(run, traced=True)
    if not touched:
        return None
    cfg, peaks = run["config"], run["peaks"]
    took = least = 0.0
    for name, seconds, calls in trace.get("ops", []):
        shape = moe_serve.call(name)
        if shape is None:
            continue
        rows, k, n = shape
        seen = (touched if rows <= decode_rows(cfg)
                else moe_serve.touched_uniform(rows, cfg["num_experts"]))
        ops, nbytes = moe_serve.call_cost(rows, seen, k, n)
        took += seconds
        least += calls * max(ops / peaks["bf16_flops_per_s"],
                             nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took if took and least else None

"""Kernels: flash decode's share of its roofline over the traced window,
in percent. The kernel carries no name of its own yet (the compiler calls
every such Pallas call `closed_call.N`), so it is told by its operands: a
vector of lengths, a bf16 query, int8 K and V slabs and their f32 scales.
Least time: for every token delivered in the traced window after a
request's first, what its context needs in each layer
(opcount/flash_decode.py), bytes over the HBM rate or operations over the
bf16 peak, whichever is larger; over the kernel's device time."""

import re

from opcount import flash_decode

KERNEL = re.compile(r"^closed_call\.\d+\(s32\[\d+\],bf16\[[\d,]+\],"
                    r"s8\[[\d,]+\],s8\[[\d,]+\],f32\[[\d,]+\],f32\[[\d,]+\]\)")


def read(run):
    trace = run.get("trace")
    took = sum(s for name, s, _ in (trace or {}).get("ops", [])
               if KERNEL.match(name))
    if not took:
        return None
    cfg, peaks = run["config"], run["peaks"]
    lo = run["window"]["t_open"]
    hi = lo + trace["window_s"]
    least = 0.0
    for r in run["requests"]:
        n = len(r["prompt"])
        for j, t in enumerate(r["token_at"]):
            if j and lo <= t <= hi:
                ops, nbytes = flash_decode.cost(cfg, n + j)
                least += cfg["num_hidden_layers"] * max(
                    ops / peaks["bf16_flops_per_s"],
                    nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took if least else None

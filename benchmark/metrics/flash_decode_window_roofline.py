"""Kernels: flash decode ON THE SLIDING LAYERS' RING, its share of its
roofline over the traced window, in percent. The kernel carries no name of
its own (the compiler calls every such Pallas call `closed_call.N`), so it
is told by its operands, as `flash_decode_roofline` tells the slab's: a
vector of lengths, a bf16 query, int8 K and V and their f32 scales, here
with the RING's rows a slot (`kv_window_ring_tokens`, the engine's count)
where the full layers' slab has max_len. Least time: for every token
delivered in the traced window after a request's first, the keys a window
holds at its context (opcount/flash_decode.py at min(context,
sliding_window)), in each sliding layer, bytes over the HBM rate or
operations over the bf16 peak, whichever is larger; over the kernel's
device time."""

import re

from opcount import flash_decode

KERNEL = re.compile(r"^[\w.\-]+\(s32\[\d+\],bf16\[[\d,]+\],"
                    r"s8\[\d+,\d+,(\d+),\d+,\d+\],s8\[[\d,]+\],"
                    r"f32\[[\d,]+\],f32\[[\d,]+\]\)")


def read(run):
    trace = run.get("trace")
    ring = ((run.get("counters") or {}).get("after") or {}).get(
        "kv_window_ring_tokens")
    if not trace or not ring:
        return None
    took = 0.0
    for name, seconds, _ in trace.get("ops", []):
        m = KERNEL.match(name)
        if m and int(m.group(1)) == ring:
            took += seconds
    if not took:
        return None
    cfg, peaks = run["config"], run["peaks"]
    n = cfg["num_hidden_layers"]
    sliding = [h for h, t in zip(cfg["num_attention_heads_per_layer"][:n],
                                 cfg["layer_types"][:n])
               if t == "sliding_attention"]
    lo = run["window"]["t_open"]
    hi = lo + trace["window_s"]
    least = 0.0
    for r in run["requests"]:
        plen = len(r["prompt"])
        for j, t in enumerate(r["token_at"]):
            if j and lo <= t <= hi:
                keys = min(plen + j, cfg["sliding_window"])
                for heads in sliding:
                    ops, nbytes = flash_decode.cost(
                        dict(cfg, num_attention_heads=heads), keys)
                    least += max(ops / peaks["bf16_flops_per_s"],
                                 nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took if least else None

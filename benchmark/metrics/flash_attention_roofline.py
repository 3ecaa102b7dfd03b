"""Kernels: the training attention kernels' (forward, backward dK/dV,
backward dQ) share of their roofline over the traced window, in percent.
One layer-step needs 7 matmul units (opcount/flash_attention.py); the
number of layer-steps is the count of the dK/dV kernel's calls; the time is
that of all three kernels, the forward's second run under remat included."""

from opcount import flash_attention as fa


def read(run):
    trace = run.get("trace")
    took = least = 0.0
    for name, seconds, calls in (trace or {}).get("ops", []):
        if not (fa.FORWARD.match(name) or fa.BACKWARD_KV.match(name)
                or fa.BACKWARD_Q.match(name)):
            continue
        took += seconds
        kv = fa.BACKWARD_KV.match(name)
        if kv:
            heads, s, hd = map(int, kv.groups()[:3])
            ops, nbytes = fa.layer_cost(heads, s, hd)
            least += calls * max(ops / run["peaks"]["bf16_flops_per_s"],
                                 nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / took if took and least else None

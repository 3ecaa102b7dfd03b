"""Kernels: latent attention's three flash kernels' (forward, backward dK/dV,
backward dQ; ops/flash_pallas.py with q/k heads padded from 192 to 256 lanes
beside values of 128) share of their roofline over the traced window, in
percent. Operations and bytes at the PUBLISHED head sizes
(opcount/mla_attention.py), the layer-steps counted by the dK/dV kernel's
calls, the time that of all three kernels, the forward's second run under
remat included."""

from opcount import mla_attention as ma


def read(run):
    trace, cfg = run.get("trace"), run["config"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    took = least = 0.0
    for name, seconds, calls in (trace or {}).get("ops", []):
        if not ma.kernel(name):
            continue
        took += seconds
        kv = ma.BACKWARD_KV.match(name)
        if kv:
            heads, s = int(kv.groups()[0]), int(kv.groups()[1])
            ops, nbytes = ma.layer_cost(heads, s, qk, int(kv.groups()[8]))
            least += calls * max(ops / run["peaks"]["bf16_flops_per_s"],
                                 nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / took if took and least else None

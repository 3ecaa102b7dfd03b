"""Kernels: the absorbed latent decode kernel's (ops/mla_decode.py) share
of its roofline over the traced window, in percent. The kernel carries no
name of its own, so it is told by its operands (opcount/mla_serve.py): a
query as wide as the slab's latent rows, an output narrower. Least time per
call: max(operations / bf16 peak, bytes / HBM rate) at the call's mean
live rows, the rows the engine's `mla_context_tokens` counted over the
traced seconds (metrics/_mla_serve.py) shared over the traced calls, a
row's 576 values (the slab pads it to 640 lanes: not counted); over the
kernel's device time."""

from metrics._mla_serve import traced_rows
from opcount import mla_serve


def read(run):
    trace = run.get("trace")
    rows = traced_rows(run)
    if not trace or not rows:
        return None
    took, calls, shape = 0.0, 0, None
    for name, seconds, n in trace.get("ops", []):
        got = mla_serve.decode_call(name)
        if got:
            took, calls, shape = took + seconds, calls + n, got
    if not took or not calls:
        return None
    slots, heads, c, latent = shape
    cfg = run["config"]
    if "kv_lora_rank" in cfg:    # the row's values, not its padding lanes
        c = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    ops, nbytes = mla_serve.decode_cost(heads, c, latent, rows / calls,
                                        slots)
    peaks = run["peaks"]
    least = calls * max(ops / peaks["bf16_flops_per_s"],
                        nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took

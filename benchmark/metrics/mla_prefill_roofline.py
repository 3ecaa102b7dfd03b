"""Kernels: the served latent attention's prefill kernel (ops/flash_pallas.py's
forward, q/k of 192 padded to 256 lanes beside values of 128, at the
chunk's offset behind its cached prefix) share of its roofline over the
traced window, in percent: per call max(operations / bf16 peak, bytes /
HBM rate) at the published head sizes, counting only the keys a causal
query sees (opcount/mla_serve.py), over the kernel's device time."""

from opcount import mla_serve


def read(run):
    trace, cfg = run.get("trace"), run["config"]
    if not trace or "kv_lora_rank" not in cfg:
        return None
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    took = least = 0.0
    peaks = run["peaks"]
    for name, seconds, calls in trace.get("ops", []):
        got = mla_serve.prefill_call(name)
        if not got:
            continue
        ops, nbytes = mla_serve.prefill_cost(*got, qk=qk,
                                             dv=cfg["v_head_dim"])
        took += seconds
        least += calls * max(ops / peaks["bf16_flops_per_s"],
                             nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took if took and least else None

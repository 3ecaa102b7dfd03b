"""Kernels: the state-space layers' chunked-scan prefill kernel
(ops/ssd.py, `ssd_chunk_scan`) share of its roofline over the traced window,
in percent. Least time: max(operations / bf16 peak, bytes / HBM rate) of
the scan for the REAL prompt tokens the engine's `ssm_scan_tokens` counted
(tokens times state-space layers) over the traced part of the window
(metrics/_mla_serve.py's interpolation), per token as opcount/ssd.py counts
it; over the kernel's device time. The bucket's pad, which the kernel also
walks, is not counted, so a kernel that skips it reads truer."""

from metrics._mla_serve import traced_rows
from opcount import ssd


def read(run):
    trace, cfg = run.get("trace"), run["config"]
    if not trace or "ssm_state_size" not in cfg:
        return None
    tokens = traced_rows(run, "ssm_scan_tokens")
    took = sum(s for name, s, _ in trace.get("ops", [])
               if ssd.SCAN.match(name))
    if not tokens or not took:
        return None
    ops, nbytes = ssd.scan_token_cost(cfg)
    peaks = run["peaks"]
    least = tokens * max(ops / peaks["bf16_flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took

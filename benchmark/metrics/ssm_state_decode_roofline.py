"""Kernels: the state-space layers' decode-step kernel (ops/ssd.py,
`ssm_state_update`, the state slab updated in place) share of its
roofline over the traced window, in percent. Least bytes: the LIVE slots'
states read and written once, with their x, B, C, dt and y
(opcount/ssd.py), for the slot-layer updates the engine's
`ssm_state_rows` counted over the traced part of the window
(metrics/_mla_serve.py's interpolation); least time max(operations / bf16
peak, bytes / HBM rate); over the kernel's device time. Dead slots, which
the kernel also updates, are not counted."""

from metrics._mla_serve import traced_rows
from opcount import ssd


def read(run):
    trace, cfg = run.get("trace"), run["config"]
    if not trace or "ssm_state_size" not in cfg:
        return None
    rows = traced_rows(run, "ssm_state_rows")
    took, itemsize = 0.0, None
    for name, seconds, _ in trace.get("ops", []):
        got = ssd.step_call(name)
        if got:
            took, itemsize = took + seconds, got
    if not rows or not took:
        return None
    ops, nbytes = ssd.step_row_cost(cfg, itemsize)
    peaks = run["peaks"]
    least = rows * max(ops / peaks["bf16_flops_per_s"],
                       nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took

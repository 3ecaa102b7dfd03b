"""Kernels: the device time under the family's own mechanisms' scopes (the
router, the routed experts, the shared expert: `moe_*`; the sliding layers'
attention: `attn_window`) over the device's busy time in the traced window,
in percent (lib/xscopes.py; the driver keeps the split under
`trace["scopes"]`): do the new mechanisms do the work in this cell, or do
the plain matmuls and the head?"""


def read(run):
    trace = run.get("trace") or {}
    scopes = trace.get("scopes")
    if not scopes or not trace.get("busy_s"):
        return None
    took = sum(s for name, s in scopes.items()
               if name.startswith("moe_") or name == "attn_window")
    return 100.0 * took / trace["busy_s"] if took else None

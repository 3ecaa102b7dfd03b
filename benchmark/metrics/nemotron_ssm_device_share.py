"""Kernels: the device time under the state-space layers' scopes
(`ssm_proj`, `ssm_conv`, `ssm_scan`, `ssm_state`) over the device's busy
time in the traced window, in percent (lib/xscopes.py; the driver keeps the
split under `trace["scopes"]`): does the new mechanism do the work in this
cell, or do the experts and the head?"""


def read(run):
    trace = run.get("trace") or {}
    scopes = trace.get("scopes")
    if not scopes or not trace.get("busy_s"):
        return None
    took = sum(s for name, s in scopes.items() if name.startswith("ssm_"))
    return 100.0 * took / trace["busy_s"] if took else None

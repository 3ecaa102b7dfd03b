"""Kernels: the device's time in operations whose innermost named scope is
`kda_solve` (ops/kda.py's UT transform: every chunk's unit lower triangle
inverted by halves in float32, XLA), wherever it runs: forward, again under
remat, and differentiated inside the backward; over the device's busy time
in the traced window, in percent: what a solve inside the kernel would take
off the step. Read from the operations' `op_name` in the capture
(lib/xscopes.py; the driver keeps it under `trace["scopes"]`)."""


def read(run):
    trace = run.get("trace") or {}
    took = (trace.get("scopes") or {}).get("kda_solve")
    if not took or not trace.get("busy_s"):
        return None
    return 100.0 * took / trace["busy_s"]

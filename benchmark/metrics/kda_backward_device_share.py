"""Kernels: the device's time in operations whose innermost named scope is
`kda_backward` (ops/kda.py's backward, chunked jax.numpy under `custom_vjp`:
the reverse walk over the chunks and stages 1-2 differentiated by JAX; the
solve inside it counts under `kda_solve_device_share`), over the device's
busy time in the traced window, in percent: what a backward kernel would
take off the step. Read from the operations' `op_name` in the capture
(lib/xscopes.py; the driver keeps it under `trace["scopes"]`)."""


def read(run):
    trace = run.get("trace") or {}
    took = (trace.get("scopes") or {}).get("kda_backward")
    if not took or not trace.get("busy_s"):
        return None
    return 100.0 * took / trace["busy_s"]

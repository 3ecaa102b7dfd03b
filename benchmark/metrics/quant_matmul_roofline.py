"""Kernels: the int8 matmul's share of its roofline over the traced
window, in percent. Each call's least time is the larger of its operations
over the bf16 peak and its bytes over the HBM rate, both from the shapes
the call ran with (opcount/quant_matmul.py, read off the kernel's name in
the trace); the share is the sum of those over the kernel's device time.
At 16 decode rows every call is bound by bytes."""

from opcount import quant_matmul


def read(run):
    trace = run.get("trace")
    least = took = 0.0
    for name, seconds, calls in (trace or {}).get("ops", []):
        if not name.startswith("_dequant_matmul"):
            continue
        c = quant_matmul.cost_of(name)
        if c is None:
            continue
        least += calls * quant_matmul.least_seconds(*c, run["peaks"])
        took += seconds
    return 100.0 * least / took if took else None

"""Kernels: the share of its roofline of the int8 matmul that reads its
layer in place from the stacked weights (`_dequant_matmul_stacked` in
ops/quant_matmul.py: every layer matmul of a decode step), over the traced
window, in percent. The kernel is told by the name the compiler gives the
custom call and M, K, N are read off its operands,
    _dequant_matmul_stacked.79(s32[1],bf16[16,4096],s8[8,4096,1024],f32[8,1,1024])->bf16[16,1024]
(the layer's index, x[M, K], the stack W[L, K, N], the scales[L, 1, N]).
One call reads ONE layer: its bytes are K x N, never L x K x N, so a call's
least time is opcount/quant_matmul.py's for that layer's matmul: the larger
of its operations over the bf16 peak and its bytes over the HBM rate. At 16
decode rows every call is bound by bytes. None where no such call ran (a
program without the kernel, as before the PR that added it)."""

import re

from opcount import quant_matmul

SIG = re.compile(
    r"^_dequant_matmul_stacked[\w.\-]*\(s32\[1\],(\w+)\[(\d+),(\d+)\],"
    r"s8\[\d+,(\d+),(\d+)\],f32\[\d+,1,(\d+)\]\)->(\w+)\[(\d+),(\d+)\]")


def cost_of(signature: str):
    """(operations, bytes) of one call, one layer's; None if the name is
    not this kernel's or its shapes do not agree."""
    hit = SIG.match(signature)
    if not hit:
        return None
    xt, m, k, k2, n, n2, ot, m2, n3 = hit.groups()
    m, k, k2, n, n2, m2, n3 = map(int, (m, k, k2, n, n2, m2, n3))
    if (k, n, m) != (k2, n2, m2) or n != n3:
        return None
    return quant_matmul.cost(m, k, n, quant_matmul.WIDTH[xt],
                             quant_matmul.WIDTH[ot])


def read(run):
    trace = run.get("trace")
    least = took = 0.0
    for name, seconds, calls in (trace or {}).get("ops", []):
        c = cost_of(name)
        if c is None:
            continue
        least += calls * quant_matmul.least_seconds(*c, run["peaks"])
        took += seconds
    return 100.0 * least / took if took else None

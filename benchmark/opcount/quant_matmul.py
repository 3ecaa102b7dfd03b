"""Weight-only int8 matmul (ops/quant_matmul.py): x[M, K] bf16 times
W[K, N] int8 with one f32 scale per output channel. Operations 2MKN;
bytes: x once, W once at one byte an element, the scales, the result once.
The compute roof is the chip's bf16 peak: the weights are dequantised to
bf16 and the MXU multiplies in bf16 (the activations are not int8)."""

from __future__ import annotations

import re

SIG = re.compile(r"\((\w+)\[(\d+),(\d+)\],s8\[(\d+),(\d+)\],f32\[1,(\d+)\]\)"
                 r"->(\w+)\[(\d+),(\d+)\]")
WIDTH = {"bf16": 2, "f32": 4, "f16": 2}


def cost(m: int, k: int, n: int, x_bytes: int = 2, out_bytes: int = 2):
    return 2.0 * m * k * n, float(x_bytes * m * k + k * n + 4 * n
                                  + out_bytes * m * n)


def cost_of(signature: str):
    """(operations, bytes) from a kernel's name with shapes, as
    lib/tracered.short_name writes it; None if it is not this kernel."""
    hit = SIG.search(signature)
    if not hit:
        return None
    xt, m, k, k2, n, n2, ot, m2, n3 = hit.groups()
    m, k, k2, n, n2, m2, n3 = map(int, (m, k, k2, n, n2, m2, n3))
    if (k, n, m) != (k2, n2, m2) or n != n3:
        return None
    return cost(m, k, n, WIDTH[xt], WIDTH[ot])


def least_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])

"""Training attention (ops/flash_pallas.py), causal, over heads of [S, hd]:
one matmul "unit" is 2 * S(S+1)/2 * hd operations for one head (only the
keys a query may see). The forward needs 2 units (QK^T, PV); the backward 5
(dV, dP, dS -> dQ, dK, and QK^T once more, which no backward can avoid
without keeping the S x S scores). The program splits the backward into two
kernels that each recompute QK^T and dP, and under full remat runs the
forward twice: neither repeat is counted. Bytes: q, k, v, o, do and the
three gradients once, in bf16; attention at S = 2048 is bound by compute."""

from __future__ import annotations

import re

#: the kernels as the trace names them (lib/tracered.short_name): Pallas
#: calls under shard_map, told by operands of bf16[heads, S, hd]
HEADS = r"bf16\[(\d+),(\d+),(\d+)\]"
FORWARD = re.compile(rf"^shard_map\.\d+\(s32\[1\],{HEADS},{HEADS},{HEADS}\)->")
BACKWARD_KV = re.compile(rf"^shard_map\.\d+\({HEADS},{HEADS},{HEADS},{HEADS},"
                         rf"f32\[[\d,]+\],f32\[[\d,]+\]\)->{HEADS},{HEADS}$")
BACKWARD_Q = re.compile(rf"^shard_map\.\d+\({HEADS},{HEADS},{HEADS},{HEADS},"
                        rf"f32\[[\d,]+\],f32\[[\d,]+\]\)->{HEADS}$")


def unit(heads: int, s: int, hd: int) -> float:
    return 2.0 * heads * hd * s * (s + 1) / 2


def layer_cost(heads: int, s: int, hd: int):
    """(operations, bytes) one layer's forward and backward need."""
    return 7 * unit(heads, s, hd), float(8 * heads * s * hd * 2)

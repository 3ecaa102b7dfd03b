"""The routed experts' grouped matmuls (megablox `gmm` / `tgmm`, called by
ops/moe.py::moe_share_mlp): a row that an expert here takes goes through
three projections of K x N = hidden x expert width; forward 3 matmuls,
backward 6 (the gradient of the rows and of the weights), a multiply-add 2.
The rows are the step's own count (`moe_rows_here`), not the buffer's size:
the buffer is sized for eight times the balanced load and the kernel skips
the tiles that hold no row. Bytes of one step: every projection's weights of
every expert held once forward and twice backward (read, and the gradient
written) in bfloat16, and each row's input and output per matmul."""

from __future__ import annotations

import re

_META = r"(?:s32\[\d*\],)+"
GMM = re.compile(rf"^[\w.\-]+\({_META}bf16\[(\d+),(\d+)\],"
                 r"bf16\[(\d+),(\d+),(\d+)\]\)->bf16\[(\d+),(\d+)\]$")
TGMM = re.compile(rf"^[\w.\-]+\({_META}bf16\[(\d+),(\d+)\],"
                  r"bf16\[(\d+),(\d+)\]\)->bf16\[(\d+),(\d+),(\d+)\]$")


def step_cost(rows: float, layers: int, held: int, d: int, f: int):
    """(operations, bytes) of one step's routed experts: `rows` summed over
    the `layers` expert layers."""
    ops = 9 * 2.0 * rows * d * f
    weights = layers * held * 3 * d * f * 2
    return ops, float(3 * weights + 9 * rows * (d + f) * 2)

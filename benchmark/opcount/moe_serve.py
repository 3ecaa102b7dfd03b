"""A grouped-matmul call of the served expert layer (megablox `gmm`, called
by ops/moe.py::moe_share_mlp with every expert held): `rows` assignments,
sorted by expert, against the experts' K x N matrices, read out of the
stack of every layer's experts `[layers * experts, K, N]` in place.
Operations: 2 * rows * K * N. Bytes: the matrix of every expert that takes
a row, ONCE a call (the kernel visits an expert's rows tile by tile with its
matrix resident; an expert no row chose is not visited), in bfloat16, and
each row's input and output.

The kernel is told by its operands (the compiler calls it `closed_call.N`):
scalar-prefetched group metadata, the bf16 rows and the bf16 stack. `rows`
is in its name, which tells a decode step's call (slots x experts per
token) from a prefill chunk's."""

from __future__ import annotations

import re

_META = r"(?:s32\[\d*\],)+"
GMM = re.compile(rf"^[\w.\-]+\({_META}bf16\[(\d+),(\d+)\],"
                 r"bf16\[(\d+),(\d+),(\d+)\]\)->bf16\[(\d+),(\d+)\]$")


def call(name: str):
    """(rows, K, N) of a grouped-matmul call's name, or None."""
    m = GMM.match(name)
    if not m:
        return None
    rows, k, _, k2, n, _, n2 = (int(g) for g in m.groups())
    return (rows, k, n) if (k, n) == (k2, n2) else None


def touched_uniform(rows: float, experts: int) -> float:
    """Experts that take at least one of `rows` assignments spread evenly:
    what a call's bytes are reckoned from where no counter saw the call (a
    prefill chunk's thousands of rows touch every expert)."""
    return experts * (1.0 - (1.0 - 1.0 / experts) ** rows)


def call_cost(rows: float, touched: float, k: int, n: int):
    """(operations, bytes) of one call."""
    return (2.0 * rows * k * n,
            float(touched * k * n * 2 + rows * (k + n) * 2))

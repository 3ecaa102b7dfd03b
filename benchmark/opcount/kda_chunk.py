"""The chunked gated delta rule (ops/kda.py), forward, for one head and one
chunk of C positions with key and value heads of dk and dv: what the
ALGORITHM needs, a multiply-add counted 2, triangles counted as triangles
(the kernels compute whole 64 x 64 blocks and mask them; that is not counted):

    A and B            2 x C(C+1)/2 x dk
    the UT transform   C^3 / 3                 (inverse of a unit lower triangle)
    M (K exp G), M V   C(C+1)/2 x (dk + dv)
    W S, (Q exp G) S   2 x C x dk x dv
    B U                C(C+1)/2 x dv
    (K exp(G_C - G))^T U   C x dk x dv

Bytes, forward: q, k, v and o once in bfloat16, the log-decay once in
float32, per position. The backward runs as XLA operations that the trace
cannot tell from the rest of the step, so the roofline share is the FORWARD
kernels': their least time over the time of every run of them (each layer
runs them twice a step under full remat, counted once: the benchmark's
convention).
"""

from __future__ import annotations

import re

CHUNK = 64

#: the kernels as the trace names them (lib/tracered.short_name), told by
#: their operands whatever the compiler calls them
_H = r"bf16\[(\d+),(\d+),(\d+)\]"
_G = r"f32\[(\d+),(\d+),(\d+)\]"
_SQ = r"f32\[\d+,\d+,64,64\]"
INTRA = re.compile(rf"^[\w.\-]+\({_H},{_H},{_G}\)->{_SQ},{_SQ}$")
#: the chunk walk; a differentiated step's run also writes the state at every
#: chunk's start (`custom_vjp`'s forward rule), a second result
STATE = re.compile(rf"^[\w.\-]+\({_H},{_H},{_H},{_G},{_SQ},{_SQ}\)->{_H}"
                   r"(?:,f32\[[\d,]+\])?$")


def chunk_flops(dk: int, dv: int, c: int = CHUNK) -> float:
    tri = c * (c + 1) / 2
    macs = (2 * tri * dk + c ** 3 / 3 + tri * (dk + dv) + 2 * c * dk * dv
            + tri * dv + c * dk * dv)
    return 2.0 * macs


def forward_cost(heads: int, s: int, dk: int, dv: int):
    """(operations, bytes) of one forward over `heads` (batch x heads)
    sequences of s positions."""
    chunks = heads * -(-s // CHUNK)
    return (chunks * chunk_flops(dk, dv),
            float(heads * s * (2 * (2 * dk + 2 * dv) + 4 * dk)))

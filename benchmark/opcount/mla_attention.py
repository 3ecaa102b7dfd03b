"""Latent attention's causal softmax (ops/flash_pallas.py with a q/k head
size of qk and a value head size of dv), over heads of S positions: one
"unit" is 2 * S(S+1)/2 operations per channel for one head (only the keys a
query may see). The forward needs QK^T (qk channels) and PV (dv); the
backward dV and dP (dv each), dQ, dK and QK^T once more (qk each): 4 qk + 3 dv
channel-units, as opcount/flash_attention.py counts 7 of one size. Counted at
the PUBLISHED sizes (192 and 128): the kernel pads q and k to 256 lanes, and
the padding is not counted. Bytes: q, k, dq, dk (qk) and v, o, do, dv (dv)
once, in bfloat16."""

from __future__ import annotations

import re

_H = r"bf16\[(\d+),(\d+),(\d+)\]"
_F = r"f32\[[\d,]+\]"
FORWARD = re.compile(rf"^[\w.\-]+\(s32\[1\],{_H},{_H},{_H}\)->{_H},{_F}$")
BACKWARD_KV = re.compile(rf"^[\w.\-]+\({_H},{_H},{_H},{_H},{_F},{_F}\)->"
                         rf"{_H},{_H}$")
BACKWARD_Q = re.compile(rf"^[\w.\-]+\({_H},{_H},{_H},{_H},{_F},{_F}\)->{_H}$")


def kernel(name: str):
    """The match of one of the three kernels, if `name` is latent
    attention's: q/k heads of another size than the values'. (Heads of one
    size are another model's attention: opcount/flash_attention.py.)"""
    m = (BACKWARD_KV.match(name) or BACKWARD_Q.match(name)
         or FORWARD.match(name))
    return m if m and m.groups()[2] != m.groups()[8] else None


def layer_cost(heads: int, s: int, qk: int, dv: int):
    """(operations, bytes) one layer's forward and backward need."""
    unit = 2.0 * heads * s * (s + 1) / 2
    return unit * (4 * qk + 3 * dv), float(heads * s * 4 * (qk + dv) * 2)

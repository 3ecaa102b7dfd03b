"""The served latent attention's two kernels, told by their operands.

DECODE (ops/mla_decode.py): `(s32[meta], q bf16[B, H, C], slab bf16[L,
slots, T, C]) -> bf16[B, H, latent]`, C = latent + rope: each head scores
every live row over C channels and sums the rows' first `latent` channels,
2 * H * (C + latent) operations a row; the bytes are the rows, C values
each, plus the query and the output.

PREFILL (ops/flash_pallas.py's forward with q/k heads padded from 192 to
256 lanes beside values of 128): `(s32[1], q bf16[BH, Sq, 256], k bf16[BH,
Sk, 256], v bf16[BH, Sk, 128]) -> bf16[BH, Sq', 128], f32[...]`; the
queries sit at positions Sk - Sq .. Sk - 1 (a chunk after its cached
prefix), and query i sees Sk - Sq + i + 1 keys. Operations and bytes at the
PUBLISHED head sizes (qk 192, v 128): the padding is not counted."""

from __future__ import annotations

import re

_B = r"bf16\[(\d+),(\d+),(\d+)\]"
DECODE = re.compile(rf"^[\w.\-]+\(s32\[\d+\],{_B},"
                    r"bf16\[\d+,\d+,(\d+),(\d+)\]\)->" + _B + "$")
PREFILL = re.compile(rf"^[\w.\-]+\(s32\[1\],{_B},{_B},{_B}\)->{_B},"
                     r"f32\[[\d,]+\]$")


def decode_call(name: str):
    """(slots, heads, C, latent) of a latent decode call, else None: the
    query is as wide as the slab's rows, the output narrower."""
    m = DECODE.match(name)
    if not m:
        return None
    b, h, c, _, width, ob, oh, latent = map(int, m.groups())
    if width != c or latent >= c or (ob, oh) != (b, h):
        return None
    return b, h, c, latent


def decode_cost(heads: int, c: int, latent: int, rows: float, slots: int):
    """(operations, bytes) of one call over `rows` live context rows."""
    ops = 2.0 * heads * (c + latent) * rows
    nbytes = 2.0 * (c * rows + slots * heads * (c + latent))
    return ops, nbytes


def prefill_call(name: str):
    """(BH, Sq, Sk) of a forward whose q/k heads are 256 wide and values
    128 (latent attention's, padded), else None."""
    m = PREFILL.match(name)
    if not m:
        return None
    g = list(map(int, m.groups()))
    bh, sq, dq, bk, sk, dk, bv, sv, dv = g[:9]
    if not (dq == dk == 256 and dv == 128 and bh == bk == bv and sk == sv
            and sq <= sk):
        return None
    return bh, sq, sk


def prefill_cost(bh: int, sq: int, sk: int, qk: int = 192, dv: int = 128):
    """(operations, bytes) of one call: the keys a causal query sees, at
    the published head sizes."""
    p = sk - sq
    pairs = sq * p + sq * (sq + 1) / 2.0
    ops = 2.0 * bh * pairs * (qk + dv)
    nbytes = 2.0 * bh * (sq * (qk + dv) + sk * (qk + dv))
    return ops, nbytes

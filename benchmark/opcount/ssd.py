"""The served state-space layer's two kernels (ops/ssd.py), told by their
names, and the least work of what they compute: the chunked scan of a
prompt, and one step of the recurrence for a decode step. A multiply-add
is 2. H heads of P channels, a state of N, G groups of B and C, chunks of
Q positions.

SCAN, per REAL prompt token and layer (the bucket's pad is not counted):
C B^T over the positions of its chunk that it sees, G (Q + 1) / 2 N
multiply-adds (a triangle counted as a triangle); the masked decay products
times x, H (Q + 1) / 2 P; the state's part of y, H P N; its part of the
state, H P N. Bytes: x read and y written in bfloat16 (2 H P each), B and C
read (2 G N each), dt in float32 (4 H). The state a prompt reads and
writes once (H P N float32 each) is not counted: the counter counts tokens,
not prompts, so the share reads low by that much, never high.

STEP, per LIVE slot and layer: the state read and written once (H P N at
the slab's itemsize each), x and B, C in bfloat16, dt in float32, y written
in float32 (4 H P); operations 5 H P N (the decay, the input's outer
product, and y's multiply-add)."""

from __future__ import annotations

import re

#: the kernels' names in a trace (lib/tracered.short_name): the name the
#: Pallas call gives them, a number, then the shapes
SCAN = re.compile(r"^ssd_chunk_scan(?:\.\d+)?\(")
STEP = re.compile(r"^ssm_state_update(?:\.\d+)?\(.*,(bf16|f32)\[(\d+),(\d+),"
                  r"(\d+),(\d+),(\d+)\]\)->")


def dims(cfg: dict) -> dict:
    return {"h": cfg["mamba_num_heads"], "p": cfg["mamba_head_dim"],
            "n": cfg["ssm_state_size"], "g": cfg["n_groups"],
            "q": cfg["chunk_size"]}


def scan_token_cost(cfg: dict):
    """(operations, bytes) of one real prompt token through one layer's
    scan."""
    m = dims(cfg)
    h, p, n, g, q = m["h"], m["p"], m["n"], m["g"], m["q"]
    macs = g * (q + 1) / 2 * n + h * (q + 1) / 2 * p + 2 * h * p * n
    nbytes = 2 * (2 * h * p) + 2 * (2 * g * n) + 4 * h
    return 2.0 * macs, float(nbytes)


def step_row_cost(cfg: dict, state_itemsize: int):
    """(operations, bytes) of one live slot's step through one layer."""
    m = dims(cfg)
    h, p, n, g = m["h"], m["p"], m["n"], m["g"]
    nbytes = (2 * state_itemsize * h * p * n + 2 * h * p + 2 * 2 * g * n
              + 4 * h + 4 * h * p)
    return 5.0 * h * p * n, float(nbytes)


def step_call(name: str):
    """The state slab's itemsize of a decode-step call, else None."""
    m = STEP.match(name)
    if not m:
        return None
    return 4 if m.group(1) == "f32" else 2

"""Flash decode (ops/flash_decode.py): one query row per slot against that
slot's cached keys and values, int8 with an f32 scale per token and KV
head. What one decoded token at context c needs in one layer: QK^T and PV,
4 * heads * head_dim * c operations; bytes: K and V of c tokens at one byte
an element, their scales, and the query and output rows in bf16. Only the
keys the token may see count: the kernel is handed the whole slab of
16 x span and skips what lies beyond a slot's length."""

from __future__ import annotations


def cost(cfg: dict, context: int):
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // nh
    ops = 4.0 * nh * hd * context
    nbytes = float(2 * context * nkv * hd + 2 * context * nkv * 4
                   + 2 * nh * hd * 2)
    return ops, nbytes

"""Attention ops — XLA reference implementation.

This is the numerically-golden path every optimized kernel (Pallas flash
attention, ring attention) is tested against. The reference platform ships no
attention code at all (SURVEY.md §5.7 — sequence handling is user-code);
here the compute layer is first-class.

Layout convention: [batch, seq, heads, head_dim] ("BSHD") throughout, which
shards naturally as (batch->data/fsdp, seq->sequence, heads->tensor).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """Expand KV heads for grouped-query attention: [B,S,Hkv,D] -> [B,S,Hkv*n,D]."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    segment_ids: jax.Array | None = None,
    q_offset: int | jax.Array = 0,
    window: int | None = None,
) -> jax.Array:
    """Multi-head attention, BSHD layout, fp32 softmax accumulation.

    q: [B, Sq, H, D]; k/v: [B, Sk, Hkv, D] (GQA expanded automatically).
    `q_offset` positions the query block within the kv sequence for causal
    masking — used by decode (Sq=1 at position t) and ring attention shards.
    `window` (causal only): key j is visible to query i iff
    0 <= i - j < window — sliding-window attention.
    """
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    if hkv != h:
        k = repeat_kv(k, h // hkv)
        v = repeat_kv(v, h // hkv)
    scale = scale if scale is not None else 1.0 / (d**0.5)

    # [B,H,Sq,Sk] logits in fp32 for numerical stability on bf16 inputs
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits *= scale

    mask = None
    if causal:
        sk = k.shape[1]
        q_pos = jnp.arange(sq)[:, None] + q_offset
        k_pos = jnp.arange(sk)[None, :]
        mask = q_pos >= k_pos  # [Sq, Sk]
        if window is not None:
            mask = mask & (q_pos - k_pos < window)
        mask = mask[None, None, :, :]
    if segment_ids is not None:
        if segment_ids.shape[1] != sq or k.shape[1] != sq:
            raise ValueError("segment_ids require Sq == Sk (self-attention)")
        seg = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        mask = seg if mask is None else (mask & seg)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)

    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

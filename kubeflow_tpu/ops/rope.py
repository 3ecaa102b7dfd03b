"""Rotary position embeddings (RoPE), offset-aware for SP/decoding.

Offsets matter twice in this framework: (a) decode-time KV-cache positions,
(b) sequence-parallel shards where each device holds positions
[shard*chunk, (shard+1)*chunk) — SURVEY.md §5.7 calls out per-shard RoPE
offsets as a correctness hazard of ring attention.

Two published variants ride the same functions (both off by default, and
the defaults compute exactly what they always did): PARTIAL rotary
(`rotary_dim` < head_dim: the first `rotary_dim` dims of a head rotate,
half-split among themselves, the rest pass through) and YaRN frequencies
(`yarn`: each frequency a blend of itself and itself / factor, and cos and
sin scaled by the attention factor).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Yarn(NamedTuple):
    """A published `rope_type: yarn` description (HF `rope_utils`'
    `_compute_yarn_parameters`, its keys under their names)."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None    # None: 0.1 ln(factor) + 1

    @property
    def cos_sin_scale(self) -> float:
        if self.attention_factor is not None:
            return float(self.attention_factor)
        return 0.1 * math.log(self.factor) + 1.0 if self.factor > 1 else 1.0


def rope_frequencies(head_dim: int, theta: float = 10000.0, *,
                     rotary_dim: int | None = None,
                     yarn: Yarn | None = None) -> jax.Array:
    """Inverse frequencies of the rotated pairs, [rotary_dim // 2]."""
    dim = head_dim if rotary_dim is None else rotary_dim
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    freqs = 1.0 / (theta**exponent)  # [dim//2]
    if yarn is None:
        return freqs

    def pair_turning(turns: float) -> float:
        # the (fractional) index of the pair that turns `turns` times over
        # the original positions
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(yarn.beta_fast)), 0)
    high = min(math.ceil(pair_turning(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001   # the published guard against a zero-width ramp
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    # below the ramp a pair keeps its frequency, above it the frequency is
    # divided by the factor, between them the two are blended
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    *,
    theta: float = 10000.0,
    rotary_dim: int | None = None,
    yarn: Yarn | None = None,
) -> jax.Array:
    """Apply RoPE to [B, S, H, D] given integer positions [B, S] or [S]."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta, rotary_dim=rotary_dim, yarn=yarn)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [B,S,1,D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    if yarn is not None:
        cos, sin = cos * yarn.cos_sin_scale, sin * yarn.cos_sin_scale
    rd = d if rotary_dim is None else rotary_dim
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf[..., :rd] if rd != d else xf, 2, axis=-1)
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if rd != d:
        parts.append(xf[..., rd:])
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)

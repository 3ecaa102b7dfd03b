"""Mixtral-style MoE transformer: llama attention blocks + top-k-routed
SwiGLU experts, expert-parallel over the mesh `expert` axis.

The reference platform orchestrates MoE only as opaque user containers
(SURVEY.md §2.2: expert parallelism "user code only"); here it is a
first-class model family. All expert weights are stacked [L, E, ...] so the
layer scan and the expert sharding compose; GSPMD turns the dispatch einsums
into the expert all-to-all (see ops/moe.py).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_tpu.models import llama
from kubeflow_tpu.ops.moe import MoEArgs, moe_mlp
from kubeflow_tpu.ops.norms import rms_norm

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoELlamaConfig(llama.LlamaConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    router_z_coef: float = 1e-3

    @property
    def moe_args(self) -> MoEArgs:
        return MoEArgs(self.n_experts, self.top_k, self.capacity_factor,
                       self.aux_loss_coef, self.router_z_coef)

    @staticmethod
    def mixtral_8x7b() -> "MoELlamaConfig":
        return MoELlamaConfig(vocab_size=32000, d_model=4096, n_layers=32,
                              n_heads=32, n_kv_heads=8, d_ff=14336,
                              max_seq_len=32768, rope_theta=1e6,
                              n_experts=8, top_k=2)

    @staticmethod
    def tiny(vocab_size: int = 512) -> "MoELlamaConfig":
        return MoELlamaConfig(vocab_size=vocab_size, d_model=64, n_layers=2,
                              n_heads=8, n_kv_heads=4, d_ff=96,
                              max_seq_len=128, rope_theta=10000.0,
                              n_experts=4, top_k=2)


def init(rng: jax.Array, cfg: MoELlamaConfig) -> Params:
    params = llama.init(rng, cfg)
    pd = cfg.param_dtype
    d, f, L, E = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.n_experts
    keys = jax.random.split(jax.random.fold_in(rng, 101), 4)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / (fan_in ** 0.5)).astype(pd)

    layers = params["layers"]
    for name in ("w_gate", "w_up", "w_down"):
        del layers[name]
    layers["router"] = dense(keys[0], (L, d, E), d)
    layers["w_gate"] = dense(keys[1], (L, E, d, f), d)
    layers["w_up"] = dense(keys[2], (L, E, d, f), d)
    layers["w_down"] = dense(keys[3], (L, E, f, d), f)
    return params


def logical_axes(cfg: MoELlamaConfig) -> Params:
    axes = llama.logical_axes(cfg)
    axes["layers"]["router"] = ("layers", "embed", None)
    axes["layers"]["w_gate"] = ("layers", "expert", "embed", "mlp")
    axes["layers"]["w_up"] = ("layers", "expert", "embed", "mlp")
    axes["layers"]["w_down"] = ("layers", "expert", "mlp", "embed")
    return axes


def _layer_body(cfg: MoELlamaConfig, carry, layer, positions, segment_ids):
    x, aux = carry
    x = llama._attention(cfg, x, layer, positions, segment_ids)
    h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    out, layer_aux = moe_mlp(h, layer["router"], layer["w_gate"],
                             layer["w_up"], layer["w_down"], cfg.moe_args,
                             dtype=cfg.dtype)
    return (x + out, aux + layer_aux), None


def apply(
    params: Params,
    tokens: jax.Array,
    cfg: MoELlamaConfig,
    *,
    positions: jax.Array | None = None,
    segment_ids: jax.Array | None = None,
    return_aux: bool = False,
):
    """[B, S] int tokens -> [B, S, vocab] fp32 logits (+ router aux loss)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.arange(s)
    x = params["embed"].astype(cfg.dtype)[tokens]

    body = partial(_layer_body, cfg, positions=positions,
                   segment_ids=segment_ids)
    if cfg.remat:
        policy = {
            "minimal": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            "full": jax.checkpoint_policies.nothing_saveable,
            "none": jax.checkpoint_policies.everything_saveable,
        }[cfg.remat_policy]
        body = jax.checkpoint(body, policy=policy)
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])
    else:
        carry = (x, jnp.zeros((), jnp.float32))
        for i in range(cfg.n_layers):
            layer = jax.tree.map(lambda p: p[i], params["layers"])
            carry, _ = body(carry, layer)
        x, aux = carry

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    return (logits, aux) if return_aux else logits


def loss_fn(params: Params, batch: dict[str, jax.Array], cfg: MoELlamaConfig):
    """Next-token cross-entropy + router load-balance aux loss."""
    tokens = batch["tokens"]
    logits, aux = apply(params, tokens, cfg,
                        positions=jnp.arange(tokens.shape[1]),
                        segment_ids=batch.get("segment_ids"),
                        return_aux=True)
    logits = logits[:, :-1]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    token_loss = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    mask = jnp.ones_like(token_loss) if mask is None else mask[:, 1:]
    total = jnp.sum(token_loss * mask)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    ce = total / denom
    return ce + aux, {"loss": ce, "aux_loss": aux, "tokens": jnp.sum(mask)}


"""Kimi-Linear-style hybrid decoder, trained (not served).

The first family here whose layers differ in kind, so there is no stacked
layer tree and no `lax.scan` over layers: `params["layers"]` is a dict of
per-layer dicts ("00", "01", ...) and the layers run as a Python loop, each
under its own `jax.checkpoint`.

  - attention is KDA (ops/kda.py: a gated delta rule with a per-channel
    decay, conv4 + SiLU on q/k/v, l2-normalised q and k, an output gate) in
    the layers `linear_attn_config.kda_layers` names, and latent attention
    without rotary (`mla_use_nope`: q/k heads of 128 + 64 beside values of
    128, through ops/flash_attention.py) in `full_attn_layers`;
  - the FFN is a dense SwiGLU in the first `first_k_dense_replace` layers and
    elsewhere one shared expert plus sigmoid-routed experts
    (ops/moe.py::moe_share_mlp: top-k over all `n_router_experts`, no token
    dropped, the experts [first_expert, first_expert + n_experts_held)
    computed here: one expert-parallel rank's part of the sum, before the
    exchange; with every expert held it is the whole layer).

The configuration's field names are the published `config.json`'s, so a job
passes those keys through. Parameters float32, compute bfloat16; the KDA
gate's sums, its state and the l2 norms are float32. `init` makes the draw
benchmark/reference/kimi_linear.py states (normal / sqrt(fan_in), one key a
leaf), bit for bit.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_tpu.ops.flash_attention import flash_attention
from kubeflow_tpu.ops.attention import mha
from kubeflow_tpu.ops.kda import chunk_kda
from kubeflow_tpu.ops.moe import ShareArgs, moe_share_mlp
from kubeflow_tpu.ops.norms import rms_norm

Params = dict[str, Any]

#: every leaf a layer can have; a leaf's index keys its draw
LEAVES = ("attn_norm", "mlp_norm",
          "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "f_a", "f_b",
          "A_log", "dt_bias", "wb", "g_a", "g_b", "o_norm", "wo",
          "wkva", "kv_norm", "wkvb",
          "w_gate", "w_up", "w_down",
          "router", "router_bias", "e_gate", "e_up", "e_down",
          "s_gate", "s_up", "s_down")


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    num_hidden_layers: int = 27
    # {"kda_layers": [...], "full_attn_layers": [...]} (layers from 1),
    # "num_heads", "head_dim", "short_conv_kernel_size"
    linear_attn_config: Any = None
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64      # carried without rotary (mla_use_nope)
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    first_k_dense_replace: int = 1
    num_experts_per_token: int = 8
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    rms_norm_eps: float = 1e-5
    # the share: the router's width, and which experts' weights are here
    n_router_experts: int = 256
    n_experts_held: int = 256
    first_expert: int = 0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "flash"   # flash | xla
    remat: bool = True              # each layer recomputed in the backward
    ce_chunk: int = 0               # >0: cross-entropy per chunk of positions

    def __post_init__(self):
        lin = dict(self.linear_attn_config or {
            "kda_layers": [i for i in range(1, self.num_hidden_layers + 1)
                           if i % 4],
            "full_attn_layers": [i for i in range(
                1, self.num_hidden_layers + 1) if i % 4 == 0],
            "num_heads": 32, "head_dim": 128, "short_conv_kernel_size": 4})
        kinds = sorted(lin["kda_layers"] + lin["full_attn_layers"])
        if kinds != list(range(1, self.num_hidden_layers + 1)):
            raise ValueError(
                "kda_layers and full_attn_layers must name every layer from "
                f"1 to {self.num_hidden_layers} once; got {kinds}")
        object.__setattr__(self, "linear_attn_config", lin)
        if self.attention_impl not in ("flash", "xla"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r}")
        if self.first_expert + self.n_experts_held > self.n_router_experts:
            raise ValueError("the experts held lie outside the router's")

    def is_kda(self, i: int) -> bool:
        return (i + 1) in self.linear_attn_config["kda_layers"]

    def is_dense(self, i: int) -> bool:
        return i < self.first_k_dense_replace

    @property
    def share(self) -> ShareArgs:
        return ShareArgs(self.n_router_experts, self.num_experts_per_token,
                         self.n_experts_held, self.first_expert,
                         self.routed_scaling_factor, self.moe_renormalize)

    @staticmethod
    def tiny(vocab_size: int = 512) -> "KimiLinearConfig":
        """Test size: the cut's five layer kinds (KDA dense, KDA, KDA,
        latent, KDA with experts), toy widths."""
        return KimiLinearConfig(
            vocab_size=vocab_size, hidden_size=64, num_hidden_layers=5,
            linear_attn_config={"kda_layers": [1, 2, 3, 5],
                                "full_attn_layers": [4], "num_heads": 2,
                                "head_dim": 16, "short_conv_kernel_size": 4},
            num_attention_heads=2, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=32, intermediate_size=128,
            moe_intermediate_size=32, num_experts_per_token=2,
            n_router_experts=8, n_experts_held=4)


def layer_shapes(cfg: KimiLinearConfig, i: int) -> dict:
    """{leaf: (shape, fan_in or the name of a rule)} of layer i (from 0)."""
    lin = cfg.linear_attn_config
    d, h, dk, kc = (cfg.hidden_size, lin["num_heads"], lin["head_dim"],
                    lin["short_conv_kernel_size"])
    out = {"attn_norm": ((d,), "one"), "mlp_norm": ((d,), "one")}
    if cfg.is_kda(i):
        out.update({
            "wq": ((d, h * dk), d), "wk": ((d, h * dk), d),
            "wv": ((d, h * dk), d),
            "conv_q": ((kc, h * dk), kc), "conv_k": ((kc, h * dk), kc),
            "conv_v": ((kc, h * dk), kc),
            "f_a": ((d, dk), d), "f_b": ((dk, h * dk), dk),
            "A_log": ((h,), "a_log"), "dt_bias": ((h * dk,), "dt_bias"),
            "wb": ((d, h), d),
            "g_a": ((d, dk), d), "g_b": ((dk, h * dk), dk),
            "o_norm": ((dk,), "one"), "wo": ((h * dk, d), h * dk)})
    else:
        nh = cfg.num_attention_heads
        qk, vd, r = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)
        out.update({
            "wq": ((d, nh * qk), d),
            "wkva": ((d, r + cfg.qk_rope_head_dim), d),
            "kv_norm": ((r,), "one"),
            "wkvb": ((r, nh * (cfg.qk_nope_head_dim + vd)), r),
            "wo": ((nh * vd, d), nh * vd)})
    if cfg.is_dense(i):
        f = cfg.intermediate_size
        out.update({"w_gate": ((d, f), d), "w_up": ((d, f), d),
                    "w_down": ((f, d), f)})
    else:
        e, f = cfg.n_experts_held, cfg.moe_intermediate_size
        out.update({
            "router": ((d, cfg.n_router_experts), d),
            "router_bias": ((cfg.n_router_experts,), "zero"),
            "e_gate": ((e, d, f), d), "e_up": ((e, d, f), d),
            "e_down": ((e, f, d), f),
            "s_gate": ((d, f), d), "s_up": ((d, f), d),
            "s_down": ((f, d), f)})
    return out


def _draw(key, shape, rule, dtype):
    if rule == "one":
        return jnp.ones(shape, dtype)
    if rule == "zero":
        return jnp.zeros(shape, dtype)
    if rule == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                       ).astype(dtype)
    if rule == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return (jax.random.normal(key, shape, jnp.float32) / (rule ** 0.5)
            ).astype(dtype)


def init(rng: jax.Array, cfg: KimiLinearConfig) -> Params:
    pd = cfg.param_dtype
    keys = jax.random.split(rng, cfg.num_hidden_layers + 1)
    layers = {}
    for i in range(cfg.num_hidden_layers):
        layers[f"{i:02d}"] = {
            name: _draw(jax.random.fold_in(keys[i + 1], LEAVES.index(name)),
                        shape, rule, pd)
            for name, (shape, rule) in layer_shapes(cfg, i).items()}
    d, v = cfg.hidden_size, cfg.vocab_size
    return {"embed": _draw(keys[0], (v, d), d, pd),
            "layers": layers,
            "final_norm": jnp.ones((d,), pd),
            "lm_head": _draw(jax.random.fold_in(keys[0], 1), (d, v), d, pd)}


_AXES = {
    "attn_norm": ("embed_no_fsdp",), "mlp_norm": ("embed_no_fsdp",),
    "wq": ("embed", "qkv"), "wk": ("embed", "qkv"), "wv": ("embed", "qkv"),
    "conv_q": (None, "qkv"), "conv_k": (None, "qkv"), "conv_v": (None, "qkv"),
    "f_a": ("embed", None), "f_b": (None, "qkv"),
    "A_log": (None,), "dt_bias": ("qkv",), "wb": ("embed", None),
    "g_a": ("embed", None), "g_b": (None, "qkv"), "o_norm": (None,),
    "wo": ("qkv", "embed"),
    "wkva": ("embed", None), "kv_norm": (None,), "wkvb": (None, "qkv"),
    "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
    "w_down": ("mlp", "embed"),
    "router": ("embed", None), "router_bias": (None,),
    "e_gate": ("expert", "embed", "mlp"), "e_up": ("expert", "embed", "mlp"),
    "e_down": ("expert", "mlp", "embed"),
    "s_gate": ("embed", "mlp"), "s_up": ("embed", "mlp"),
    "s_down": ("mlp", "embed"),
}


def logical_axes(cfg: KimiLinearConfig) -> Params:
    return {"embed": ("vocab", "embed"),
            "layers": {f"{i:02d}": {name: _AXES[name]
                                    for name in layer_shapes(cfg, i)}
                       for i in range(cfg.num_hidden_layers)},
            "final_norm": ("embed_no_fsdp",),
            "lm_head": ("embed", "vocab")}


# -- the block ------------------------------------------------------------------

def _mm(x, w, dtype, out=None):
    return jnp.matmul(x.astype(dtype), w.astype(dtype),
                      preferred_element_type=out or dtype)


def _conv_silu(x, w):
    """Causal depthwise convolution over positions, then SiLU, in float32:
    x [B, S, C], w [K, C]; tap K - 1 is the current position."""
    k = w.shape[0]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return jax.nn.silu(sum(xf[:, j:j + x.shape[1]] * w[j] for j in range(k)))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda_attention(cfg: KimiLinearConfig, n, w):
    lin = cfg.linear_attn_config
    b, s, _ = n.shape
    h, dk, dt = lin["num_heads"], lin["head_dim"], cfg.dtype
    heads = lambda x: x.reshape(b, s, h, dk)
    q, k, v = (heads(_conv_silu(_mm(n, w["w" + a], dt), w["conv_" + a]))
               for a in "qkv")
    q, k = _l2norm(q) * dk ** -0.5, _l2norm(k)
    f32 = jnp.float32
    pre = _mm(_mm(n, w["f_a"], dt, f32), w["f_b"], dt, f32)
    g = -jnp.exp(w["A_log"].astype(f32))[None, None, :, None] * heads(
        jax.nn.softplus(pre + w["dt_bias"].astype(f32)))
    beta = jax.nn.sigmoid(_mm(n, w["wb"], dt, f32))
    o = chunk_kda(q.astype(dt), k.astype(dt), v.astype(dt), g, beta,
                  mm_dtype=dt)
    gate = jax.nn.sigmoid(_mm(_mm(n, w["g_a"], dt, f32), w["g_b"], dt, f32))
    o = rms_norm(o, w["o_norm"], cfg.rms_norm_eps).astype(f32) * heads(gate)
    return _mm(o.reshape(b, s, h * dk), w["wo"], dt)


def _latent_attention(cfg: KimiLinearConfig, n, w):
    b, s, _ = n.shape
    nh, nope, rope, vd, r = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim,
                             cfg.kv_lora_rank)
    dt = cfg.dtype
    q = _mm(n, w["wq"], dt).reshape(b, s, nh, nope + rope)
    ckr = _mm(n, w["wkva"], dt)
    kv = _mm(rms_norm(ckr[..., :r], w["kv_norm"], cfg.rms_norm_eps),
             w["wkvb"], dt).reshape(b, s, nh, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(ckr[:, :, None, r:], (b, s, nh, rope))], axis=-1)
    v = kv[..., nope:]
    scale = 1.0 / math.sqrt(nope + rope)
    if cfg.attention_impl == "flash":
        o = flash_attention(q, k, v, causal=True, scale=scale)
    else:
        o = mha(q, k, v, causal=True, scale=scale)
    return _mm(o.reshape(b, s, nh * vd), w["wo"], dt)


def _swiglu(n, gate, up, down, dt):
    return _mm(jax.nn.silu(_mm(n, gate, dt)) * _mm(n, up, dt), down, dt)


def _layer(cfg: KimiLinearConfig, i: int, x, w):
    """x [B, S, d] -> (x, the routed experts' counters or None)."""
    n = rms_norm(x, w["attn_norm"], cfg.rms_norm_eps)
    if cfg.is_kda(i):
        with jax.named_scope("kda"):
            x = x + _kda_attention(cfg, n, w)
    else:
        with jax.named_scope("mla"):
            x = x + _latent_attention(cfg, n, w)
    n = rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
    if cfg.is_dense(i):
        return x + _swiglu(n, w["w_gate"], w["w_up"], w["w_down"],
                           cfg.dtype), None
    with jax.named_scope("shared_expert"):
        out = _swiglu(n, w["s_gate"], w["s_up"], w["s_down"], cfg.dtype)
    routed, counters = moe_share_mlp(
        n, w["router"], w["router_bias"], w["e_gate"], w["e_up"],
        w["e_down"], cfg.share, dtype=cfg.dtype)
    return x + out + routed, counters


def apply_hidden(params: Params, tokens: jax.Array, cfg: KimiLinearConfig):
    """[B, S] tokens -> (final-normed [B, S, d], the step's counters)."""
    x = params["embed"].astype(cfg.dtype)[tokens]
    seen = []
    for i in range(cfg.num_hidden_layers):
        body = lambda x, w, i=i: _layer(cfg, i, x, w)
        if cfg.remat:
            body = jax.checkpoint(body)
        x, counters = body(x, params["layers"][f"{i:02d}"])
        if counters is not None:
            seen.append(counters)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    zero = jnp.zeros((), jnp.float32)
    col = lambda k: jnp.stack([c[k] for c in seen]) if seen else zero[None]
    return x, {
        # summed over the expert layers
        "moe_rows_here": jnp.sum(col("rows_here")),
        "moe_rows_dropped": jnp.sum(col("rows_dropped")),
        # the worst expert layer's
        "moe_expert_load_max_over_mean": jnp.max(col("load_max_over_mean")),
        "router_top1_share_max": jnp.max(col("top1_share_max")),
    }


def apply(params: Params, tokens: jax.Array, cfg: KimiLinearConfig):
    """[B, S] int tokens -> [B, S, vocab] float32 logits."""
    x, _ = apply_hidden(params, tokens, cfg)
    return _mm(x, params["lm_head"], cfg.dtype, jnp.float32)


def loss_fn(params: Params, batch: dict[str, jax.Array],
            cfg: KimiLinearConfig):
    """Next-token cross-entropy alone (the configuration states no auxiliary
    loss), with the routed experts' counters among the metrics."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    h, counters = apply_hidden(params, tokens, cfg)
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    valid = jnp.ones((b, s), jnp.float32).at[:, -1].set(0.0)
    mask = batch.get("loss_mask")
    if mask is not None:
        valid = valid * jnp.concatenate(
            [mask[:, 1:].astype(jnp.float32),
             jnp.zeros((b, 1), jnp.float32)], axis=1)
    c = cfg.ce_chunk or s
    if s % c:
        raise ValueError(f"seq_len {s} must divide by ce_chunk {c}")
    chunks = lambda x: jnp.moveaxis(
        x.reshape(b, s // c, c, *x.shape[2:]), 1, 0)

    @jax.checkpoint
    def chunk(total, inp):
        hc, tc, vc = inp
        logits = _mm(hc, params["lm_head"], cfg.dtype, jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return total + jnp.sum((lse - picked) * vc), None

    total, _ = jax.lax.scan(chunk, jnp.float32(0.0),
                            (chunks(h), chunks(targets), chunks(valid)))
    denom = jnp.maximum(jnp.sum(valid), 1.0)
    loss = total / denom
    return loss, {"loss": loss, "tokens": denom, **counters}

"""Llama-family transformer, TPU-first.

The flagship model for the BASELINE.json contract (Llama-3-8B JAXJob on
v5e-16 at >=40% MFU). The reference platform never implements a model — it
launches Megatron/DeepSpeed containers (SURVEY.md §2.2, L7); here the model is
part of the framework, designed around XLA/Pallas:

  - pure-functional param pytrees (no framework Module state) + logical-axis
    trees so any (data, fsdp, tensor, sequence) mesh layout is a rule change;
  - all L layers stacked on a leading axis and executed with ``lax.scan``
    (one compiled layer body — O(1) compile time in depth); shallow models
    can set ``scan_layers=False`` to unroll instead, trading O(L) compile
    for the removal of the scan's residual-stacking copies;
  - bf16 activations/weights with fp32 softmax/norm statistics;
  - GQA (n_kv_heads < n_heads), RoPE with explicit position offsets so
    sequence-parallel shards and KV-cache decode share one code path;
  - attention is pluggable: "xla" reference einsum, "flash" Pallas kernel,
    "ring" sequence-parallel ring attention.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.ops import quant
from kubeflow_tpu.ops.attention import mha, repeat_kv
from kubeflow_tpu.ops.norms import rms_norm
from kubeflow_tpu.ops.rope import apply_rope
from kubeflow_tpu.parallel import overlap

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "flash"  # flash | xla | ring | ulysses (flash
    # auto-selects the Pallas TPU kernel, blockwise-XLA off-TPU; ring/ulysses
    # are the sequence-parallel paths — shard_map islands over the ambient
    # mesh's `sequence` axis, §5.7)
    remat: bool = True
    # remat policy: "none" | "minimal" (checkpoint_dots) | "full"
    remat_policy: str = "minimal"
    # microbatches for the GPipe schedule when the mesh has a `stage` axis;
    # 0 = one microbatch per stage (minimum that fills the pipe)
    pipeline_microbatches: int = 0
    # True: layers run under lax.scan (compact HLO, fast compile — the
    # right call for deep models). False: python-loop unroll; for shallow
    # models this removes the scan's residual-stacking dynamic-update-slice
    # traffic (profiled at ~20% of the train step at L8/d2048: +3 MFU pts)
    scan_layers: bool = True
    # >0: sequence-chunked cross-entropy — lm_head + log-softmax run per
    # ce_chunk tokens under jax.checkpoint so the full [B, S, vocab] f32
    # logits never materialize (the seq-32k single-chip memory wall);
    # 0 = whole-sequence CE (faster at short seq, same numbers)
    ce_chunk: int = 0
    # serving DECODE/verify attention over the KV cache slab (ISSUE 15):
    # "xla" reference einsum, "flash" the fused Pallas flash-decode
    # kernel (ops/flash_decode.py — online softmax over KV blocks, int8
    # dequant fused at the block load, GQA regrouped in-kernel), "auto"
    # the selection policy (flash where it compiles — a TPU target and a
    # head_dim the kernel tiles — xla elsewhere:
    # ops/pallas_compat.resolve_flash_impl). Orthogonal to attention_impl,
    # which governs the TRAINING/prefill full-sequence attention.
    decode_attention_impl: str = "auto"
    # serving PREFILL chunk attention (ISSUE 20): "xla" the reference
    # mha einsum, "flash" the fused Pallas chunked-prefill kernel
    # (ops/flash_prefill.py — online softmax over KV blocks, q_offset
    # causal masking, int8 dequant fused at the block load), "auto" the
    # selection policy (the decode rule:
    # ops/pallas_compat.resolve_flash_impl). Governs the serving
    # prefill_inner/prefill_continue_inner bodies — TRAINING attention
    # stays on attention_impl.
    prefill_attention_impl: str = "auto"

    def __post_init__(self):
        if self.attention_impl not in ("xla", "flash", "ring", "ulysses"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r}")
        if self.decode_attention_impl not in ("auto", "xla", "flash"):
            raise ValueError("unknown decode_attention_impl "
                             f"{self.decode_attention_impl!r}")
        if self.prefill_attention_impl not in ("auto", "xla", "flash"):
            raise ValueError("unknown prefill_attention_impl "
                             f"{self.prefill_attention_impl!r}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, d_model=4096, n_layers=32,
                           n_heads=32, n_kv_heads=8, d_ff=14336,
                           rope_theta=500000.0)

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LlamaConfig":
        """Test-size config: real structure, toy dims (multiple-of-8 friendly)."""
        return LlamaConfig(vocab_size=vocab_size, d_model=64, n_layers=2,
                           n_heads=8, n_kv_heads=4, d_ff=128, max_seq_len=128,
                           rope_theta=10000.0)


def init(rng: jax.Array, cfg: LlamaConfig) -> Params:
    """Initialize stacked-layer params: every per-layer tensor has leading
    axis n_layers (the lax.scan carry axis)."""
    keys = jax.random.split(rng, 8)
    pd = cfg.param_dtype
    d, f, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    nh, nkv, L = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / (fan_in**0.5)).astype(pd)

    return {
        "embed": dense(keys[0], (cfg.vocab_size, d), d),  # scaled like d for stability
        "layers": {
            "wq": dense(keys[1], (L, d, nh * hd), d),
            "wk": dense(keys[2], (L, d, nkv * hd), d),
            "wv": dense(keys[3], (L, d, nkv * hd), d),
            "wo": dense(keys[4], (L, nh * hd, d), nh * hd),
            "w_gate": dense(keys[5], (L, d, f), d),
            "w_up": dense(keys[6], (L, d, f), d),
            "w_down": dense(keys[7], (L, f, d), f),
            "attn_norm": jnp.ones((L, d), pd),
            "mlp_norm": jnp.ones((L, d), pd),
        },
        "final_norm": jnp.ones((d,), pd),
        # LM head is tied to embed by default (llama3 unties; keep explicit)
        "lm_head": dense(jax.random.fold_in(keys[0], 1), (d, cfg.vocab_size), d),
    }


def logical_axes(cfg: LlamaConfig) -> Params:
    """Logical sharding tree matching init()'s structure (see parallel.sharding)."""
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "wq": ("layers", "embed", "qkv"),
            "wk": ("layers", "embed", "qkv"),
            "wv": ("layers", "embed", "qkv"),
            "wo": ("layers", "qkv", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
            "attn_norm": ("layers", "embed_no_fsdp"),
            "mlp_norm": ("layers", "embed_no_fsdp"),
        },
        "final_norm": ("embed_no_fsdp",),
        "lm_head": ("embed", "vocab"),
    }


QUANT_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_params(params: Params) -> Params:
    """Weight-only int8 for SERVING (ops/quant.py): every matmul weight
    becomes {"q": int8, "s": f32 per-out-channel}; embed (a gather) and the
    norms (tiny) stay in param dtype. Decode re-reads all weights per step,
    so this halves the dominant HBM traffic vs bf16 (4x vs f32) while the
    MXU still computes in bf16. Training params are never quantized."""
    out = dict(params)
    out["layers"] = {
        k: (quant.quantize_int8(v) if k in QUANT_LEAVES else v)
        for k, v in params["layers"].items()}
    out["lm_head"] = quant.quantize_int8(params["lm_head"])
    return out


def logical_axes_for(params: Params, cfg: LlamaConfig) -> Params:
    """logical_axes matching `params`' ACTUAL structure: quantized leaves
    expand to {"q": <full axes>, "s": <axes minus the contracted dim>}."""
    base = logical_axes(cfg)

    def expand(axes, value):
        if quant.is_quantized(value):
            return {"q": axes, "s": axes[:-2] + (axes[-1],)}
        return axes

    return jax.tree.map(expand, base, params,
                        is_leaf=lambda x: isinstance(x, tuple))


def _attention(cfg: LlamaConfig, x, layer, positions, segment_ids):
    b, s, d = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = quant.matmul(h, layer["wq"], cfg.dtype).reshape(b, s, nh, hd)
    k = quant.matmul(h, layer["wk"], cfg.dtype).reshape(b, s, nkv, hd)
    v = quant.matmul(h, layer["wv"], cfg.dtype).reshape(b, s, nkv, hd)
    out = _attend(cfg, q, k, v, positions, segment_ids)
    return x + quant.matmul(out, layer["wo"], cfg.dtype)


def _attend(cfg: LlamaConfig, q, k, v, positions, segment_ids):
    """RoPE and the configured full-sequence attention over projected
    q [B, S, nh, hd], k, v [B, S, nkv, hd] -> [B, S, nh * hd]."""
    b, s, nh, hd = q.shape
    q = apply_rope(q, positions, theta=cfg.rope_theta)
    k = apply_rope(k, positions, theta=cfg.rope_theta)

    if cfg.attention_impl == "flash":
        from kubeflow_tpu.ops.flash_attention import flash_attention

        out = flash_attention(q, k, v, causal=True, segment_ids=segment_ids)
    elif cfg.attention_impl in ("ring", "ulysses"):
        # sequence-parallel islands: the surrounding model runs under
        # GSPMD jit with seq-sharded activations; the attention op alone
        # drops to shard_map for its manual collectives (ppermute ring /
        # all-to-all reshard). Mesh comes from parallel.active_mesh —
        # degrade to plain attention when there's no seq axis to ride.
        # When `sequence` is ALREADY manual (a pipeline stage body that
        # manualized stage+sequence together), call the per-device bodies
        # directly — Shardy rejects the nested-island form.
        from kubeflow_tpu.parallel.mesh import (get_active_mesh,
                                                manual_axis_names,
                                                mesh_shape)

        mesh = get_active_mesh()
        seq_n = mesh_shape(mesh).get("sequence", 1) if mesh is not None else 1
        if seq_n == 1:
            out = mha(q, k, v, causal=True, segment_ids=segment_ids)
        elif "sequence" in manual_axis_names(mesh):
            if cfg.attention_impl == "ring":
                from kubeflow_tpu.ops.ring_attention import ring_attention

                out = ring_attention(q, k, v, causal=True,
                                     segment_ids=segment_ids)
            else:
                from kubeflow_tpu.ops.ulysses import ulysses_attention

                out = ulysses_attention(q, k, v, causal=True,
                                        segment_ids=segment_ids)
        elif cfg.attention_impl == "ring":
            from kubeflow_tpu.ops.ring_attention import ring_attention_sharded

            out = ring_attention_sharded(q, k, v, mesh, causal=True,
                                         segment_ids=segment_ids)
        else:
            from kubeflow_tpu.ops.ulysses import ulysses_attention_sharded

            out = ulysses_attention_sharded(q, k, v, mesh, causal=True,
                                            segment_ids=segment_ids)
    else:
        out = mha(q, k, v, causal=True, segment_ids=segment_ids)
    return out.reshape(b, s, nh * hd)


def _mlp(cfg: LlamaConfig, x, layer):
    # delegates to the serving MLP with no adapters — one SwiGLU body
    return _serving_mlp(cfg, x, layer)


def _overlapped_layer(cfg: LlamaConfig, mesh, x, layer, positions,
                      segment_ids):
    """The training layer on a mesh with `tensor` > 1 (parallel/overlap.py
    has the mechanism and `mesh_for` the selection): the residual x is
    [batch, seq / tensor, embed] between projections, and each projection
    is a region manual over `tensor` that moves its exchange under its
    own matmuls. The weights' `fsdp` axis stays with the partitioner, and
    so does everything between the regions: RoPE and the attention (the
    Pallas island opens its own shard_map) see q, k, v with the whole
    sequence and heads over `tensor`, as under plain GSPMD."""
    b, s, _ = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt, ax = cfg.dtype, overlap.AXIS
    seq, cols, rows = P(None, ax), P(None, ax), P(ax)
    region = partial(jax.shard_map, mesh=mesh, axis_names=frozenset({ax}))

    def qkv(x, norm, wq, wk, wv):
        h = rms_norm(x, norm, cfg.norm_eps)
        return overlap.gather_matmul(
            h, (wq.astype(dt), wk.astype(dt), wv.astype(dt)), axis=1,
            site="wq|wk|wv")

    def wo(x, out, w):
        return x + overlap.matmul_scatter(out, w.astype(dt), axis=1,
                                          site="wo")

    def mlp(x, norm, w_gate, w_up, w_down):
        h = rms_norm(x, norm, cfg.norm_eps)
        act = [jax.nn.silu(gate) * up for gate, up in overlap.gather_matmul(
            h, (w_gate.astype(dt), w_up.astype(dt)), axis=1, blocks=True,
            site="w_gate|w_up")]
        return x + overlap.matmul_scatter(act, w_down.astype(dt), axis=1,
                                          site="w_down")

    heads = P(None, None, ax)
    q, k, v = region(qkv, in_specs=(seq, P(), cols, cols, cols),
                     out_specs=heads)(
        x, layer["attn_norm"], layer["wq"], layer["wk"], layer["wv"])
    out = _attend(cfg, q.reshape(b, s, nh, hd), k.reshape(b, s, nkv, hd),
                  v.reshape(b, s, nkv, hd), positions, segment_ids)
    x = region(wo, in_specs=(seq, heads, rows), out_specs=seq)(
        x, out, layer["wo"])
    return region(mlp, in_specs=(seq, P(), cols, cols, rows),
                  out_specs=seq)(
        x, layer["mlp_norm"], layer["w_gate"], layer["w_up"],
        layer["w_down"])


def _layer_body(cfg: LlamaConfig, carry, layer, positions, segment_ids):
    x = carry
    mesh = overlap.mesh_for(x.shape[1], [layer[t] for t in QUANT_LEAVES])
    if mesh is not None:
        return _overlapped_layer(cfg, mesh, x, layer, positions,
                                 segment_ids), None
    x = _attention(cfg, x, layer, positions, segment_ids)
    x = _mlp(cfg, x, layer)
    return x, None


def apply_hidden(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    positions: jax.Array | None = None,
    segment_ids: jax.Array | None = None,
) -> jax.Array:
    """Forward pass up to (and including) the final norm: [B, S] int
    tokens -> [B, S, d_model] activations, no lm_head projection. The
    chunked-CE loss path projects per sequence chunk so the [B, S, vocab]
    f32 logits never materialize whole (the 32k-context memory wall)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.arange(s)
    x = params["embed"].astype(cfg.dtype)[tokens]  # [B,S,D] gather

    body = partial(_layer_body, cfg, positions=positions, segment_ids=segment_ids)
    if cfg.remat:
        policy = {
            "minimal": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            "full": jax.checkpoint_policies.nothing_saveable,
            "none": jax.checkpoint_policies.everything_saveable,
        }[cfg.remat_policy]
        body = jax.checkpoint(body, policy=policy)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, params["layers"])
    else:
        for i in range(cfg.n_layers):
            layer = jax.tree.map(lambda p: p[i], params["layers"])
            x, _ = body(x, layer)

    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def apply(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    positions: jax.Array | None = None,
    segment_ids: jax.Array | None = None,
) -> jax.Array:
    """Forward pass: [B, S] int tokens -> [B, S, vocab] fp32 logits."""
    x = apply_hidden(params, tokens, cfg, positions=positions,
                     segment_ids=segment_ids)
    logits = quant.matmul_f32_out(x, params["lm_head"], cfg.dtype)
    return logits


def loss_fn(params: Params, batch: dict[str, jax.Array], cfg: LlamaConfig):
    """Next-token cross-entropy with optional loss mask. batch: tokens [B,S],
    optionally loss_mask [B,S] (1.0 where the target counts).

    On a mesh with a `stage` axis the whole forward+loss runs as a GPipe
    schedule instead (parallel.pipeline) — same math, pipelined execution."""
    from kubeflow_tpu.parallel.mesh import get_active_mesh, mesh_shape

    mesh = get_active_mesh()
    if mesh is not None and mesh_shape(mesh).get("stage", 1) > 1:
        from kubeflow_tpu.parallel.pipeline import pipelined_llama_loss

        return pipelined_llama_loss(params, batch, cfg, mesh,
                                    cfg.pipeline_microbatches or None)
    tokens = batch["tokens"]
    if cfg.ce_chunk:
        return _chunked_ce_loss(params, batch, cfg)
    # Forward on the FULL sequence, shift logits afterwards: S-1 wouldn't
    # divide a `sequence` mesh axis, and the slice lives in GSPMD-land where
    # resharding is legal (the shard_map attention islands only ever see S).
    logits = apply(params, tokens, cfg,
                   positions=jnp.arange(tokens.shape[1]),
                   segment_ids=batch.get("segment_ids"))[:, :-1]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    token_loss = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    mask = jnp.ones_like(token_loss) if mask is None else mask[:, 1:]
    total = jnp.sum(token_loss * mask)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return total / denom, {"loss": total / denom, "tokens": jnp.sum(mask)}


def _chunked_ce_loss(params: Params, batch: dict[str, jax.Array],
                     cfg: LlamaConfig):
    """Sequence-chunked cross-entropy (cfg.ce_chunk > 0): the lm_head
    projection + log-softmax run per ce_chunk-token slice under
    jax.checkpoint, so only ONE [B, C, vocab] f32 logits block is ever
    live (fwd AND bwd) instead of the whole [B, S, vocab] — at seq 32768
    x vocab 32000 the whole-sequence block is ~4 GiB x several copies,
    the single-chip long-context memory wall. Numerically the same loss
    as the plain path (parity-tested); requires S % ce_chunk == 0."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    c = cfg.ce_chunk
    if s % c:
        raise ValueError(f"seq_len {s} must divide by ce_chunk {c}")
    h = apply_hidden(params, tokens, cfg,
                     positions=jnp.arange(s),
                     segment_ids=batch.get("segment_ids"))
    # targets roll left; the final position is masked off (no target)
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    valid = jnp.ones((b, s), jnp.float32).at[:, -1].set(0.0)
    mask = batch.get("loss_mask")
    if mask is not None:
        # plain path indexes loss_mask by TARGET position (mask[:, 1:])
        valid = valid * jnp.concatenate(
            [mask[:, 1:].astype(jnp.float32),
             jnp.zeros((b, 1), jnp.float32)], axis=1)
    n_chunks = s // c
    xs = (jnp.moveaxis(h.reshape(b, n_chunks, c, -1), 1, 0),
          jnp.moveaxis(targets.reshape(b, n_chunks, c), 1, 0),
          jnp.moveaxis(valid.reshape(b, n_chunks, c), 1, 0))

    @jax.checkpoint
    def chunk(carry, inp):
        hc, tc, vc = inp
        logits = quant.matmul_f32_out(hc, params["lm_head"], cfg.dtype)
        logp = jax.nn.log_softmax(logits, axis=-1)
        tl = -jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        total, denom = carry
        return (total + jnp.sum(tl * vc), denom + jnp.sum(vc)), None

    (total, denom), _ = jax.lax.scan(chunk, (jnp.float32(0.0),
                                             jnp.float32(0.0)), xs)
    denom = jnp.maximum(denom, 1.0)
    return total / denom, {"loss": total / denom, "tokens": denom}


# ---------------------------------------------------------------------------
# Serving path: KV-cache prefill + decode (the in-framework replacement for
# the reference's Triton/torchserve runtime containers, SURVEY.md §2.4/§2.6).
# Static shapes throughout: prompt lengths are bucketed by the serving
# scheduler; the cache is a fixed [L, slots, max_len, kv, hd] ring of slots.
# ---------------------------------------------------------------------------


def init_cache(cfg: LlamaConfig, n_slots: int, max_len: int,
               kv_quantize: str | None = None,
               chunk: int | None = None) -> Params:
    """The slab KV cache: payload `[L, slots, max_len, kv_heads, hd]`;
    int8 payloads come with their per-token-per-head f32 scales stored
    LANE-MAJOR, `[L, slots, kv_heads, max_len]`: the layout the decode
    kernel reads in place (ops/flash_decode.py), dense under the TPU's
    (8, 128) tiling where `[..., max_len, kv_heads]` pads 8 lanes to 128.
    `chunk` (the most rows one prefill program writes) sizes a family's
    ring; every layer here keeps all of `max_len`, so it is not read."""
    shape = (cfg.n_layers, n_slots, max_len, cfg.n_kv_heads, cfg.head_dim)
    if kv_quantize == "int8":
        sshape = (cfg.n_layers, n_slots, cfg.n_kv_heads, max_len)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(sshape, jnp.float32),
                "v_s": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def cache_kv_spec(name: str, axis: str = "tensor"):
    """PartitionSpec that shards cache array `name` ("k", "v", "k_s",
    "v_s") over its kv-head dimension: dim 3 of the 5-D payloads, dim 2
    of the lane-major scale planes. No trailing None: GSPMD emits the
    trimmed spec on program outputs and the jit cache compares specs
    structurally."""
    from jax.sharding import PartitionSpec as P

    return (P(None, None, axis) if name.endswith("_s")
            else P(None, None, None, axis))


#: per-step counters a family's decode_step hands back under
#: new_cache["counters"] (f32 [len]); the engine packs them into the rows
#: it fetches anyway and sums them into metrics(). This family keeps none.
STEP_COUNTERS: tuple[str, ...] = ()


def cache_stats(cache: Params) -> dict[str, Any]:
    """What metrics() says of the cache's layout beyond `kv_layout`:
    nothing here, one slab being all there is."""
    return {}


def cache_write(cache: Params, slot, start: int, count: int, ks, vs, *,
                kv_quantize: str | None = None) -> Params:
    """Write one prompt's [L, count, kv, hd] KV rows into a slot's
    [start, start+count) range, quantizing when the cache is int8.
    start/count are static."""
    out = dict(cache)
    if kv_quantize == "int8":
        kq, ksc = quantize_kv(ks)
        vq, vsc = quantize_kv(vs)
        out["k"] = cache["k"].at[:, slot, start:start + count].set(kq)
        out["v"] = cache["v"].at[:, slot, start:start + count].set(vq)
        # scales are stored lane-major: [L, slots, kv, max_len]
        out["k_s"] = cache["k_s"].at[:, slot, :, start:start + count
                                     ].set(jnp.swapaxes(ksc, 1, 2))
        out["v_s"] = cache["v_s"].at[:, slot, :, start:start + count
                                     ].set(jnp.swapaxes(vsc, 1, 2))
    else:
        out["k"] = cache["k"].at[:, slot, start:start + count].set(
            ks.astype(cache["k"].dtype))
        out["v"] = cache["v"].at[:, slot, start:start + count].set(
            vs.astype(cache["v"].dtype))
    return out


def slot_scales(scales: jax.Array, slot, p: int) -> jax.Array:
    """A slot's first `p` per-token scales out of the lane-major cache
    plane `[L, slots, kv, max_len]`, store-shaped [L, 1, p, kv]."""
    rows = jax.lax.dynamic_index_in_dim(scales, slot, axis=1,
                                        keepdims=False)[:, :, :p]
    return jnp.swapaxes(rows, 1, 2)[:, None]


def extract_prefix(cfg, cache: Params, slot, p: int, *,
                   kv_quantize: str | None = None, dtype=None):
    """A freshly prefilled slot's first `p` KV rows as prefill_continue
    takes its prefix: (k, v) [L, 1, P, kv, hd], dequantized."""
    k = jax.lax.dynamic_index_in_dim(cache["k"], slot, axis=1,
                                     keepdims=False)[:, :p][:, None]
    v = jax.lax.dynamic_index_in_dim(cache["v"], slot, axis=1,
                                     keepdims=False)[:, :p][:, None]
    if kv_quantize == "int8":
        ksc, vsc = (slot_scales(cache[n], slot, p) for n in ("k_s", "v_s"))
        k = dequantize_kv(k, ksc, dtype)
        v = dequantize_kv(v, vsc, dtype)
    return k, v


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-token-per-head symmetric int8 over head_dim: [..., hd] ->
    (int8 [..., hd], f32 scale [...]). Decode re-reads the whole cache every
    step, so int8 KV halves that HBM traffic vs bf16 (ops/quant.py's
    weight-only argument, applied to the cache)."""
    s = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1),
                    1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, s


def dequantize_kv(q: jax.Array, s: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * s[..., None]).astype(dtype)


def _adapted(h, layer, t: str, lora_layer, ids, dtype):
    """One serving matmul with an optional per-row LoRA path.

    h: [B, S, d_in]; lora_layer[t] = {"a": [A, d_in, r], "b": [A, r, d_out]}
    (adapter-stacked, THIS layer's slice; b is pre-scaled by alpha/rank);
    ids: [B] adapter index per row (0 = the zero adapter = base only).
    Multi-adapter batched serving: x@W once for the batch, plus the
    low-rank bypass gathered per row — S-LoRA's trick, XLA-shaped (the
    gather is tiny next to the W read decode is bound on)."""
    y = quant.matmul(h, layer[t], dtype, layer.get("layer_idx"))
    if lora_layer is None or t not in lora_layer:
        return y
    a = lora_layer[t]["a"][ids].astype(jnp.float32)  # [B, d_in, r]
    b = lora_layer[t]["b"][ids].astype(jnp.float32)  # [B, r, d_out]
    z = jnp.einsum("bsd,bdr->bsr", h.astype(jnp.float32), a)
    return y + jnp.einsum("bsr,bro->bso", z, b).astype(y.dtype)


def _project_qkv(cfg: LlamaConfig, layer, x, positions, lora_layer=None,
                 ids=None):
    b, s, _ = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = _adapted(h, layer, "wq", lora_layer, ids, cfg.dtype).reshape(
        b, s, nh, hd)
    k = _adapted(h, layer, "wk", lora_layer, ids, cfg.dtype).reshape(
        b, s, nkv, hd)
    v = _adapted(h, layer, "wv", lora_layer, ids, cfg.dtype).reshape(
        b, s, nkv, hd)
    return (apply_rope(q, positions, theta=cfg.rope_theta),
            apply_rope(k, positions, theta=cfg.rope_theta), v)


def _serving_mlp(cfg: LlamaConfig, x, layer, lora_layer=None, ids=None):
    h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    gate = _adapted(h, layer, "w_gate", lora_layer, ids, cfg.dtype)
    up = _adapted(h, layer, "w_up", lora_layer, ids, cfg.dtype)
    return x + _adapted(jax.nn.silu(gate) * up, layer, "w_down",
                        lora_layer, ids, cfg.dtype)


def _wo(cfg: LlamaConfig, out, layer, lora_layer=None, ids=None):
    return _adapted(out, layer, "wo", lora_layer, ids, cfg.dtype)


def _scan_layers(layers: Params):
    """A layer slab as the serving scans take it: (xs, layer_of).

    The quantized matmul leaves stay OUT of `xs`: the body closes over the
    whole stacks [Ls, in, out] (loop invariants of the `while`) and
    `layer_of(one iteration's slice of xs)` hands them to _adapted with
    the layer's index "layer_idx", so the int8 matmul kernel reads its
    layer in place (ops/quant.py matmul). Sliced by the scan, each would
    reach the kernel, a custom call XLA fuses nothing into, as a copy made
    on every step. Norms and an unquantized tree ride `xs` as ever; the
    index is within the slab."""
    stacked = {t: w for t, w in layers.items() if quant.is_quantized(w)}
    n_layers = jax.tree.leaves(layers)[0].shape[0]
    xs = ({t: w for t, w in layers.items() if t not in stacked},
          jnp.arange(n_layers))

    def layer_of(inp):
        sliced, li = inp
        return {**sliced, **stacked, "layer_idx": li}

    return xs, layer_of


def prefill_inner(layers: Params, x: jax.Array, positions: jax.Array,
                  cfg: LlamaConfig, lora: Params | None = None,
                  ids: jax.Array | None = None):
    """Layer-slab half of prefill: [B, S, D] activations through a
    contiguous slab of layers → (x, k, v [Ls, B, S, kv, hd]). `layers`
    may be ANY leading-axis slice of the full stack — prefill() runs the
    whole model through it, the pipeline stage runner
    (parallel/pipeline.py) feeds each stage its own slab. Keeping ONE
    body is what makes stage-sharded serving byte-exact against the
    single-program engine."""
    b, s = x.shape[:2]
    # resolved ONCE per trace (static): the whole compiled prefill menu
    # of an engine runs one prefill-attention impl — the mha einsum or
    # the fused Pallas chunked-prefill kernel (cfg.prefill_attention_impl)
    attn_impl = resolve_prefill_attn(cfg)
    layer_xs, layer_of = _scan_layers(layers)

    def body(carry, inp):
        x = carry
        lx, ll = inp if lora is not None else (inp, None)
        layer = layer_of(lx)
        q, k, v = _project_qkv(cfg, layer, x, positions, ll, ids)
        out = prefill_attention(cfg, q, k, v, q_offset=0, impl=attn_impl)
        x = x + _wo(cfg, out.reshape(b, s, -1), layer, ll, ids)
        x = _serving_mlp(cfg, x, layer, ll, ids)
        return x, (k, v)

    xs = (layer_xs, lora) if lora is not None else layer_xs
    return jax.lax.scan(body, x, xs)


def lm_head(params: Params, x: jax.Array, cfg: LlamaConfig,
            rows: jax.Array | None = None) -> jax.Array:
    """final_norm + lm_head projection — the serving tail every prefill/
    decode wrapper (and the LAST pipeline stage) shares.

    `rows` [B] int32 projects ONLY position rows[b] of each sequence:
    x [B, S, D] -> logits [B, vocab]. A prefill samples one token per
    prompt, and the all-position f32 logits of a 16 x 1024 wave at vocab
    128256 are 7.8 GiB — more than a 16 GB chip has beside the model.
    Out-of-range rows clamp (the dynamic_index_in_dim rule)."""
    if rows is not None:
        x = jnp.take_along_axis(x, rows[:, None, None], axis=1,
                                mode="clip")[:, 0]
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return quant.matmul_f32_out(x, params["lm_head"], cfg.dtype)


def prefill(params: Params, tokens: jax.Array, cfg: LlamaConfig,
            lora: Params | None = None, ids: jax.Array | None = None,
            logit_rows: jax.Array | None = None):
    """Forward a (right-padded) prompt, returning logits and per-layer KV.

    tokens: [B, S] → (logits [B, S, vocab] fp32, k, v [L, B, S, kv, hd]);
    with `logit_rows` [B], logits are [B, vocab] of those positions only
    (lm_head's `rows`). Pad positions produce garbage KV past the true
    length — callers track lengths and decode masks them out.

    `lora`/`ids`: optional multi-adapter batch (serving/llm.py
    `adapters=`): lora = {target: {"a": [L, A, d_in, r], "b": [L, A, r,
    d_out]}} (adapter-stacked per layer, b pre-scaled by alpha/rank),
    ids = [B] adapter index per row, 0 = base-only.
    """
    _, s = tokens.shape
    positions = jnp.arange(s)
    x = params["embed"].astype(cfg.dtype)[tokens]
    x, (ks, vs) = prefill_inner(params["layers"], x, positions, cfg,
                                lora, ids)
    return lm_head(params, x, cfg, logit_rows), ks, vs


def prefill_continue(params: Params, tail_tokens: jax.Array,
                     k_prefix: jax.Array, v_prefix: jax.Array,
                     cfg: LlamaConfig, lora: Params | None = None,
                     ids: jax.Array | None = None,
                     logit_rows: jax.Array | None = None):
    """Continuation prefill: forward only the TAIL of a prompt whose prefix
    KV is already computed (prefix caching — serving/llm.py).

    tail_tokens: [B, T] (right-padded); k_prefix/v_prefix: [L, B, P, kv, hd]
    from a previous prefill of the shared prefix. Returns
    (logits [B, T, vocab] fp32, k_tail, v_tail [L, B, T, kv, hd]) — or
    logits [B, vocab] of tail positions `logit_rows` [B] only (lm_head's
    `rows`). The tail attends causally over prefix+tail (q_offset = P);
    pad tail positions produce garbage KV the caller masks by true lengths.
    """
    positions = k_prefix.shape[2] + jnp.arange(tail_tokens.shape[1])
    x = params["embed"].astype(cfg.dtype)[tail_tokens]
    x, (ks, vs) = prefill_continue_inner(params["layers"], x, k_prefix,
                                         v_prefix, positions, cfg,
                                         lora, ids)
    return lm_head(params, x, cfg, logit_rows), ks, vs


def prefill_continue_inner(layers: Params, x: jax.Array,
                           k_prefix: jax.Array, v_prefix: jax.Array,
                           positions: jax.Array, cfg: LlamaConfig,
                           lora: Params | None = None,
                           ids: jax.Array | None = None):
    """Layer-slab half of prefill_continue (see prefill_inner): `layers`
    and `k_prefix`/`v_prefix` may be any matching leading-axis slice of
    the stack — the pipeline stage runner hands each stage its own slab
    and prefix-KV slab."""
    b, t = x.shape[:2]
    p = k_prefix.shape[2]
    # static impl resolution, like prefill_inner: one impl per trace
    attn_impl = resolve_prefill_attn(cfg)
    layer_xs, layer_of = _scan_layers(layers)

    def body(carry, inp):
        x = carry
        if lora is not None:
            lx, kp, vp, ll = inp
        else:
            (lx, kp, vp), ll = inp, None  # kp/vp: [B, P, kv, hd]
        layer = layer_of(lx)
        q, k_new, v_new = _project_qkv(cfg, layer, x, positions, ll, ids)
        k_full = jnp.concatenate([kp.astype(cfg.dtype), k_new], axis=1)
        v_full = jnp.concatenate([vp.astype(cfg.dtype), v_new], axis=1)
        out = prefill_attention(cfg, q, k_full, v_full, q_offset=p,
                                impl=attn_impl)
        x = x + _wo(cfg, out.reshape(b, t, -1), layer, ll, ids)
        x = _serving_mlp(cfg, x, layer, ll, ids)
        return x, (k_new, v_new)

    xs = ((layer_xs, k_prefix, v_prefix, lora)
          if lora is not None else (layer_xs, k_prefix, v_prefix))
    return jax.lax.scan(body, x, xs)


def decode_step(params: Params, last_tokens: jax.Array, cache: Params,
                lengths: jax.Array, cfg: LlamaConfig,
                span: int | None = None, lora: Params | None = None,
                ids: jax.Array | None = None,
                active: jax.Array | None = None):
    """One continuous-batching decode step over all cache slots.

    last_tokens: [B] token per slot; lengths: [B] current KV lengths
    (position where this step's KV is written). Returns
    (logits [B, vocab] fp32, updated cache). Inactive slots just produce
    garbage logits the engine ignores — shapes stay static; with `active`
    [B] bool they also attend nothing (verify_inner).

    `span` (static) bounds the attention to the cache's first `span` rows —
    the length-aware decode menu (serving/llm.py). The einsum path reads
    and computes the whole span; the flash kernel only the blocks a
    slot's length reaches, so there it sets the grid's length alone.
    Caller guarantees lengths < span; writes still land in the full cache.

    This IS verify_step at S_v=1 — one attention body, so a masking or
    quantization change can never diverge the plain and speculative paths.
    """
    logits, new_cache = verify_step(params, last_tokens[:, None], cache,
                                    lengths, cfg, span=span, lora=lora,
                                    ids=ids, active=active)
    return logits[:, 0], new_cache


def verify_step(params: Params, tokens: jax.Array, cache: Params,
                lengths: jax.Array, cfg: LlamaConfig,
                span: int | None = None, lora: Params | None = None,
                ids: jax.Array | None = None,
                active: jax.Array | None = None):
    """Speculative-verify step: forward S_v tokens per slot in ONE pass.

    tokens: [B, S_v] — row b holds the slot's pending last token followed by
    S_v-1 draft tokens; they occupy positions lengths[b]..lengths[b]+S_v-1.
    Returns (logits [B, S_v, vocab] fp32, updated cache): logits[:, i] is the
    model's next-token distribution after consuming tokens[:, i] — the
    verifier accepts the longest draft prefix where argmax(logits[:, i]) ==
    tokens[:, i+1] (serving/llm.py). KV rows for ALL S_v positions are
    written (rejected rows become stale, masked by `lengths` and overwritten
    by later writes — same contract as decode_step's junk writes for
    inactive slots). With S_v=1 this is exactly decode_step.

    The per-slot position offsets are what distinguish this from a prefill:
    every slot verifies at a DIFFERENT depth in its cache, which is why the
    reference's GPU runtimes (⊘ vllm speculative worker) need a dedicated
    program here too. Decode is HBM-bound on weight+cache reads, so the
    extra S_v-1 query rows ride along nearly free — that asymmetry is the
    entire speculative-decoding bet.
    """
    x = params["embed"].astype(cfg.dtype)[tokens]  # [B, S_v, D]
    cache_keys = (("k", "v", "k_s", "v_s") if "k_s" in cache
                  else ("k", "v"))
    if "tbl" in cache:   # paged KV (ISSUE 19): the block tables ride along
        cache_keys = cache_keys + ("tbl",)
    cache_in = {name: cache[name] for name in cache_keys}
    x, new_cache = verify_inner(params["layers"], x, cache_in, lengths,
                                cfg, span=span, lora=lora, ids=ids,
                                active=active)
    return lm_head(params, x, cfg), new_cache


def resolve_decode_attn(cfg: LlamaConfig) -> str:
    """The decode-attention impl this config resolves to ("xla"/"flash")
    under the ops/flash_decode selection policy — static, so each
    engine's compiled program menu covers exactly the selected impl."""
    from kubeflow_tpu.ops import flash_decode

    return flash_decode.resolve_impl(cfg.decode_attention_impl,
                                     head_dim=cfg.head_dim,
                                     n_kv_heads=cfg.n_kv_heads)


def resolve_prefill_attn(cfg: LlamaConfig) -> str:
    """The prefill-attention impl this config resolves to ("xla"/
    "flash") under the ops/flash_prefill selection policy — static per
    trace, the prefill twin of resolve_decode_attn."""
    from kubeflow_tpu.ops import flash_prefill

    return flash_prefill.resolve_impl(cfg.prefill_attention_impl,
                                      head_dim=cfg.head_dim,
                                      n_kv_heads=cfg.n_kv_heads)


def prefill_attention(cfg: LlamaConfig, q: jax.Array, k: jax.Array,
                      v: jax.Array, cks=None, cvs=None, *,
                      q_offset: int = 0, impl: str | None = None,
                      tables: jax.Array | None = None,
                      window: int | None = None) -> jax.Array:
    """Causal GQA chunk attention for prefill — the prefill twin of
    decode_attention, THE pluggable seam of the TTFT hot path (ISSUE 20).

    q: [B, S_chunk, nh, hd] (post-RoPE, cfg.dtype) — row i sits at
    absolute position `q_offset + i` (a static python int: full prefill
    at 0, continuation chunks and radix prefix-cache-hit starts at the
    prefix length p — the engine groups continuation waves by (p, t));
    k/v: [B, T, kv, hd] prefix+chunk KV covering positions 0..T-1, in
    cfg.dtype or int8 with cks/cvs [B, T, kv] f32 per-token scales (the
    breakdown probe's cache-direct shape; the engine bodies pass float
    KV). Key position t is visible to row i iff t <= q_offset + i.
    Returns [B, S_chunk, nh, hd] in cfg.dtype (mha's shape contract, so
    the prefill bodies swap in without reshapes).

    impl: "xla" — the reference ops/attention.mha einsum; "flash" — the
    fused Pallas chunked-prefill kernel (ops/flash_prefill.py;
    interpret-mode off-TPU, so the differential tests run on CPU); None
    resolves cfg.prefill_attention_impl.

    PAGED mode: with `tables` [B, T//bt] int32, k/v are the POOL layer
    `[N_blocks, bt, kv, hd]` (cks/cvs `[N_blocks, bt, kv]`). The flash
    kernel indirects its kv-block grid axis through the scalar-
    prefetched table; the XLA path gathers the same blocks into the
    contiguous slab view and falls into the identical mha — the parity
    anchor that keeps slab and paged byte-comparable, exactly like
    decode_attention's twin paths.

    `window` (static; None = causal alone): key t is visible to row i iff
    `0 <= q_offset + i - t < window`, in both impls.
    """
    if impl is None:
        impl = resolve_prefill_attn(cfg)
    b = q.shape[0]
    nkv, hd = cfg.n_kv_heads, cfg.head_dim
    if impl == "flash":
        from kubeflow_tpu.ops.flash_prefill import flash_prefill_attention

        return flash_prefill_attention(q, k, v, q_offset=q_offset,
                                       k_scale=cks, v_scale=cvs,
                                       scale=1.0 / (hd ** 0.5),
                                       tables=tables,
                                       **({} if window is None
                                          else {"window": window}))
    if tables is not None:
        # XLA gather twin (see decode_attention): stage the table's
        # blocks as the contiguous [B, T, kv, hd] slab view, then the
        # SAME mha below runs unchanged.
        bt, nb = k.shape[1], tables.shape[1]
        k = jnp.take(k, tables, axis=0).reshape(b, nb * bt, nkv, hd)
        v = jnp.take(v, tables, axis=0).reshape(b, nb * bt, nkv, hd)
        if cks is not None:
            cks = jnp.take(cks, tables, axis=0).reshape(b, nb * bt, nkv)
            cvs = jnp.take(cvs, tables, axis=0).reshape(b, nb * bt, nkv)
    if cks is not None:
        # int8 cache probe path: dequantize the chunk's KV view in
        # cfg.dtype — prefill reads each key once (unlike decode's
        # re-reads), so the einsum reference keeps the simple form
        k = k.astype(cfg.dtype) * cks[..., None].astype(cfg.dtype)
        v = v.astype(cfg.dtype) * cvs[..., None].astype(cfg.dtype)
    return mha(q, k.astype(cfg.dtype), v.astype(cfg.dtype), causal=True,
               q_offset=q_offset, window=window)


def decode_attention(cfg: LlamaConfig, q: jax.Array, cache: Params,
                     layer, positions: jax.Array, *,
                     span: int | None = None, slot_start: int = 0,
                     impl: str | None = None,
                     tables: jax.Array | None = None, new_scales=None,
                     window: int | None = None):
    """Grouped-query decode/verify attention of ONE layer over the KV
    cache as the layer scan carries it — THE pluggable seam of the
    serving hot loop (ISSUE 15; in place since ISSUE 28).

    q: [B, S_v, nh, hd] (post-RoPE, cfg.dtype); cache: {"k", "v"} the
    whole payloads `[L, slots, max_len, kv, hd]` in cache dtype (int8 or
    cfg.dtype), with {"k_s", "v_s"} `[L, slots, kv, max_len]` f32
    per-token scales when int8; `layer`: which of the L (the scan's
    index, traced); the B rows are cache slots `slot_start ..
    slot_start + B - 1` and attend the first `span` rows (static; the
    whole of max_len by default); positions: [B, S_v] absolute key
    positions of the query rows — row i MUST sit at positions[:, 0] + i
    (the decode/verify contract; the flash kernel exploits it). Key t is
    visible to row i iff t <= positions[:, i]. Returns [B, S_v, nh*hd]
    attention output in cfg.dtype.

    impl: "flash" — the fused Pallas kernel, which takes the arrays
    whole: its index maps pick layer, slot window and the blocks a
    slot's context reaches (ops/flash_decode.py; interpret-mode off-TPU,
    so the differential tests run on CPU); "xla" — the reference einsum
    path (dequant fused into the einsum operands, f32 softmax), which
    slices its layer and span out here; None resolves
    cfg.decode_attention_impl.

    PAGED mode (ISSUE 19): with `tables` [B, span//bt] int32 (this
    batch's rows, clipped to the span), the payloads are the POOL
    `[L, N_blocks, bt, kv, hd]` (scales `[L, N_blocks, kv, bt]`) and row
    b's logical span is the concatenation of its table's blocks. The
    flash kernel indirects its kv-block grid axis through the
    scalar-prefetched table; the XLA path gathers the same blocks into
    the contiguous slab view and falls into the identical einsum — the
    parity anchor that makes slab vs paged byte-comparable.

    `new_scales` (flash, int8): the (k, v) `[B, S_v, kv]` scales of the
    rows this step wrote at `positions`, which the planes do not hold
    yet: the kernel attends with them and stores them, and the return is
    `(out, k_s, v_s)` (ops/flash_decode.py says why it is the kernel's
    to do).

    `window` (static; None = the whole context): the slab is a RING of
    its T rows, position p in row `p mod T`, and key p is visible to row
    i iff `0 <= positions[:, i] - p < window`; `span` is not read. Both
    impls: the flash kernel folds its blocks onto the ring, the einsum
    path masks every ring row by the position it holds.
    """
    if impl is None:
        impl = resolve_decode_attn(cfg)
    b, s_v = q.shape[:2]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    quantized = "k_s" in cache
    if impl == "flash":
        from kubeflow_tpu.ops.flash_decode import flash_decode_attention

        out = flash_decode_attention(
            q, cache["k"], cache["v"], positions[:, 0], layer=layer,
            span=span, slot_start=slot_start, k_scale=cache.get("k_s"),
            v_scale=cache.get("v_s"), new_scales=new_scales,
            scale=1.0 / (hd ** 0.5), tables=tables,
            **({} if window is None else {"window": window}))
        if new_scales is None:
            return out.reshape(b, s_v, nh * hd)
        return (out[0].reshape(b, s_v, nh * hd), *out[1:])
    if new_scales is not None:
        raise ValueError("new_scales are the flash kernel's to store")

    def layer_rows(name):
        # index the layer FIRST, then slice: the other order would stage
        # an [L, B, span, ...] temp of the whole cache
        rows = jax.lax.dynamic_index_in_dim(cache[name], layer, axis=0,
                                            keepdims=False)
        if tables is not None:   # pool layer: the TABLE does the slicing
            return rows
        if slot_start or rows.shape[0] != b:   # a microbatch's window
            rows = jax.lax.slice_in_dim(rows, slot_start, slot_start + b,
                                        axis=0)
        t_axis = 2 if name.endswith("_s") else 1
        return jax.lax.slice_in_dim(
            rows, 0, rows.shape[t_axis]
            if span is None or window is not None else span, axis=t_axis)

    ck, cv = layer_rows("k"), layer_rows("v")
    cks, cvs = ((layer_rows("k_s"), layer_rows("v_s")) if quantized
                else (None, None))
    if tables is not None:
        # XLA gather twin: jnp.take stages the table's blocks as the
        # [B, span, kv, hd] slab view (the transient copy the
        # `kv_gather` breakdown bucket measures), then the SAME einsum
        # below runs unchanged — one masking/softmax body for slab and
        # paged, so the layouts can never diverge numerically.
        bt, nb = ck.shape[1], tables.shape[1]
        ck = jnp.take(ck, tables, axis=0).reshape(b, nb * bt, nkv, hd)
        cv = jnp.take(cv, tables, axis=0).reshape(b, nb * bt, nkv, hd)
        if quantized:   # [b, nb, kv, bt] -> [b, kv, span]
            cks, cvs = (jnp.swapaxes(jnp.take(sc, tables, axis=0), 1, 2)
                        .reshape(b, nkv, nb * bt) for sc in (cks, cvs))
    # XLA reference: grouped-query attention WITHOUT repeat_kv — q
    # regroups to [B, kv, g, Sv, hd] and both einsums contract against
    # the [B, span, kv, hd] cache directly; materializing the 4x
    # head-expanded K/V (and, when quantized, a dequantized copy) would
    # add GiB-scale HBM traffic per step at 8B dims. The int8 cache
    # dequant stays INSIDE the einsum operand (convert + scale fuse into
    # the dot read); scales ([B, kv, span]) apply to the score/output
    # instead of the payload where the algebra allows.
    g = nh // nkv
    k_pos = jnp.arange(ck.shape[1])
    if window is None:
        mask = (k_pos[None, None, None, :]
                <= positions[:, None, :, None])  # [B, 1, Sv, span]
    else:
        # ring row r holds the newest position <= the row's newest that
        # is r mod T (negative: nothing yet)
        top = positions[:, -1:]
        held = (top - jnp.mod(top - k_pos[None], ck.shape[1])
                )[:, None, None, :]
        q_pos = positions[:, None, :, None]
        mask = (held >= 0) & (held <= q_pos) & (q_pos - held < window)
    qg = jnp.moveaxis(q.reshape(b, s_v, nkv, g, hd), 1, 3)
    if quantized:
        att = jnp.einsum("bhgqd,bkhd->bhgqk", qg, ck.astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
        att = att * cks[:, :, None, None, :]
    else:
        att = jnp.einsum("bhgqd,bkhd->bhgqk", qg, ck,
                         preferred_element_type=jnp.float32)
    att = att * (1.0 / (hd ** 0.5))
    att = jnp.where(mask[:, :, None], att, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(att, axis=-1).astype(cfg.dtype)
    if quantized:
        # v = vq * vs[..., None]: fold vs into probs' k axis so the
        # int8 payload feeds the dot un-materialized
        probs_s = probs * cvs[:, :, None, None, :].astype(probs.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs_s,
                         cv.astype(cfg.dtype))
    else:
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, cv)
    return out.reshape(b, s_v, nh * hd)


def verify_inner(layers: Params, x: jax.Array, cache: Params,
                 lengths: jax.Array, cfg: LlamaConfig,
                 span: int | None = None, lora: Params | None = None,
                 ids: jax.Array | None = None, slot_start: int = 0,
                 active: jax.Array | None = None):
    """Layer-slab half of verify_step: x [B, S_v, D] activations through
    a contiguous slab of layers against that slab's KV cache →
    (x, new_cache). The cache may hold MORE slots than x carries rows:
    `slot_start` names the first cache slot this batch occupies (the
    pipeline stage runner decodes one microbatch of slots at a time
    against the stage's full-slot cache slab; the single-program path
    always passes the full batch at slot_start 0). `lengths` is per-ROW
    of x (already sliced to the microbatch). `active` [B] bool, where
    the caller has it: a row that is not attends NOTHING (a finished
    slot keeps a stale length, and its old context is not worth
    fetching); its KV write and RoPE positions stay as `lengths` has
    them, so junk writes still vanish (mode="drop")."""
    b, s_v = x.shape[:2]
    paged = "tbl" in cache
    if paged:
        # paged KV (ISSUE 19): cache holds the POOL arrays [L, N_blocks,
        # bt, kv, hd] plus the per-slot block tables "tbl" [n_slots,
        # max_len // bt]. The tables are carried alongside (never
        # written per layer — pop them from the scan carry) and logical
        # coordinates indirect through them everywhere below.
        cache = dict(cache)
        tbl = cache.pop("tbl")
        bt = cache["k"].shape[2]
        max_len = tbl.shape[1] * bt
    else:
        max_len = cache["k"].shape[2]
    span = max_len if span is None else min(span, max_len)
    quantized = "k_s" in cache
    rows = slot_start + jnp.arange(b)
    positions = lengths[:, None] + jnp.arange(s_v)[None]  # [B, S_v]
    attn_positions = positions if active is None else jnp.where(
        active[:, None], positions, jnp.arange(s_v)[None] - s_v)
    if paged:
        if span % bt:
            raise ValueError(
                f"paged span {span} must divide by block_tokens {bt}")
        # this batch's table rows, clipped to the attention span
        tbl_b = tbl[slot_start:slot_start + b, :span // bt]
        # write coordinates: position p of row r lands at block
        # tbl[r, p // bt], offset p % bt. Positions at/past max_len
        # (inactive slots' junk) — and any position whose table entry
        # was never allocated — indirect to block 0, the pool's trash
        # sentinel: the paged twin of the slab path's mode="drop".
        pos_c = jnp.minimum(positions, max_len - 1)
        blk = jnp.where(positions < max_len,
                        tbl[rows[:, None], pos_c // bt], 0)
        w_row, w_pos = blk, positions % bt
    else:
        # drop mode: inactive slots can carry lengths near max_len —
        # their junk writes must vanish, not clamp onto the last live row
        w_row, w_pos = rows[:, None], positions
    # resolved ONCE per trace (static): the whole compiled menu of an
    # engine runs one decode-attention impl — xla einsum or the fused
    # Pallas flash-decode kernel (cfg.decode_attention_impl)
    attn_impl = resolve_decode_attn(cfg)
    kernel_stores = quantized and attn_impl == "flash"

    # The KV cache rides the scan as CARRY (not xs/ys): a per-layer
    # dynamic-update-slice on the carried buffer updates S_v rows in
    # place (XLA aliases while-loop carries), where stacked ys would
    # re-write the ENTIRE cache every decode step — at 8B dims that is
    # ~2 GiB of junk HBM write+read per step on the serving hot path.
    def body(carry, inp):
        x, cache_c = carry
        lx, ll = inp if lora is not None else (inp, None)
        layer = layer_of(lx)
        li = layer["layer_idx"]
        q, k_new, v_new = _project_qkv(cfg, layer, x, positions, ll, ids)
        if quantized:
            kq, ksc = quantize_kv(k_new)
            vq, vsc = quantize_kv(v_new)
            writes = {"k": kq, "v": vq, "k_s": ksc, "v_s": vsc}
        else:
            writes = {"k": k_new.astype(cache_c["k"].dtype),
                      "v": v_new.astype(cache_c["v"].dtype)}
        # payload rows are [.., token, kv, hd], scale rows [.., kv, token]:
        # the einsum path scatters both here, the flash kernel stores the
        # step's scales itself (it holds the block they land in)
        cache_c = dict(cache_c)
        for name in ("k", "v"):
            cache_c[name] = cache_c[name].at[li, w_row, w_pos].set(
                writes[name], mode="drop")
        if quantized and not kernel_stores:
            for name in ("k_s", "v_s"):
                cache_c[name] = cache_c[name].at[li, w_row, :, w_pos].set(
                    writes[name], mode="drop")
        # attention rides the pluggable decode_attention seam over the
        # carry itself: the xla einsum reference slices its layer and
        # span out, the fused Pallas flash-decode kernel reads them in
        # place, per cfg.decode_attention_impl — ONE body for plain
        # decode (S_v=1) and speculative verify, so the impls can never
        # diverge the two paths
        out = decode_attention(
            cfg, q, cache_c, li, attn_positions, span=span,
            slot_start=slot_start, impl=attn_impl,
            tables=tbl_b if paged else None,
            new_scales=(ksc, vsc) if kernel_stores else None)
        if kernel_stores:
            out, k_s, v_s = out
            cache_c = dict(cache_c, k_s=k_s, v_s=v_s)
        x = x + _wo(cfg, out, layer, ll, ids)
        x = _serving_mlp(cfg, x, layer, ll, ids)
        return (x, cache_c), None

    layer_xs, layer_of = _scan_layers(layers)
    xs = (layer_xs, lora) if lora is not None else layer_xs
    (x, new_cache), _ = jax.lax.scan(body, (x, cache), xs)
    if paged:
        new_cache = dict(new_cache, tbl=tbl)   # tables pass through
    return x, new_cache


# ---------------------------------------------------------------------------
# HuggingFace checkpoint ingestion (SURVEY.md §2.4 huggingfaceserver slot;
# VERDICT r1 missing #2: real published weights must be servable).
# HF llama uses the same rotate_half RoPE convention as ops/rope.py, so the
# mapping is pure renaming + the torch Linear [out,in] -> x@W [in,out]
# transpose; per-layer tensors stack onto the leading lax.scan axis.
# ---------------------------------------------------------------------------

# our stacked-layer leaf -> (HF per-layer template, needs_transpose)
_HF_LAYER_MAP = {
    "wq": ("model.layers.{i}.self_attn.q_proj.weight", True),
    "wk": ("model.layers.{i}.self_attn.k_proj.weight", True),
    "wv": ("model.layers.{i}.self_attn.v_proj.weight", True),
    "wo": ("model.layers.{i}.self_attn.o_proj.weight", True),
    "w_gate": ("model.layers.{i}.mlp.gate_proj.weight", True),
    "w_up": ("model.layers.{i}.mlp.up_proj.weight", True),
    "w_down": ("model.layers.{i}.mlp.down_proj.weight", True),
    "attn_norm": ("model.layers.{i}.input_layernorm.weight", False),
    "mlp_norm": ("model.layers.{i}.post_attention_layernorm.weight", False),
}


def is_hf_checkpoint(path: str) -> bool:
    """True for a HuggingFace-format model dir (config.json + safetensors)."""
    import glob
    import os

    return (os.path.isdir(path)
            and os.path.exists(os.path.join(path, "config.json"))
            and bool(glob.glob(os.path.join(path, "*.safetensors"))))


def config_from_hf(path: str, **overrides: Any) -> LlamaConfig:
    """LlamaConfig from an HF config.json (llama-family field names)."""
    import json
    import os

    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    heads = hf["num_attention_heads"]
    fields = dict(
        vocab_size=hf["vocab_size"],
        d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=heads,
        n_kv_heads=hf.get("num_key_value_heads", heads),
        d_ff=hf["intermediate_size"],
        max_seq_len=hf.get("max_position_embeddings", 8192),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
    )
    fields.update(overrides)
    return LlamaConfig(**fields)


def load_hf(path: str, cfg: LlamaConfig | None = None, *,
            mesh=None, rules=None) -> tuple[Params, LlamaConfig]:
    """Load an HF-format llama checkpoint dir into init()-shaped params.

    Returns (params, cfg). With `mesh`, every leaf is device_put with the
    sharding the logical-axis rules give it (parallel/sharding.py) — the
    same layout the trainer/serving engine use, so an 8B load lands
    directly sharded instead of materializing replicas per device.
    Handles sharded checkpoints (model.safetensors.index.json) and tied
    embeddings (no lm_head.weight -> embed.T). ⊘ kserve huggingfaceserver.
    """
    import json
    import os

    import numpy as np
    import torch
    from safetensors import safe_open

    if cfg is None:
        cfg = config_from_hf(path)

    index_path = os.path.join(path, "model.safetensors.index.json")
    if os.path.exists(index_path):
        with open(index_path) as f:
            weight_map = json.load(f)["weight_map"]
    else:
        import glob

        files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
        if not files:
            raise FileNotFoundError(f"no *.safetensors under {path}")
        weight_map = {}
        for fn in files:
            with safe_open(fn, framework="pt") as f:
                for key in f.keys():
                    weight_map[key] = os.path.basename(fn)

    handles: dict[str, Any] = {}

    def tensor(name: str) -> np.ndarray:
        if name not in weight_map:
            raise KeyError(f"{name} missing from checkpoint {path}")
        fn = weight_map[name]
        if fn not in handles:
            handles[fn] = safe_open(os.path.join(path, fn), framework="pt")
        t = handles[fn].get_tensor(name)
        # torch tensors cover bf16 (numpy can't); fp32 round-trips exactly
        return t.to(torch.float32).numpy()

    try:
        pd = cfg.param_dtype
        embed = tensor("model.embed_tokens.weight").astype(pd)
        if "lm_head.weight" in weight_map:
            lm_head = tensor("lm_head.weight").T.astype(pd)
        else:  # tied embeddings (llama-2-style / tie_word_embeddings)
            lm_head = embed.T.copy()

        layers = {
            leaf: np.stack([
                (tensor(tpl.format(i=i)).T if transpose
                 else tensor(tpl.format(i=i))).astype(pd)
                for i in range(cfg.n_layers)])
            for leaf, (tpl, transpose) in _HF_LAYER_MAP.items()
        }
        params: Params = {
            "embed": embed,
            "layers": layers,
            "final_norm": tensor("model.norm.weight").astype(pd),
            "lm_head": lm_head,
        }
    finally:
        # release the mmapped shard files deterministically — a long-lived
        # serving process would otherwise hold every shard open forever
        for h in handles.values():
            close = getattr(h, "__exit__", None)
            if close is not None:
                close(None, None, None)
    expected = jax.eval_shape(lambda: init(jax.random.key(0), cfg))
    jax.tree.map(lambda got, want: None if got.shape == want.shape else
                 (_ for _ in ()).throw(ValueError(
                     f"shape mismatch: {got.shape} != {want.shape}")),
                 params, expected)

    if mesh is not None:
        from kubeflow_tpu.parallel.sharding import (shard_tree,
                                                    tree_logical_to_sharding)

        shardings = tree_logical_to_sharding(logical_axes(cfg), mesh, rules)
        params = shard_tree(params, shardings)
    else:
        params = jax.tree.map(jnp.asarray, params)
    return params, cfg


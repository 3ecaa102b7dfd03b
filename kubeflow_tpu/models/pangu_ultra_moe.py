"""openPangu Ultra MoE (`model_type: pangu_ultra_moe`) as a SERVED family:
latent attention (MLA) with a decoupled rotary key, sandwich norms, a
leading dense layer and sigmoid-routed expert layers with a shared expert,
on one expert-parallel rank that holds a share of each layer's experts.

Layer l on the residual x (published names in brackets):

    n     = rmsnorm(x)                                    [input_layernorm]
    c_q   = rmsnorm(n W_DQ);  [q_nope | q_rope] = c_q W_UQ, a head 128 + 64
    [c_kv* | k_r] = n W_DKV;  c_kv = rmsnorm(c_kv*);  k_rope = rope(k_r)
    [k_nope | v]  = c_kv W_UKV, a head 128 + 128
    a_h   = softmax((q_nope.k_nope + rope(q_rope).k_rope) / sqrt(192)) v
    x     = x + rmsnorm_post(concat_h(a_h) W_O)        [sandwich post-norm]
    x     = x + rmsnorm_post(ffn(rmsnorm(x)))

`ffn` is a SwiGLU for the first `first_k_dense_replace` layers; after them
it is the shared expert plus `routed_scaling_factor` times the chosen
experts' outputs, each weighted by its sigmoid score over the sum of the
chosen scores (`norm_topk_prob`); the router scores ALL the experts of the
layer (`n_router_experts`), and this rank computes the terms of the
`n_routed_experts` it holds (ops/moe.py::moe_share_mlp; the exchange that
would add the other ranks' terms is not served).

What that forces on a serving engine, and where it lives here:

  - ONE LATENT SLAB `[L, slots, max_len, 640]`: a token of a layer is its
    normed `c_kv` (512) and its rotated `k_rope` (64), 1,152 B in bfloat16
    where a cache of expanded keys and values would hold 64 KiB, padded to
    640 lanes (`slab_width`: the layout the decode kernel reads in place);
  - TWO ATTENTION PATHS. Prefill is EXPANDED: each head's 192-wide key
    and 128-wide value are made from the chunk's latent rows and its
    cached prefix's, and the chunk attends causally at `q_offset` in
    ops/flash_pallas.py's forward (q/k padded to 256 lanes). Decode is
    ABSORBED: `q_nope W_UK` folds into a 512-wide query beside the rotated
    one, scored against the slab's rows in place (ops/mla_decode.py), and
    the 512-wide output goes through `W_UV` and `W_O`;
  - the decode step's counters leave it with the new cache
    (`STEP_COUNTERS`): the routed experts' as Laguna counts them, and the
    context rows the decode kernel covered.

Weights are STACKED BY KIND (`attn` for every layer, `dense_ffn`,
`experts`) and read in place by index. The bodies carry the names the
engine calls on a family's module (serving/llm.py, "THE FAMILY SEAM"). Not
served, and refused by name where a deployment asks
(serving/llm_runtime.py): the MTP module (speculation), int8 weights or
cache, adapters, the prefix cache, the paged pool, a mesh.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_tpu.ops.moe import ShareArgs, moe_share_mlp
from kubeflow_tpu.ops.norms import rms_norm
from kubeflow_tpu.ops.rope import apply_rope

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class PanguUltraMoEConfig:
    """The published `config.json` keys under their own names, then how
    this repo runs it. `n_routed_experts` is the experts HELD here;
    `n_router_experts` the router's width (None: every expert is held)."""
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25600000.0
    rms_norm_eps: float = 1e-5
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    sandwich_norm: bool = True
    max_position_embeddings: int = 131072
    # -- how it runs here
    #: the post-norms' gain (Pangu Ultra's depth-scaled sandwich norm:
    #: c / sqrt(L) with c = 1 over the PUBLISHED 61 layers)
    post_norm_gain: float = 1.0 / math.sqrt(61)
    n_router_experts: int | None = None
    first_expert: int = 0
    dtype: Any = jnp.bfloat16          # weights, activations and matmuls
    decode_attention_impl: str = "auto"
    prefill_attention_impl: str = "auto"

    def __post_init__(self):
        if isinstance(self.dtype, str):
            object.__setattr__(self, "dtype", jnp.dtype(self.dtype).type)
        if self.n_router_experts is None:
            object.__setattr__(self, "n_router_experts",
                               self.n_routed_experts)
        if self.first_expert + self.n_routed_experts > self.n_router_experts:
            raise ValueError("the experts held lie outside the router's")
        for name in ("decode_attention_impl", "prefill_attention_impl"):
            if getattr(self, name) not in ("auto", "xla", "flash"):
                raise ValueError(f"unknown {name} {getattr(self, name)!r}")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values a token keeps a layer in the slab: c_kv, then k_rope."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def slab_width(self) -> int:
        """A slab row's lanes: the latent row padded with zeros to a whole
        number of 128 (576 -> 640). A 576-wide bfloat16 array is tiled
        otherwise than the decode kernel reads it, and the compiler copies
        the whole slab round every call (compiled for the v5e and read);
        at 640 it reads the slab in place."""
        return -(-self.latent_width // 128) * 128

    @property
    def n_dense(self) -> int:
        return min(self.first_k_dense_replace, self.num_hidden_layers)

    def is_dense(self, i: int) -> bool:
        return i < self.first_k_dense_replace

    @property
    def share_args(self) -> ShareArgs:
        return ShareArgs(self.n_router_experts, self.num_experts_per_tok,
                         self.n_routed_experts, self.first_expert,
                         scale=self.routed_scaling_factor,
                         renormalize=self.norm_topk_prob)


def stack_shapes(cfg: PanguUltraMoEConfig) -> dict[str, dict[str, tuple]]:
    """{stack: {leaf: (shape of ONE layer's slice, fan_in; None for a norm
    of ones, "gain" for a post-norm)}}. The draw's order is this dict's;
    `w_ukv` is drawn whole and held split as `w_uk` and `w_uv`."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    qr, r = cfg.q_lora_rank, cfg.kv_lora_rank
    out = {"attn": {
        "attn_norm": ((d,), None), "w_dq": ((d, qr), d),
        "q_norm": ((qr,), None), "w_uq": ((qr, h * cfg.qk_head_dim), qr),
        "w_dkv": ((d, cfg.latent_width), d), "kv_norm": ((r,), None),
        "w_ukv": ((r, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)), r),
        "w_o": ((h * cfg.v_head_dim, d), h * cfg.v_head_dim),
        "post_attn_norm": ((d,), "gain")}}
    if cfg.n_dense:
        f = cfg.intermediate_size
        out["dense_ffn"] = {"pre_mlp_norm": ((d,), None),
                            "w_gate": ((d, f), d), "w_up": ((d, f), d),
                            "w_down": ((f, d), f),
                            "post_mlp_norm": ((d,), "gain")}
    if cfg.num_hidden_layers > cfg.n_dense:
        e, f = cfg.n_routed_experts, cfg.moe_intermediate_size
        fs = cfg.n_shared_experts * f
        out["experts"] = {
            "pre_mlp_norm": ((d,), None),
            "router": ((d, cfg.n_router_experts), d),
            "w_gate": ((e, d, f), d), "w_up": ((e, d, f), d),
            "w_down": ((e, f, d), f),
            "shared_gate": ((d, fs), d), "shared_up": ((d, fs), d),
            "shared_down": ((fs, d), fs),
            "post_mlp_norm": ((d,), "gain")}
    return out


def _layers(cfg: PanguUltraMoEConfig, stack: str) -> int:
    return {"attn": cfg.num_hidden_layers, "dense_ffn": cfg.n_dense,
            "experts": cfg.num_hidden_layers - cfg.n_dense}[stack]


def _split_ukv(cfg: PanguUltraMoEConfig, w):
    """[..., r, h * (nope + v)] -> (w_uk [..., r, h, nope], w_uv [..., r,
    h, v]): each head's columns are its key's, then its value's."""
    h, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    w = w.reshape(w.shape[:-1] + (h, nope + cfg.v_head_dim))
    return w[..., :nope], w[..., nope:]


def init(rng: jax.Array, cfg: PanguUltraMoEConfig) -> Params:
    """Seeded weights IN THE SERVED DTYPE, a layer's slice of a leaf at a
    time (no float32 tree ever exists): normal / sqrt(fan_in) drawn in
    float32 and cast inside one compiled program; slice `i` of leaf number
    `n` (every leaf counts, norms too) from `fold_in(fold_in(rng, n), i)`.
    The embedding's rows are unit normal; norms are ones, post-norms
    `post_norm_gain`; the router float32."""
    def draw(key, shape, fan_in, dtype, layers, post=None):
        if fan_in is None or fan_in == "gain":
            value = 1.0 if fan_in is None else cfg.post_norm_gain
            return jnp.full((layers,) + shape if layers else shape, value,
                            dtype)

        def one(k):
            # the barrier keeps the scale one multiplication of the finished
            # normal wherever this compiles (the reference draws again)
            unit = jax.lax.optimization_barrier(
                jax.random.normal(k, shape, jnp.float32))
            return (unit * (fan_in ** -0.5)).astype(dtype)
        if not layers:
            return jax.jit(one)(key)
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(layers))
        return jax.jit(lambda ks: (post or (lambda w: w))(
            jax.lax.map(one, ks)))(keys)

    d, v = cfg.hidden_size, cfg.vocab_size
    params: Params = {
        "embed": draw(jax.random.fold_in(rng, 0), (v, d), 1, cfg.dtype, 0),
        "lm_head": draw(jax.random.fold_in(rng, 1), (d, v), d, cfg.dtype, 0),
        "final_norm": jnp.ones((d,), cfg.dtype)}
    leaf_no = 2
    for stack, leaves in stack_shapes(cfg).items():
        params[stack] = {}
        for leaf, (shape, fan_in) in leaves.items():
            dtype = jnp.float32 if leaf == "router" else cfg.dtype
            key = jax.random.fold_in(rng, leaf_no)
            leaf_no += 1
            if leaf == "w_ukv":
                params[stack]["w_uk"], params[stack]["w_uv"] = draw(
                    key, shape, fan_in, dtype, _layers(cfg, stack),
                    post=lambda w: _split_ukv(cfg, w))
                continue
            params[stack][leaf] = draw(key, shape, fan_in, dtype,
                                       _layers(cfg, stack))
    return params


def logical_axes(cfg: PanguUltraMoEConfig) -> Params:
    """Replicated: this family runs on one chip (no mesh is served)."""
    shapes = jax.eval_shape(lambda: init(jax.random.key(0), cfg))
    return jax.tree.map(lambda s: (None,) * len(s.shape), shapes)


# ---------------------------------------------------------------------------
# layer bodies
# ---------------------------------------------------------------------------

def _mm(x, w, dtype):
    return jnp.matmul(x.astype(dtype), w.astype(dtype))


def _rope(cfg: PanguUltraMoEConfig, x, positions):
    """x [B, S, H, rope] rotated whole (pairs (i, i + rope / 2))."""
    return apply_rope(x, positions, theta=float(cfg.rope_theta))


def _queries(cfg: PanguUltraMoEConfig, p: Params, i: int, n, positions):
    """n [B, S, D] -> (q_nope [B, S, H, nope], q_rope rotated [B, S, H,
    rope])."""
    b, s, _ = n.shape
    c_q = rms_norm(_mm(n, p["w_dq"][i], cfg.dtype), p["q_norm"][i],
                   cfg.rms_norm_eps)
    q = _mm(c_q, p["w_uq"][i], cfg.dtype).reshape(
        b, s, cfg.num_attention_heads, cfg.qk_head_dim)
    return (q[..., :cfg.qk_nope_head_dim],
            _rope(cfg, q[..., cfg.qk_nope_head_dim:], positions))


def _latent_rows(cfg: PanguUltraMoEConfig, p: Params, i: int, n, positions):
    """n [B, S, D] -> the slab's rows [B, S, slab_width]: the normed c_kv,
    k_rope rotated at its position, zeros."""
    r = cfg.kv_lora_rank
    ckr = _mm(n, p["w_dkv"][i], cfg.dtype)
    c_kv = rms_norm(ckr[..., :r], p["kv_norm"][i], cfg.rms_norm_eps)
    k_rope = _rope(cfg, ckr[..., None, r:], positions)[..., 0, :]
    pad = jnp.zeros(c_kv.shape[:-1] + (cfg.slab_width - cfg.latent_width,),
                    c_kv.dtype)
    return jnp.concatenate([c_kv, k_rope, pad], axis=-1).astype(cfg.dtype)


def _post(cfg: PanguUltraMoEConfig, out, gain):
    return (rms_norm(out, gain, cfg.rms_norm_eps) if cfg.sandwich_norm
            else out)


def _attn_out(cfg: PanguUltraMoEConfig, p: Params, i: int, x, o):
    """Heads' outputs [B, S, H, v] through W_O and the post-norm, onto
    the residual."""
    b, s = o.shape[:2]
    out = _mm(o.reshape(b, s, -1), p["w_o"][i], cfg.dtype)
    return x + _post(cfg, out, p["post_attn_norm"][i])


def prefill_attention(cfg: PanguUltraMoEConfig, q, k, v, q_offset: int,
                      impl: str):
    """Causal expanded attention: q [B, S, H, 192] at positions q_offset +
    i over k [B, T, H, 192], v [B, T, H, 128]."""
    scale = 1.0 / math.sqrt(cfg.qk_head_dim)
    if impl == "flash":
        from kubeflow_tpu.ops.flash_pallas import pallas_flash_attention

        return pallas_flash_attention(q, k, v, causal=True, scale=scale,
                                      q_offset=q_offset)
    from kubeflow_tpu.ops.attention import mha

    return mha(q, k, v, causal=True, scale=scale, q_offset=q_offset)


def _swiglu(x, w_gate, w_up, w_down, dtype):
    return _mm(jax.nn.silu(_mm(x, w_gate, dtype)) * _mm(x, w_up, dtype),
               w_down, dtype)


def _ffn(cfg: PanguUltraMoEConfig, i: int, params: Params, x):
    """x + post(FFN(rmsnorm(x))) -> (x, the routed experts' counters or
    None)."""
    if cfg.is_dense(i):
        p = params["dense_ffn"]
        h = rms_norm(x, p["pre_mlp_norm"][i], cfg.rms_norm_eps)
        with jax.named_scope("dense_ffn"):
            out = _swiglu(h, p["w_gate"][i], p["w_up"][i], p["w_down"][i],
                          cfg.dtype)
            return x + _post(cfg, out, p["post_mlp_norm"][i]), None
    p, j = params["experts"], i - cfg.n_dense
    h = rms_norm(x, p["pre_mlp_norm"][j], cfg.rms_norm_eps)
    # moe_share_mlp opens the scopes moe_route and moe_experts itself
    routed, counters = moe_share_mlp(
        h, p["router"][j], jnp.zeros((cfg.n_router_experts,), jnp.float32),
        p["w_gate"], p["w_up"], p["w_down"], cfg.share_args, cfg.dtype,
        layer=j)
    with jax.named_scope("moe_shared"):
        shared = _swiglu(h, p["shared_gate"][j], p["shared_up"][j],
                         p["shared_down"][j], cfg.dtype)
    return x + _post(cfg, routed + shared, p["post_mlp_norm"][j]), counters


def lm_head(params: Params, x, cfg: PanguUltraMoEConfig, rows=None):
    """final norm + head; `rows` [B] projects those positions only."""
    if rows is not None:
        x = jnp.take_along_axis(x, rows[:, None, None], axis=1,
                                mode="clip")[:, 0]
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.matmul(x.astype(cfg.dtype),
                          params["lm_head"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def _prefill_layers(params: Params, x, positions, cfg: PanguUltraMoEConfig,
                    prefix=None):
    """Every layer over a chunk x [B, S, D] at `positions` -> (x, the
    chunk's slab rows [L, B, S, slab_width]). With a prefix (the
    slab's rows of the P positions before the chunk, [L, B, P, ...]) the
    chunk attends prefix + chunk."""
    impl = resolve_prefill_attn(cfg)
    p = params["attn"]
    b, s, _ = x.shape
    h, r = cfg.num_attention_heads, cfg.kv_lora_rank
    q_offset = 0 if prefix is None else prefix.shape[2]
    new = []
    for i in range(cfg.num_hidden_layers):
        with jax.named_scope("mla_project"):
            n = rms_norm(x, p["attn_norm"][i], cfg.rms_norm_eps)
            q_nope, q_rope = _queries(cfg, p, i, n, positions)
            rows = _latent_rows(cfg, p, i, n, positions)
            new.append(rows)
            seen = (rows if prefix is None else jnp.concatenate(
                [prefix[i].astype(cfg.dtype), rows], axis=1))
            t = seen.shape[1]
            k_nope = jnp.einsum("btc,chd->bthd", seen[..., :r],
                                p["w_uk"][i].astype(cfg.dtype))
            v = jnp.einsum("btc,chd->bthd", seen[..., :r],
                           p["w_uv"][i].astype(cfg.dtype))
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                seen[:, :, None, r:cfg.latent_width],
                (b, t, h, cfg.qk_rope_head_dim))], axis=-1)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
        with jax.named_scope("mla_prefill"):
            o = prefill_attention(cfg, q, k, v, q_offset, impl)
        with jax.named_scope("mla_project"):
            x = _attn_out(cfg, p, i, x, o)
        x, _ = _ffn(cfg, i, params, x)
    return x, jnp.stack(new)


def prefill(params: Params, tokens, cfg: PanguUltraMoEConfig, lora=None,
            ids=None, logit_rows=None):
    """tokens [B, S] (right-padded) -> (logits [B, S, vocab] float32, or
    [B, vocab] of `logit_rows`; the latent rows [L, B, S, C]; {})."""
    _no_adapters(lora)
    x = params["embed"].astype(cfg.dtype)[tokens]
    x, rows = _prefill_layers(params, x, jnp.arange(tokens.shape[1]), cfg)
    return lm_head(params, x, cfg, logit_rows), rows, {}


def prefill_continue(params: Params, tail_tokens, k_prefix, v_prefix,
                     cfg: PanguUltraMoEConfig, lora=None, ids=None,
                     logit_rows=None):
    """The TAIL of a prompt whose first P positions are in the slab:
    k_prefix as `extract_prefix` gives it ([L, B, P, C]; v_prefix {}).
    Returns the tail's logits and its own latent rows."""
    _no_adapters(lora)
    positions = k_prefix.shape[2] + jnp.arange(tail_tokens.shape[1])
    x = params["embed"].astype(cfg.dtype)[tail_tokens]
    x, rows = _prefill_layers(params, x, positions, cfg, k_prefix)
    return lm_head(params, x, cfg, logit_rows), rows, {}


def apply(params: Params, tokens, cfg: PanguUltraMoEConfig, **_):
    """tokens [B, S] -> logits [B, S, vocab] float32: the plain forward
    pass (the einsum attention), for tests."""
    return prefill(params, tokens, dataclasses.replace(
        cfg, prefill_attention_impl="xla"))[0]


def loss_fn(params: Params, batch: dict[str, jax.Array],
            cfg: PanguUltraMoEConfig):
    """Next-token cross-entropy of the plain forward pass (the registry's
    contract; this family is served, no training cell runs it)."""
    tokens = batch["tokens"]
    logp = jax.nn.log_softmax(apply(params, tokens[:, :-1], cfg), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    return loss, {"loss": loss, "tokens": jnp.asarray(nll.size, jnp.float32)}


# ---------------------------------------------------------------------------
# the latent slab
# ---------------------------------------------------------------------------

def init_cache(cfg: PanguUltraMoEConfig, n_slots: int, max_len: int,
               kv_quantize: str | None = None,
               chunk: int | None = None) -> Params:
    """{"kv": [L, slots, max_len, slab_width]} in the model dtype."""
    if kv_quantize is not None:
        raise ValueError("the pangu_ultra_moe family keeps its latent cache "
                         "in the model dtype (no int8 latent kernel)")
    return {"kv": jnp.zeros((cfg.num_hidden_layers, n_slots, max_len,
                             cfg.slab_width), cfg.dtype)}


def cache_stats(cache: Params) -> dict[str, Any]:
    """metrics(): the slab's bytes."""
    return {"kv_bytes_latent": int(cache["kv"].nbytes)}


def cache_write(cache: Params, slot, start: int, count: int, ks, vs, *,
                kv_quantize: str | None = None) -> Params:
    """One prompt's slab rows [L, rows, slab_width] into a slot at positions
    [start, start + rows) (start + rows <= max_len: the engine's chain
    never writes past the slab)."""
    out = dict(cache)
    # an update of a slice, not a scatter: a scatter of a long prefix's
    # rows made the compiler copy the whole slab twice (compiled for the
    # v5e and read: 4.7 GB of temporaries at a 10k prefix)
    out["kv"] = jax.lax.dynamic_update_slice(
        cache["kv"], ks[:, None].astype(cache["kv"].dtype),
        (0, slot, start, 0))
    return out


def extract_prefix(cfg: PanguUltraMoEConfig, cache: Params, slot, p: int, *,
                   kv_quantize: str | None = None, dtype=None):
    """A slot's first `p` positions as prefill_continue takes its prefix:
    ([L, 1, p, C], {})."""
    rows = jax.lax.dynamic_index_in_dim(cache["kv"], slot, axis=1,
                                        keepdims=False)[:, :p]
    return rows[:, None], {}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

#: a decode step's counts: (name in metrics(), how the engine folds the
#: steps: "sum", or "last" seen). The first four over the expert layers;
#: `mla_context_tokens` the live rows the decode attention covered, over
#: the slots and the layers
STEP_COUNTERS = (("moe_assignments", "sum"), ("moe_expert_visits", "sum"),
                 ("moe_rows_dropped", "sum"),
                 ("moe_load_max_over_mean", "last"),
                 ("mla_context_tokens", "sum"))


def _fold_counters(seen: list[dict], context) -> jax.Array:
    if not seen:
        return jnp.stack([jnp.zeros((), jnp.float32)] * 4 + [context])
    col = lambda k: jnp.stack([c[k] for c in seen])   # noqa: E731
    return jnp.stack([jnp.sum(col("rows_here")),
                      jnp.sum(col("experts_touched")),
                      jnp.sum(col("rows_dropped")),
                      jnp.max(col("load_max_over_mean")), context])


def latent_decode(cfg: PanguUltraMoEConfig, q, slab, lengths, *, layer: int,
                  span: int, impl: str):
    """The absorbed attention of a decode step: q [B, H, slab_width]
    against the slab's layer `layer` -> [B, H, latent]."""
    from kubeflow_tpu.ops import mla_decode

    fn = (mla_decode.mla_decode_attention if impl == "flash"
          else mla_decode.mla_decode_xla)
    return fn(q, slab, lengths, layer=layer, latent=cfg.kv_lora_rank,
              scale=1.0 / math.sqrt(cfg.qk_head_dim), span=span)


def decode_step(params: Params, last_tokens, cache: Params, lengths,
                cfg: PanguUltraMoEConfig, span: int | None = None, lora=None,
                ids=None, active=None):
    """One continuous-batching decode step over all cache slots:
    last_tokens [B], lengths [B] (where this step's latent row is written)
    -> (logits [B, vocab] float32, the new cache, with the step's
    STEP_COUNTERS under "counters"). A slot that is not `active` attends
    nothing; its junk row lands on its own (dead) rows. `span` bounds the
    rows the attention covers."""
    _no_adapters(lora)
    b = last_tokens.shape[0]
    x = params["embed"].astype(cfg.dtype)[last_tokens][:, None]   # [B,1,D]
    max_len = cache["kv"].shape[2]
    span = max_len if span is None else min(span, max_len)
    slots = jnp.arange(b)
    positions = lengths[:, None]                                  # [B, 1]
    seen_to = lengths if active is None else jnp.where(active, lengths, -1)
    impl = resolve_decode_attn(cfg)
    p = params["attn"]
    cache = dict(cache)
    seen = []
    for i in range(cfg.num_hidden_layers):
        with jax.named_scope("mla_project"):
            n = rms_norm(x, p["attn_norm"][i], cfg.rms_norm_eps)
            q_nope, q_rope = _queries(cfg, p, i, n, positions)
            rows = _latent_rows(cfg, p, i, n, positions)[:, 0]
            # drop mode: a dead slot's stale length may sit at max_len
            cache["kv"] = cache["kv"].at[i, slots, lengths].set(
                rows, mode="drop")
            q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0],
                               p["w_uk"][i].astype(cfg.dtype),
                               preferred_element_type=jnp.float32)
            q = jnp.concatenate([
                q_lat.astype(cfg.dtype), q_rope[:, 0],
                jnp.zeros(q_rope.shape[:1] + q_rope.shape[2:3]
                          + (cfg.slab_width - cfg.latent_width,),
                          cfg.dtype)], axis=-1)                   # [B,H,C]
        with jax.named_scope("mla_decode"):
            o = latent_decode(cfg, q, cache["kv"], seen_to, layer=i,
                              span=span, impl=impl)
        with jax.named_scope("mla_project"):
            o = jnp.einsum("bhc,chd->bhd", o, p["w_uv"][i].astype(cfg.dtype))
            x = _attn_out(cfg, p, i, x, o[:, None])
        x, counters = _ffn(cfg, i, params, x)
        if counters is not None:
            seen.append(counters)
    context = (cfg.num_hidden_layers * jnp.sum(
        jnp.clip(seen_to + 1, 0, span))).astype(jnp.float32)
    cache["counters"] = _fold_counters(seen, context)
    return lm_head(params, x, cfg)[:, 0], cache


def verify_step(*_, **__):
    raise NotImplementedError(
        "the pangu_ultra_moe family has no speculative verify step: its "
        "drafter would be the MTP module, which is not served")


# ---------------------------------------------------------------------------
# the rest of the seam
# ---------------------------------------------------------------------------

#: no leaf of this family is served quantized, so none takes an adapter
QUANT_LEAVES: tuple[str, ...] = ()


def _no_int8(*_, **__):
    raise NotImplementedError("the pangu_ultra_moe family keeps its latent "
                              "cache in the model dtype")


quantize_kv = dequantize_kv = _no_int8


def _no_adapters(lora) -> None:
    if lora is not None:
        raise NotImplementedError(
            "the pangu_ultra_moe family serves no adapters")


def quantize_params(params: Params) -> Params:
    raise NotImplementedError(
        "the pangu_ultra_moe family is served in its published bfloat16: "
        "int8 experts need a grouped matmul that dequantizes its groups")


def logical_axes_for(params: Params, cfg: PanguUltraMoEConfig) -> Params:
    raise NotImplementedError("the pangu_ultra_moe family is served on one "
                              "chip: its experts have no exchange")


def cache_kv_spec(name: str, axis: str = "tensor"):
    raise NotImplementedError("the pangu_ultra_moe family is served on one "
                              "chip: its latent slab has no mesh layout")


def resolve_decode_attn(cfg: PanguUltraMoEConfig) -> str:
    """"flash" (ops/mla_decode.py) on a TPU target, else "xla": every
    head reads one shared latent row, which the kernel tiles whole."""
    from kubeflow_tpu.ops import pallas_compat

    return pallas_compat.resolve_flash_impl(
        cfg.decode_attention_impl, head_dim=cfg.latent_width, n_kv_heads=1)


def resolve_prefill_attn(cfg: PanguUltraMoEConfig) -> str:
    """"flash" (ops/flash_pallas.py's forward, q/k of 192 padded to 256
    lanes beside values of 128) on a TPU target, else "xla"."""
    from kubeflow_tpu.ops import pallas_compat

    return pallas_compat.resolve_flash_impl(
        cfg.prefill_attention_impl, head_dim=cfg.v_head_dim,
        n_kv_heads=cfg.num_attention_heads)

"""Laguna: a decoder whose layers differ in kind, as a SERVED family.

Published shape (`model_type: laguna`): pre-norm residual layers; attention
is grouped-query with 8 KV heads of 128 and a per-head sigmoid OUTPUT GATE
(`g = sigmoid(RMSNorm(x) W_g)`, `W_g` [hidden, heads], each head's output
times its g before `W_o`); `layer_types[l]` says FULL or SLIDING attention
(period of 4: full, sliding x3), and the two kinds have their own head
counts (`num_attention_heads_per_layer`) and their own rotary description
(`rope_parameters`: sliding layers rotate the whole head at theta 10000;
full layers rotate the first half of each head with YaRN frequencies);
`mlp_layer_types[l]` says DENSE SwiGLU or SPARSE: a sigmoid router over all
experts, the top k chosen, their scores normalised and scaled, each expert
a SwiGLU whose OUTPUT takes the weight, plus one shared SwiGLU on every
token.

What that forces on a serving engine, and where it lives here:

  - weights STACKED BY KIND (`full`, `sliding`, `dense_ffn`, `experts`):
    layers of one kind share shapes, layers of different kinds do not; the
    `plan` says which stack and which index each layer reads, and the
    layer loop is a Python loop over it. A stack is read in place by its
    index (a static slice that feeds a matmul; the grouped matmul takes
    the whole `[layers * experts, ...]` stack and group sizes that are
    zero outside the layer's own experts);
  - TWO CACHE SLABS by layer kind: the full layers' `[n_full, slots,
    max_len, kv, hd]` and the sliding layers' `[n_sliding, slots, R, kv,
    hd]`, a RING of `R` = window + the largest prefill chunk, rounded up
    to the decode kernel's KV block, written at `position mod R`;
  - the routed experts' counters leave a decode step with its new cache
    (`STEP_COUNTERS`).

The bodies carry the names the engine calls on a family's module
(serving/llm.py, "THE FAMILY SEAM"). Not served by this family, and refused
by name where a deployment asks (serving/llm_runtime.py): int8 weights,
speculative verify, adapters, the prefix cache, the paged pool, a mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models import llama
from kubeflow_tpu.ops.moe import ShareArgs, moe_share_mlp
from kubeflow_tpu.ops.norms import rms_norm
from kubeflow_tpu.ops.rope import Yarn, apply_rope

Params = dict[str, Any]

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"

_DEFAULT_ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 4096, "beta_slow": 1,
           "beta_fast": 64, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1},
}


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The published `config.json` keys under their own names (the lists
    may be longer than `num_hidden_layers`: a cut in depth keeps the first
    layers), then how this repo runs it."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    sliding_window: int = 512
    layer_types: tuple[str, ...] | None = None
    mlp_layer_types: tuple[str, ...] | None = None
    num_attention_heads_per_layer: tuple[int, ...] | None = None
    rope_parameters: Any = None
    # -- how it runs here
    dtype: Any = jnp.bfloat16          # weights, activations and matmuls
    decode_attention_impl: str = "auto"
    prefill_attention_impl: str = "auto"

    def __post_init__(self):
        n = self.num_hidden_layers
        put = lambda k, v: object.__setattr__(self, k, v)   # noqa: E731
        put("layer_types", tuple(
            self.layer_types or [FULL if i % 4 == 0 else SLIDING
                                 for i in range(n)])[:n])
        put("mlp_layer_types", tuple(
            self.mlp_layer_types or [DENSE if i == 0 else SPARSE
                                     for i in range(n)])[:n])
        put("num_attention_heads_per_layer", tuple(
            self.num_attention_heads_per_layer
            or [self.num_attention_heads if t == FULL else 64
                for t in self.layer_types])[:n])
        put("rope_parameters", {
            k: dict(v) for k, v in (self.rope_parameters
                                    or _DEFAULT_ROPE).items()
            if k in (FULL, SLIDING)})
        if isinstance(self.dtype, str):
            put("dtype", jnp.dtype(self.dtype).type)
        if min(len(self.layer_types), len(self.mlp_layer_types),
               len(self.num_attention_heads_per_layer)) < n:
            raise ValueError("a per-layer list is shorter than the depth")
        for kind in (FULL, SLIDING):
            counts = {h for h, t in zip(self.num_attention_heads_per_layer,
                                        self.layer_types) if t == kind}
            if len(counts) > 1:
                raise ValueError(f"{kind} layers differ in heads: {counts}")
        for name in ("decode_attention_impl", "prefill_attention_impl"):
            if getattr(self, name) not in ("auto", "xla", "flash"):
                raise ValueError(f"unknown {name} {getattr(self, name)!r}")

    # the names the engine and the kernel-selection rule read
    @property
    def n_kv_heads(self) -> int:
        return self.num_key_value_heads

    def heads(self, kind: str) -> int:
        return next(h for h, t in zip(self.num_attention_heads_per_layer,
                                      self.layer_types) if t == kind)

    def rope(self, kind: str) -> dict[str, Any]:
        """apply_rope's keywords for a layer kind."""
        r = self.rope_parameters[kind]
        rd = int(self.head_dim * r.get("partial_rotary_factor", 1))
        yarn = None
        if r.get("rope_type", "default") == "yarn":
            yarn = Yarn(r["factor"], r["original_max_position_embeddings"],
                        r.get("beta_fast", 32), r.get("beta_slow", 1),
                        r.get("attention_factor"))
        elif r.get("rope_type", "default") != "default":
            raise ValueError(f"rope_type {r['rope_type']!r}")
        return {"theta": float(r["rope_theta"]),
                "rotary_dim": None if rd == self.head_dim else rd,
                "yarn": yarn}

    @property
    def share_args(self) -> ShareArgs:
        return ShareArgs(self.num_experts, self.num_experts_per_tok,
                         self.num_experts, 0,
                         scale=self.moe_routed_scaling_factor)


class Layer(NamedTuple):
    attn: str        # "full" | "sliding": the stack its attention reads
    attn_at: int     # ... and the index in it (also the cache slab's layer)
    ffn: str         # "dense_ffn" | "experts"
    ffn_at: int


def plan(cfg: LagunaConfig) -> list[Layer]:
    seen = {"full": 0, "sliding": 0, "dense_ffn": 0, "experts": 0}
    out = []
    for lt, mt in zip(cfg.layer_types, cfg.mlp_layer_types):
        a = "full" if lt == FULL else "sliding"
        f = "dense_ffn" if mt == DENSE else "experts"
        out.append(Layer(a, seen[a], f, seen[f]))
        seen[a] += 1
        seen[f] += 1
    return out


def _counts(cfg: LagunaConfig) -> dict[str, int]:
    p = plan(cfg)
    return {k: sum((l.attn == k) + (l.ffn == k) for l in p)
            for k in ("full", "sliding", "dense_ffn", "experts")}


def stack_shapes(cfg: LagunaConfig) -> dict[str, dict[str, tuple]]:
    """{stack: {leaf: (shape of ONE layer's slice, fan_in or None)}}; a
    norm (fan_in None) is ones. The draw's order is this dict's."""
    d, kv = cfg.hidden_size, cfg.num_key_value_heads * cfg.head_dim
    e, f, fs = (cfg.num_experts, cfg.moe_intermediate_size,
                cfg.shared_expert_intermediate_size)

    def attn(nh):
        return {"attn_norm": ((d,), None), "wq": ((d, nh * cfg.head_dim), d),
                "wk": ((d, kv), d), "wv": ((d, kv), d),
                "wg": ((d, nh), d), "wo": ((nh * cfg.head_dim, d),
                                           nh * cfg.head_dim)}
    out = {}
    n = _counts(cfg)
    if n["full"]:
        out["full"] = attn(cfg.heads(FULL))
    if n["sliding"]:
        out["sliding"] = attn(cfg.heads(SLIDING))
    if n["dense_ffn"]:
        ff = cfg.intermediate_size
        out["dense_ffn"] = {"mlp_norm": ((d,), None),
                            "w_gate": ((d, ff), d), "w_up": ((d, ff), d),
                            "w_down": ((ff, d), ff)}
    if n["experts"]:
        out["experts"] = {
            "mlp_norm": ((d,), None), "router": ((d, e), d),
            "w_gate": ((e, d, f), d), "w_up": ((e, d, f), d),
            "w_down": ((e, f, d), f),
            "shared_gate": ((d, fs), d), "shared_up": ((d, fs), d),
            "shared_down": ((fs, d), fs)}
    return out


def _leaf_dtype(cfg: LagunaConfig, leaf: str):
    # the router chooses: float32 (the choice is discontinuous)
    return jnp.float32 if leaf == "router" else cfg.dtype


def init(rng: jax.Array, cfg: LagunaConfig) -> Params:
    """Seeded weights IN THE SERVED DTYPE, a layer's slice of a leaf at a
    time: normal / sqrt(fan_in) drawn in float32 and cast inside one
    compiled program, so no float32 tree (15.5 GB at the benchmark's cut)
    ever exists; the key of slice `i` of leaf number `n` is
    `fold_in(fold_in(rng, n), i)`. Norms are ones."""
    def draw(key, shape, fan_in, dtype, layers):
        if fan_in is None:
            return jnp.ones((layers,) + shape if layers else shape, dtype)

        def one(k):
            # the barrier keeps the compiler from folding the scale into
            # the normal's own arithmetic: the values are then the same
            # bits wherever this is compiled (the plain reference draws
            # them again by its own code)
            unit = jax.lax.optimization_barrier(
                jax.random.normal(k, shape, jnp.float32))
            return (unit * (fan_in ** -0.5)).astype(dtype)
        if not layers:
            return jax.jit(one)(key)
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(layers))
        return jax.jit(lambda ks: jax.lax.map(one, ks))(keys)

    d, v = cfg.hidden_size, cfg.vocab_size
    n = _counts(cfg)
    params: Params = {}
    leaf_no = 0
    # the embedding's rows are unit normal: a token's vector, not a matmul
    for name, shape, fan_in in (("embed", (v, d), 1), ("lm_head", (d, v), d)):
        params[name] = draw(jax.random.fold_in(rng, leaf_no), shape, fan_in,
                            cfg.dtype, 0)
        leaf_no += 1
    params["final_norm"] = jnp.ones((d,), cfg.dtype)
    for stack, leaves in stack_shapes(cfg).items():
        params[stack] = {}
        for leaf, (shape, fan_in) in leaves.items():
            params[stack][leaf] = draw(
                jax.random.fold_in(rng, leaf_no), shape, fan_in,
                _leaf_dtype(cfg, leaf), n[stack])
            leaf_no += 1
    return params


def logical_axes(cfg: LagunaConfig) -> Params:
    """Replicated: this family runs on one chip (no mesh is served)."""
    leaves = stack_shapes(cfg)
    out: Params = {"embed": (None, None), "lm_head": (None, None),
                   "final_norm": (None,)}
    for stack, group in leaves.items():
        out[stack] = {leaf: (None,) * (len(shape) + 1)
                      for leaf, (shape, _) in group.items()}
    return out


# ---------------------------------------------------------------------------
# layer bodies
# ---------------------------------------------------------------------------

class _AttnDims(NamedTuple):
    """What llama's attention seams read of a config, for one layer kind."""
    n_heads: int
    n_kv_heads: int
    head_dim: int
    dtype: Any


def _dims(cfg: LagunaConfig, layer: Layer) -> _AttnDims:
    return _AttnDims(cfg.heads(FULL if layer.attn == "full" else SLIDING),
                     cfg.num_key_value_heads, cfg.head_dim, cfg.dtype)


def _mm(x, w, dtype):
    return jnp.matmul(x.astype(dtype), w.astype(dtype))


def _project(cfg: LagunaConfig, layer: Layer, params: Params, x, positions):
    """x [B, S, D] -> (q [B, S, nh, hd], k, v [B, S, kv, hd], gate
    [B, S, nh] float32), q and k rotated as the layer's kind says."""
    b, s, _ = x.shape
    p, i = params[layer.attn], layer.attn_at
    dims = _dims(cfg, layer)
    h = rms_norm(x, p["attn_norm"][i], cfg.rms_norm_eps)
    q = _mm(h, p["wq"][i], cfg.dtype).reshape(b, s, dims.n_heads,
                                              dims.head_dim)
    k = _mm(h, p["wk"][i], cfg.dtype).reshape(b, s, dims.n_kv_heads,
                                              dims.head_dim)
    v = _mm(h, p["wv"][i], cfg.dtype).reshape(b, s, dims.n_kv_heads,
                                              dims.head_dim)
    with jax.named_scope("attn_gate"):
        gate = jax.nn.sigmoid(_mm(h, p["wg"][i], cfg.dtype)
                              .astype(jnp.float32))
    rope = cfg.rope(FULL if layer.attn == "full" else SLIDING)
    return (apply_rope(q, positions, **rope),
            apply_rope(k, positions, **rope), v, gate)


def _attn_out(cfg: LagunaConfig, layer: Layer, params: Params, x, out, gate):
    """Heads' outputs [B, S, nh, hd] times their gates, through W_o, onto
    the residual."""
    b, s = out.shape[:2]
    with jax.named_scope("attn_gate"):
        out = (out.astype(jnp.float32) * gate[..., None]).astype(cfg.dtype)
    return x + _mm(out.reshape(b, s, -1),
                   params[layer.attn]["wo"][layer.attn_at], cfg.dtype)


def _swiglu(x, w_gate, w_up, w_down, dtype):
    return _mm(jax.nn.silu(_mm(x, w_gate, dtype)) * _mm(x, w_up, dtype),
               w_down, dtype)


def _ffn(cfg: LagunaConfig, layer: Layer, params: Params, x):
    """x + FFN(RMSNorm(x)) -> (x, the routed experts' counters or None)."""
    p, i = params[layer.ffn], layer.ffn_at
    h = rms_norm(x, p["mlp_norm"][i], cfg.rms_norm_eps)
    if layer.ffn == "dense_ffn":
        with jax.named_scope("dense_ffn"):
            return x + _swiglu(h, p["w_gate"][i], p["w_up"][i],
                               p["w_down"][i], cfg.dtype), None
    # moe_share_mlp opens the scopes moe_route and moe_experts itself
    routed, counters = moe_share_mlp(
        h, p["router"][i], jnp.zeros((cfg.num_experts,), jnp.float32),
        p["w_gate"], p["w_up"], p["w_down"], cfg.share_args, cfg.dtype,
        layer=i)
    with jax.named_scope("moe_shared"):
        shared = _swiglu(h, p["shared_gate"][i], p["shared_up"][i],
                         p["shared_down"][i], cfg.dtype)
    return x + routed + shared, counters


#: a decode step's counts, over its sparse layers: (name in metrics(), how
#: the engine folds the steps: "sum", or "last" seen)
STEP_COUNTERS = (("moe_assignments", "sum"), ("moe_expert_visits", "sum"),
                 ("moe_rows_dropped", "sum"),
                 ("moe_load_max_over_mean", "last"))


def _fold_counters(seen: list[dict]) -> jax.Array:
    if not seen:
        return jnp.zeros((len(STEP_COUNTERS),), jnp.float32)
    col = lambda k: jnp.stack([c[k] for c in seen])   # noqa: E731
    return jnp.stack([jnp.sum(col("rows_here")),
                      jnp.sum(col("experts_touched")),
                      jnp.sum(col("rows_dropped")),
                      jnp.max(col("load_max_over_mean"))])


def lm_head(params: Params, x, cfg: LagunaConfig, rows=None):
    """final norm + head; `rows` [B] projects those positions only (a
    prefill samples one token a prompt)."""
    if rows is not None:
        x = jnp.take_along_axis(x, rows[:, None, None], axis=1,
                                mode="clip")[:, 0]
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.matmul(x.astype(cfg.dtype),
                          params["lm_head"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def _attn_scope(layer: Layer) -> str:
    return "attn_full" if layer.attn == "full" else "attn_window"


def _window(cfg: LagunaConfig, layer: Layer) -> int | None:
    return None if layer.attn == "full" else cfg.sliding_window


def _prefill_layers(params: Params, x, positions, cfg: LagunaConfig,
                    k_prefix=None, v_prefix=None):
    """Every layer over a chunk x [B, S, D] at `positions` -> (x, ks, vs):
    the chunk's own K and V by slab, {"full": [n_full, B, S, kv, hd],
    "window": [n_sliding, B, S, kv, hd]}. With a prefix (what
    `extract_prefix` hands out: the full layers' P rows, the sliding
    layers' last min(P, window)) the chunk attends prefix + chunk."""
    impl = resolve_prefill_attn(cfg)
    new = {"full": ([], []), "window": ([], [])}
    for layer in plan(cfg):
        slab = "full" if layer.attn == "full" else "window"
        with jax.named_scope(_attn_scope(layer)):
            q, k, v, gate = _project(cfg, layer, params, x, positions)
            new[slab][0].append(k)
            new[slab][1].append(v)
            k_all, v_all, q_offset = k, v, 0
            if k_prefix is not None:
                kp = k_prefix[slab][layer.attn_at].astype(cfg.dtype)
                vp = v_prefix[slab][layer.attn_at].astype(cfg.dtype)
                k_all = jnp.concatenate([kp, k], axis=1)
                v_all = jnp.concatenate([vp, v], axis=1)
                q_offset = kp.shape[1]
            out = llama.prefill_attention(
                _dims(cfg, layer), q, k_all, v_all, q_offset=q_offset,
                impl=impl, window=_window(cfg, layer))
            x = _attn_out(cfg, layer, params, x, out, gate)
        x, _ = _ffn(cfg, layer, params, x)

    def stacked(slab, n):
        rows = new[slab][n]
        if rows:
            return jnp.stack(rows)
        b, s = x.shape[:2]
        return jnp.zeros((0, b, s, cfg.num_key_value_heads, cfg.head_dim),
                         cfg.dtype)
    return (x, {s: stacked(s, 0) for s in new},
            {s: stacked(s, 1) for s in new})


def prefill(params: Params, tokens, cfg: LagunaConfig, lora=None, ids=None,
            logit_rows=None):
    """tokens [B, S] (right-padded) -> (logits [B, S, vocab] float32, or
    [B, vocab] of `logit_rows`; ks, vs by slab, see _prefill_layers)."""
    _no_adapters(lora)
    x = params["embed"].astype(cfg.dtype)[tokens]
    x, ks, vs = _prefill_layers(params, x, jnp.arange(tokens.shape[1]), cfg)
    return lm_head(params, x, cfg, logit_rows), ks, vs


def prefill_continue(params: Params, tail_tokens, k_prefix, v_prefix,
                     cfg: LagunaConfig, lora=None, ids=None,
                     logit_rows=None):
    """The TAIL of a prompt whose first P tokens are in the cache (the
    continuation chain of a prompt longer than the largest bucket):
    k_prefix / v_prefix as `extract_prefix` gives them. Returns the tail's
    logits and its own K and V by slab."""
    _no_adapters(lora)
    p = k_prefix["full"].shape[2] if k_prefix["full"].shape[0] \
        else k_prefix["window"].shape[2]
    positions = p + jnp.arange(tail_tokens.shape[1])
    x = params["embed"].astype(cfg.dtype)[tail_tokens]
    x, ks, vs = _prefill_layers(params, x, positions, cfg, k_prefix,
                                v_prefix)
    return lm_head(params, x, cfg, logit_rows), ks, vs


def apply(params: Params, tokens, cfg: LagunaConfig, **_):
    """tokens [B, S] -> logits [B, S, vocab] float32: the plain forward
    pass (the einsum attention), for tests."""
    return prefill(params, tokens, dataclasses.replace(
        cfg, prefill_attention_impl="xla"))[0]


def loss_fn(params: Params, batch: dict[str, jax.Array], cfg: LagunaConfig):
    """Next-token cross-entropy of the plain forward pass (the registry's
    contract; this family is served, no training cell runs it)."""
    tokens = batch["tokens"]
    logp = jax.nn.log_softmax(apply(params, tokens[:, :-1], cfg), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    return loss, {"loss": loss, "tokens": jnp.asarray(nll.size, jnp.float32)}


# ---------------------------------------------------------------------------
# the cache: two slabs by layer kind
# ---------------------------------------------------------------------------

_SLABS = {"full": ("k", "v", "k_s", "v_s"),
          "window": ("kw", "vw", "kw_s", "vw_s")}


def ring_rows(cfg: LagunaConfig, chunk: int, max_len: int) -> int:
    """Rows a slot of the sliding layers' slab holds: the window and the
    largest chunk one prefill program writes (its junk rows past a
    prompt's end must not land on a row a query can still see), rounded
    up to the decode kernel's KV block; never more than max_len."""
    from kubeflow_tpu.ops.flash_decode import DEFAULT_BLOCK_KV

    need = cfg.sliding_window + chunk
    block = min(DEFAULT_BLOCK_KV, max_len)
    return min(-(-need // block) * block, max_len)


def init_cache(cfg: LagunaConfig, n_slots: int, max_len: int,
               kv_quantize: str | None = None,
               chunk: int | None = None) -> Params:
    """{"k", "v"(, "k_s", "v_s")}: the full layers' slab, llama's layout
    `[n_full, slots, max_len, kv, hd]` (int8 payloads with lane-major
    float32 scales `[n_full, slots, kv, max_len]`); {"kw", "vw"(, "kw_s",
    "vw_s")}: the sliding layers' ring `[n_sliding, slots, R, kv, hd]`,
    R = ring_rows(chunk)."""
    n = _counts(cfg)
    kv, hd = cfg.num_key_value_heads, cfg.head_dim
    rows = {"full": max_len,
            "window": ring_rows(cfg, max_len if chunk is None else chunk,
                                max_len)}
    out = {}
    for slab, names in _SLABS.items():
        layers = n["full" if slab == "full" else "sliding"]
        shape = (layers, n_slots, rows[slab], kv, hd)
        if kv_quantize == "int8":
            sshape = (layers, n_slots, kv, rows[slab])
            out.update({names[0]: jnp.zeros(shape, jnp.int8),
                        names[1]: jnp.zeros(shape, jnp.int8),
                        names[2]: jnp.zeros(sshape, jnp.float32),
                        names[3]: jnp.zeros(sshape, jnp.float32)})
        else:
            out.update({names[0]: jnp.zeros(shape, cfg.dtype),
                        names[1]: jnp.zeros(shape, cfg.dtype)})
    return out


def cache_stats(cache: Params) -> dict[str, Any]:
    """metrics(): each slab's bytes and the ring's length."""
    def nbytes(names):
        return int(sum(cache[n].nbytes for n in names if n in cache))
    return {"kv_bytes_full": nbytes(_SLABS["full"]),
            "kv_bytes_window": nbytes(_SLABS["window"]),
            "kv_window_ring_tokens": int(cache["kw"].shape[2])}


def _runs(positions: np.ndarray, ring: int) -> list[tuple[int, int, int]]:
    """Static positions -> [(first offset in `positions`, ring row, count)]
    of maximal runs that lie contiguous in the ring."""
    out, i = [], 0
    while i < len(positions):
        row = int(positions[i]) % ring
        n = min(len(positions) - i, ring - row)
        out.append((i, row, n))
        i += n
    return out


def cache_write(cache: Params, slot, start: int, count: int, ks, vs, *,
                kv_quantize: str | None = None) -> Params:
    """One prompt's K and V by slab ({"full": [n_full, rows, kv, hd],
    "window": [n_sliding, rows_w, kv, hd]}) into a slot, for positions
    [start, start + count): the full slab takes its `count` rows there;
    the ring takes the window leaf's rows as the LAST rows of that span
    (a prefix handed back holds only its last `window`), each at its
    position mod R, the newest R of them if there are more."""
    out = dict(cache)
    quant = kv_quantize == "int8"

    def put(names, rows, pos0, ring):
        n = rows[0].shape[1]
        keep = min(n, ring)
        positions = np.arange(pos0 + n - keep, pos0 + n)
        for name, sname, leaf in zip(names[0::2], names[1::2], rows):
            leaf = leaf[:, n - keep:]
            if quant:
                leaf, scales = llama.quantize_kv(leaf)
                scales = jnp.swapaxes(scales, 1, 2)   # lane-major
            else:
                leaf = leaf.astype(out[name].dtype)
            for at, row, cnt in _runs(positions, ring):
                out[name] = out[name].at[:, slot, row:row + cnt].set(
                    leaf[:, at:at + cnt])
                if quant:
                    out[sname] = out[sname].at[
                        :, slot, :, row:row + cnt].set(
                            scales[:, :, at:at + cnt])

    if ks["full"].shape[0]:
        put(("k", "k_s", "v", "v_s"), (ks["full"], vs["full"]), start,
            cache["k"].shape[2])
    if ks["window"].shape[0]:
        n_w = ks["window"].shape[1]
        put(("kw", "kw_s", "vw", "vw_s"), (ks["window"], vs["window"]),
            start + count - n_w, cache["kw"].shape[2])
    return out


def extract_prefix(cfg: LagunaConfig, cache: Params, slot, p: int, *,
                   kv_quantize: str | None = None, dtype=None):
    """A slot's first `p` positions as prefill_continue takes its prefix:
    (k, v), each {"full": [n_full, 1, p, kv, hd], "window": [n_sliding, 1,
    min(p, window), kv, hd]} (the ring's rows of positions [p - that, p)),
    dequantized."""
    quant = kv_quantize == "int8"

    def take(name, sname, rows):
        slab = jax.lax.dynamic_index_in_dim(cache[name], slot, axis=1,
                                            keepdims=False)
        got = jnp.take(slab, rows, axis=1)
        if quant:
            sc = jax.lax.dynamic_index_in_dim(cache[sname], slot, axis=1,
                                              keepdims=False)
            got = llama.dequantize_kv(
                got, jnp.swapaxes(jnp.take(sc, rows, axis=2), 1, 2), dtype)
        return got[:, None]

    ring = cache["kw"].shape[2]
    n_w = min(p, cfg.sliding_window, ring)
    rows = {"full": jnp.arange(p),
            "window": jnp.asarray(np.arange(p - n_w, p) % ring)}
    k = {"full": take("k", "k_s", rows["full"]),
         "window": take("kw", "kw_s", rows["window"])}
    v = {"full": take("v", "v_s", rows["full"]),
         "window": take("vw", "vw_s", rows["window"])}
    return k, v


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def decode_step(params: Params, last_tokens, cache: Params, lengths,
                cfg: LagunaConfig, span: int | None = None, lora=None,
                ids=None, active=None):
    """One continuous-batching decode step over all cache slots:
    last_tokens [B], lengths [B] (where this step's K and V are written)
    -> (logits [B, vocab] float32, the new cache, with the step's
    STEP_COUNTERS under "counters"). A slot that is not `active` attends
    nothing; its junk write lands on its own (dead) rows. `span` bounds
    the full layers' attention as llama.decode_step says; the ring is
    read by the window."""
    _no_adapters(lora)
    b = last_tokens.shape[0]
    x = params["embed"].astype(cfg.dtype)[last_tokens][:, None]   # [B,1,D]
    quantized = "k_s" in cache
    max_len, ring = cache["k"].shape[2], cache["kw"].shape[2]
    span = max_len if span is None else min(span, max_len)
    rows = jnp.arange(b)
    positions = lengths[:, None]                                  # [B, 1]
    attn_positions = positions if active is None else jnp.where(
        active[:, None], positions, -1)
    impl = resolve_decode_attn(cfg)
    kernel_stores = quantized and impl == "flash"
    cache = dict(cache)
    seen = []
    for layer in plan(cfg):
        names = _SLABS["full" if layer.attn == "full" else "window"]
        li = layer.attn_at
        window = _window(cfg, layer)
        w_pos = positions if window is None else positions % ring
        with jax.named_scope(_attn_scope(layer)):
            q, k_new, v_new, gate = _project(cfg, layer, params, x,
                                             positions)
            new_scales = None
            if quantized:
                kq, ksc = llama.quantize_kv(k_new)
                vq, vsc = llama.quantize_kv(v_new)
                writes = {names[0]: kq, names[1]: vq}
                new_scales = (ksc, vsc)
            else:
                writes = {names[0]: k_new.astype(cache[names[0]].dtype),
                          names[1]: v_new.astype(cache[names[1]].dtype)}
            # drop mode: a dead slot's stale length may sit at max_len
            for name, rows_new in writes.items():
                cache[name] = cache[name].at[li, rows[:, None], w_pos].set(
                    rows_new, mode="drop")
            if quantized and not kernel_stores:
                for name, sc in zip(names[2:], new_scales):
                    cache[name] = cache[name].at[
                        li, rows[:, None], :, w_pos].set(sc, mode="drop")
            view = {"k": cache[names[0]], "v": cache[names[1]]}
            if quantized:
                view.update(k_s=cache[names[2]], v_s=cache[names[3]])
            out = llama.decode_attention(
                _dims(cfg, layer), q, view, li, attn_positions,
                span=span if window is None else None, impl=impl,
                new_scales=new_scales if kernel_stores else None,
                window=window)
            if kernel_stores:
                out, cache[names[2]], cache[names[3]] = out
            dims = _dims(cfg, layer)
            x = _attn_out(cfg, layer, params, x,
                          out.reshape(b, 1, dims.n_heads, dims.head_dim),
                          gate)
        x, counters = _ffn(cfg, layer, params, x)
        if counters is not None:
            seen.append(counters)
    cache["counters"] = _fold_counters(seen)
    return lm_head(params, x, cfg)[:, 0], cache


def verify_step(*_, **__):
    raise NotImplementedError(
        "the laguna family has no speculative verify step: a window "
        "layer's ring would have to take back the rows of rejected drafts")


# ---------------------------------------------------------------------------
# the rest of the seam
# ---------------------------------------------------------------------------

quantize_kv = llama.quantize_kv
dequantize_kv = llama.dequantize_kv

#: no leaf of this family is served quantized, so none takes an adapter
QUANT_LEAVES: tuple[str, ...] = ()


def _no_adapters(lora) -> None:
    if lora is not None:
        raise NotImplementedError("the laguna family serves no adapters")


def quantize_params(params: Params) -> Params:
    raise NotImplementedError(
        "the laguna family is served in its published bfloat16: int8 "
        "experts need a grouped matmul that dequantizes its groups")


def logical_axes_for(params: Params, cfg: LagunaConfig) -> Params:
    raise NotImplementedError("the laguna family is served on one chip: "
                              "its experts have no layout across a mesh")


def cache_kv_spec(name: str, axis: str = "tensor"):
    raise NotImplementedError("the laguna family is served on one chip: "
                              "its two cache slabs have no mesh layout")


def resolve_decode_attn(cfg: LagunaConfig) -> str:
    from kubeflow_tpu.ops import flash_decode

    return flash_decode.resolve_impl(cfg.decode_attention_impl,
                                     head_dim=cfg.head_dim,
                                     n_kv_heads=cfg.num_key_value_heads)


def resolve_prefill_attn(cfg: LagunaConfig) -> str:
    from kubeflow_tpu.ops import flash_prefill

    return flash_prefill.resolve_impl(cfg.prefill_attention_impl,
                                      head_dim=cfg.head_dim,
                                      n_kv_heads=cfg.num_key_value_heads)

"""Nemotron-H (`model_type: nemotron_h`) as a SERVED family: Mamba-2
state-space layers, grouped-query attention layers and latent expert
layers in the order `hybrid_override_pattern` gives (M, *, E), on one
expert-parallel rank that holds a share of each expert layer's experts.

Every layer is x + mixer(rmsnorm(x)); then a final norm and the head.

    M  [z | xBC | dt] = n W_in;  xBC = silu(causal_conv4(xBC) + b)
       [x | B | C] = xBC (x: heads x head_dim; B, C: n_groups x state, a
       group shared by heads / n_groups heads);  dt = softplus(dt + dt_bias)
       h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,  A = -exp(A_log)
       y = h_t C_t + D x_t;  out = rmsnorm_groups(y * silu(z)) W_out
    *  softmax(q k^T / sqrt(head_dim), causal) v over 2 KV heads, no
       rotary embedding (the family's attention is position-free)
    E  router: sigmoid(n W_r) over all the experts, top k of score + bias,
       weights over their sum times `routed_scaling_factor`;
       lat = n W_down (the latent);  routed = sum_e w_e relu(lat W1_e)^2
       W2_e;  out = routed W_up + relu(n S1)^2 S2 (the shared expert)

What that forces on a serving engine, and where it lives here:

  - TWO KINDS OF PER-SLOT STATE in one cache: the attention layers' rows
    `k` / `v` [L_attn, slots, max_len, kv, hd], and each state-space
    layer's recurrent state `ssm` [L_ssm, slots, heads, head_dim, state]
    (float32 unless the configuration says otherwise) beside its conv
    window `conv` [L_ssm, slots, conv_kernel - 1, conv width], the last
    raw xBC rows. A prefill writes a slot's states WHOLE, computed from a
    zero state at the prompt's true length (the bucket's pad changes
    nothing: ops/ssd.py zeroes dt there), so a reused slot starts clean;
  - the chunked scan for a prompt and the one-step update in place for a
    decode step (ops/ssd.py); a decode step updates every slot, and a dead
    slot's junk lands only in its own state, which its next prefill
    overwrites;
  - experts fed a latent while the router reads the full hidden state,
    squared-ReLU experts with no gate (ops/moe.py::moe_share_mlp);
  - the decode step's counters (`STEP_COUNTERS`), and the prompt tokens
    through the scans (`prompt_counters`).

Weights are STACKED BY KIND (`mamba`, `attn`, `moe`) and read in place by
index. The bodies carry the names the engine calls on a family's module
(serving/llm.py, "THE FAMILY SEAM"). Not served, and refused by name where
a deployment asks (serving/llm_runtime.py): the MTP module (speculation),
int8 weights or cache, adapters, the prefix cache, the paged pool, a mesh.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from kubeflow_tpu.models import llama
from kubeflow_tpu.ops.moe import ShareArgs, moe_share_mlp
from kubeflow_tpu.ops.norms import rms_norm
from kubeflow_tpu.ops.ssd import ssd_scan, ssm_state_step

Params = dict[str, Any]

#: the published 88 layers
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
KINDS = {"M": "mamba", "*": "attn", "E": "moe"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The published `config.json` keys under their own names, then how
    this repo runs it. `n_routed_experts` is the experts HELD here;
    `n_router_experts` the router's width (None: every expert is held)."""
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    layer_norm_epsilon: float = 1e-5
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    # -- how it runs here
    n_router_experts: int | None = None
    first_expert: int = 0
    #: weights, activations, matmuls and the cache, the recurrent state
    #: too (the scan carries its state in float32)
    dtype: Any = jnp.bfloat16
    decode_attention_impl: str = "auto"
    prefill_attention_impl: str = "auto"

    def __post_init__(self):
        # a dtype by its name, as a configuration's file gives it
        if isinstance(self.dtype, str):
            object.__setattr__(self, "dtype",
                               jnp.dtype(self.dtype).type)
        if self.n_router_experts is None:
            object.__setattr__(self, "n_router_experts",
                               self.n_routed_experts)
        if self.first_expert + self.n_routed_experts > self.n_router_experts:
            raise ValueError("the experts held lie outside the router's")
        if len(self.hybrid_override_pattern) != self.num_hidden_layers or (
                set(self.hybrid_override_pattern) - set(KINDS)):
            raise ValueError("hybrid_override_pattern must give one of "
                             f"{sorted(KINDS)} for each of the "
                             f"{self.num_hidden_layers} layers")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("n_groups must divide mamba_num_heads")
        for name in ("decode_attention_impl", "prefill_attention_impl"):
            if getattr(self, name) not in ("auto", "xla", "flash"):
                raise ValueError(f"unknown {name} {getattr(self, name)!r}")

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """xBC's width: x, then B and C of every group."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def count(self, kind: str) -> int:
        return sum(KINDS[c] == kind for c in self.hybrid_override_pattern)

    @property
    def share_args(self) -> ShareArgs:
        return ShareArgs(self.n_router_experts, self.num_experts_per_tok,
                         self.n_routed_experts, self.first_expert,
                         scale=self.routed_scaling_factor,
                         renormalize=self.norm_topk_prob)


class Layer(NamedTuple):
    kind: str       # "mamba" | "attn" | "moe"
    at: int         # its index among the layers of its kind


def plan(cfg: NemotronHConfig) -> list[Layer]:
    seen = {k: 0 for k in KINDS.values()}
    out = []
    for c in cfg.hybrid_override_pattern:
        kind = KINDS[c]
        out.append(Layer(kind, seen[kind]))
        seen[kind] += 1
    return out


def stack_shapes(cfg: NemotronHConfig) -> dict[str, dict[str, tuple]]:
    """{stack: {leaf: (shape of ONE layer's slice, how it is drawn)}}: an
    int is the fan-in of a normal draw, "ones" / "zeros" constants,
    "a_log" log(U[1, 16]) and "dt_bias" the inverse softplus of a step
    log-uniform on [time_step_min, time_step_max] floored at
    time_step_floor (Mamba-2's initialisation). The draw's order is this
    dict's; stacks without a layer in the pattern are left out."""
    d, h = cfg.hidden_size, cfg.mamba_num_heads
    lat, e = cfg.moe_latent_size, cfg.n_routed_experts
    f, fs = cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size
    qd, kvd = (cfg.num_attention_heads * cfg.head_dim,
               cfg.num_key_value_heads * cfg.head_dim)
    out = {
        "mamba": {"norm": ((d,), "ones"),
                  "in_proj": ((d, cfg.d_inner + cfg.conv_dim + h), d),
                  "conv_w": ((cfg.conv_kernel, cfg.conv_dim),
                             cfg.conv_kernel),
                  "conv_b": ((cfg.conv_dim,), cfg.conv_kernel),
                  "dt_bias": ((h,), "dt_bias"), "a_log": ((h,), "a_log"),
                  "d_skip": ((h,), "ones"),
                  "gate_norm": ((cfg.d_inner,), "ones"),
                  "out_proj": ((cfg.d_inner, d), cfg.d_inner)},
        "attn": {"norm": ((d,), "ones"), "w_q": ((d, qd), d),
                 "w_k": ((d, kvd), d), "w_v": ((d, kvd), d),
                 "w_o": ((qd, d), qd)},
        "moe": {"norm": ((d,), "ones"),
                "router": ((d, cfg.n_router_experts), d),
                "router_bias": ((cfg.n_router_experts,), "zeros"),
                "latent_down": ((d, lat), d), "w_up": ((e, lat, f), lat),
                "w_down": ((e, f, lat), f), "latent_up": ((lat, d), lat),
                "shared_up": ((d, fs), d), "shared_down": ((fs, d), fs)}}
    return {k: v for k, v in out.items() if cfg.count(k)}


#: leaves kept in float32 whatever the model dtype: the router and its
#: bias (the choice is discontinuous), and the recurrence's own numbers
FLOAT32_LEAVES = ("router", "router_bias", "dt_bias", "a_log", "d_skip")


def draw_leaf(cfg: NemotronHConfig, key, shape, how, dtype):
    """One layer's slice of a leaf from its key (the benchmark's reference
    draws with the same recipe). The barriers keep each step one operation
    of the finished value wherever this compiles."""
    if how in ("ones", "zeros"):
        return jnp.full(shape, 1.0 if how == "ones" else 0.0, dtype)
    if how == "a_log":
        u = jax.lax.optimization_barrier(
            jax.random.uniform(key, shape, jnp.float32))
        return jnp.log(1.0 + 15.0 * u).astype(dtype)
    if how == "dt_bias":
        u = jax.lax.optimization_barrier(
            jax.random.uniform(key, shape, jnp.float32))
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        z = jax.lax.optimization_barrier(u * (hi - lo) + lo)
        dt = jax.lax.optimization_barrier(
            jnp.maximum(jnp.exp(z), cfg.time_step_floor))
        tail = jax.lax.optimization_barrier(jnp.expm1(-dt))
        return (dt + jax.lax.optimization_barrier(jnp.log(-tail))).astype(
            dtype)
    unit = jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))
    return (unit * (how ** -0.5)).astype(dtype)


def init(rng: jax.Array, cfg: NemotronHConfig) -> Params:
    """Seeded weights IN THE SERVED DTYPE, a layer's slice of a leaf at a
    time (no float32 tree of the whole model ever exists): slice `i` of
    leaf number `n` (every leaf counts, norms too) from
    `fold_in(fold_in(rng, n), i)`. The embedding's rows are unit normal;
    FLOAT32_LEAVES are float32."""
    def draw(key, shape, how, dtype, layers):
        one = lambda k: draw_leaf(cfg, k, shape, how, dtype)  # noqa: E731
        if not layers:
            return jax.jit(one)(key)
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(layers))
        return jax.jit(lambda ks: jax.lax.map(one, ks))(keys)

    d, v = cfg.hidden_size, cfg.vocab_size
    params: Params = {
        "embed": draw(jax.random.fold_in(rng, 0), (v, d), 1, cfg.dtype, 0),
        "lm_head": draw(jax.random.fold_in(rng, 1), (d, v), d, cfg.dtype, 0),
        "final_norm": jnp.ones((d,), cfg.dtype)}
    leaf_no = 2
    for stack, leaves in stack_shapes(cfg).items():
        params[stack] = {}
        for leaf, (shape, how) in leaves.items():
            dtype = jnp.float32 if leaf in FLOAT32_LEAVES else cfg.dtype
            params[stack][leaf] = draw(jax.random.fold_in(rng, leaf_no),
                                       shape, how, dtype, cfg.count(stack))
            leaf_no += 1
    return params


def logical_axes(cfg: NemotronHConfig) -> Params:
    """Replicated: this family runs on one chip (no mesh is served)."""
    shapes = jax.eval_shape(lambda: init(jax.random.key(0), cfg))
    return jax.tree.map(lambda s: (None,) * len(s.shape), shapes)


# ---------------------------------------------------------------------------
# layer bodies
# ---------------------------------------------------------------------------

def _mm(x, w, dtype):
    return jnp.matmul(x.astype(dtype), w.astype(dtype))


class _AttnDims(NamedTuple):
    """What llama's attention seams read of a config."""
    n_heads: int
    n_kv_heads: int
    head_dim: int
    dtype: Any


def _dims(cfg: NemotronHConfig) -> _AttnDims:
    return _AttnDims(cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim, cfg.dtype)


def _mamba_in(cfg: NemotronHConfig, p: Params, i: int, n):
    """n [..., D] -> (z, xBC, dt) of W_in."""
    with jax.named_scope("ssm_proj"):
        zxd = _mm(n, p["in_proj"][i], cfg.dtype)
        di, cd = cfg.d_inner, cfg.conv_dim
        return zxd[..., :di], zxd[..., di:di + cd], zxd[..., di + cd:]


def _ssm_inputs(cfg: NemotronHConfig, p: Params, i: int, xbc, dt):
    """The conv's output xBC [..., conv_dim] and dt -> (x [..., H, P],
    B and C [..., G, N], dt softplus'd float32, A [H])."""
    h, g, n = cfg.mamba_num_heads, cfg.n_groups, cfg.ssm_state_size
    di = cfg.d_inner
    x = xbc[..., :di].reshape(xbc.shape[:-1] + (h, cfg.mamba_head_dim))
    bm = xbc[..., di:di + g * n].reshape(xbc.shape[:-1] + (g, n))
    cm = xbc[..., di + g * n:].reshape(xbc.shape[:-1] + (g, n))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][i])
    return x, bm, cm, dt, -jnp.exp(p["a_log"][i].astype(jnp.float32))


def _mamba_out(cfg: NemotronHConfig, p: Params, i: int, y, x, z):
    """y [..., H, P] float32 (h C) -> (y + D x) gated by silu(z), normed in
    n_groups groups, through W_out."""
    with jax.named_scope("ssm_proj"):
        y = y + p["d_skip"][i][:, None] * x.astype(jnp.float32)
        y = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
        grouped = y.reshape(y.shape[:-1] + (cfg.n_groups, -1))
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, axis=-1, keepdims=True)
            + cfg.layer_norm_epsilon)
        y = grouped.reshape(z.shape) * p["gate_norm"][i].astype(jnp.float32)
        return _mm(y, p["out_proj"][i], cfg.dtype)


def _mamba_prefill(cfg: NemotronHConfig, p: Params, i: int, n, lengths,
                   conv0, h0):
    """A chunk n [B, S, D] whose rows hold lengths [B] real positions,
    after a conv window conv0 [B, K-1, conv_dim] and a state h0 [B, H, P,
    N] -> (out [B, S, D], the conv window and the state at each row's
    length)."""
    z, xbc, dt = _mamba_in(cfg, p, i, n)
    s, k = xbc.shape[1], cfg.conv_kernel
    with jax.named_scope("ssm_conv"):
        seq = jnp.concatenate([conv0.astype(xbc.dtype), xbc], axis=1)
        w = p["conv_w"][i].astype(jnp.float32)
        conv = sum(seq[:, j:j + s].astype(jnp.float32) * w[j]
                   for j in range(k)) + p["conv_b"][i].astype(jnp.float32)
        conv = jax.nn.silu(conv).astype(cfg.dtype)
        # the window a decode step continues from: the last K-1 raw rows
        # before each row's length (seq's row j is position j - (K-1))
        window = jnp.take_along_axis(
            seq, lengths[:, None, None] + jnp.arange(k - 1)[None, :, None],
            axis=1)
    x, bm, cm, dt, a = _ssm_inputs(cfg, p, i, conv, dt)
    with jax.named_scope("ssm_scan"):
        y, h = ssd_scan(x, dt, a, bm, cm, h0, lengths)
    return _mamba_out(cfg, p, i, y.astype(jnp.float32), x, z), window, h


def _mamba_decode(cfg: NemotronHConfig, p: Params, i: int, n, cache):
    """One position n [B, 1, D] of every slot -> (out [B, 1, D], the cache
    with layer i's conv window and state advanced)."""
    z, xbc, dt = _mamba_in(cfg, p, i, n[:, 0])
    with jax.named_scope("ssm_conv"):
        win = jnp.concatenate([cache["conv"][i], xbc[:, None].astype(
            cache["conv"].dtype)], axis=1)                    # [B, K, C]
        conv = (jnp.einsum("bkc,kc->bc", win.astype(jnp.float32),
                           p["conv_w"][i].astype(jnp.float32))
                + p["conv_b"][i].astype(jnp.float32))
        conv = jax.nn.silu(conv).astype(cfg.dtype)
        cache["conv"] = cache["conv"].at[i].set(win[:, 1:])
    x, bm, cm, dt, a = _ssm_inputs(cfg, p, i, conv, dt)
    with jax.named_scope("ssm_state"):
        cache["ssm"], y = ssm_state_step(cache["ssm"], i, x, dt, a, bm, cm)
    return _mamba_out(cfg, p, i, y, x, z)[:, None], cache


def _qkv(cfg: NemotronHConfig, p: Params, i: int, n):
    b, s, _ = n.shape
    hd = cfg.head_dim
    q = _mm(n, p["w_q"][i], cfg.dtype).reshape(b, s, -1, hd)
    k = _mm(n, p["w_k"][i], cfg.dtype).reshape(b, s, -1, hd)
    v = _mm(n, p["w_v"][i], cfg.dtype).reshape(b, s, -1, hd)
    return q, k, v


def _moe(cfg: NemotronHConfig, p: Params, j: int, n):
    """The latent expert layer on n [B, S, D] -> (out, the routed experts'
    counters)."""
    with jax.named_scope("moe_latent"):
        lat = _mm(n, p["latent_down"][j], cfg.dtype)
    # moe_share_mlp opens the scopes moe_route and moe_experts itself
    routed, counters = moe_share_mlp(
        n, p["router"][j], p["router_bias"][j], None, p["w_up"],
        p["w_down"], cfg.share_args, cfg.dtype, layer=j, expert_x=lat)
    with jax.named_scope("moe_latent"):
        routed = _mm(routed, p["latent_up"][j], cfg.dtype)
    with jax.named_scope("moe_shared"):
        up = _mm(n, p["shared_up"][j], cfg.dtype).astype(jnp.float32)
        shared = _mm(jnp.square(jax.nn.relu(up)), p["shared_down"][j],
                     cfg.dtype)
    return routed + shared, counters


def _norm(cfg: NemotronHConfig, x, w):
    return rms_norm(x, w, cfg.layer_norm_epsilon)


def lm_head(params: Params, x, cfg: NemotronHConfig, rows=None):
    """final norm + head; `rows` [B] projects those positions only."""
    if rows is not None:
        x = jnp.take_along_axis(x, rows[:, None, None], axis=1,
                                mode="clip")[:, 0]
    x = _norm(cfg, x, params["final_norm"])
    with jax.named_scope("lm_head"):
        return jnp.matmul(x.astype(cfg.dtype),
                          params["lm_head"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def _prefill_layers(params: Params, x, lengths, cfg: NemotronHConfig,
                    k_prefix=None, v_prefix=None):
    """Every layer over a chunk x [B, S, D] whose rows hold lengths [B]
    real positions -> (x, k [L_attn, B, S, kv, hd], {"v", "ssm", "conv"}).
    With a prefix (`extract_prefix`'s: the attention rows of the P
    positions before the chunk, each state-space layer's state and conv
    window at P) the chunk continues from it."""
    impl = resolve_prefill_attn(cfg)
    b = x.shape[0]
    ks, vs, states, windows = [], [], [], []
    for layer in plan(cfg):
        p, i = params[layer.kind], layer.at
        n = _norm(cfg, x, p["norm"][i])
        if layer.kind == "mamba":
            if v_prefix is None:
                conv0 = jnp.zeros((b, cfg.conv_kernel - 1, cfg.conv_dim),
                                  cfg.dtype)
                h0 = jnp.zeros((b, cfg.mamba_num_heads, cfg.mamba_head_dim,
                                cfg.ssm_state_size), jnp.float32)
            else:
                conv0, h0 = v_prefix["conv"][i], v_prefix["ssm"][i]
            out, window, h = _mamba_prefill(cfg, p, i, n, lengths, conv0,
                                            h0.astype(jnp.float32))
            windows.append(window)
            states.append(h)
        elif layer.kind == "attn":
            with jax.named_scope("attn_full"):
                q, k, v = _qkv(cfg, p, i, n)
                ks.append(k)
                vs.append(v)
                k_all, v_all, q_offset = k, v, 0
                if k_prefix is not None:
                    k_all = jnp.concatenate(
                        [k_prefix[i].astype(cfg.dtype), k], axis=1)
                    v_all = jnp.concatenate(
                        [v_prefix["v"][i].astype(cfg.dtype), v], axis=1)
                    q_offset = k_prefix.shape[2]
                o = llama.prefill_attention(_dims(cfg), q, k_all, v_all,
                                            q_offset=q_offset, impl=impl)
                out = _mm(o.reshape(o.shape[:2] + (-1,)), p["w_o"][i],
                          cfg.dtype)
        else:
            out, _ = _moe(cfg, p, i, n)
        x = x + out.astype(x.dtype)
    stack = lambda rows: jnp.stack(rows) if rows else None    # noqa: E731
    return x, stack(ks), {"v": stack(vs), "ssm": stack(states),
                          "conv": stack(windows)}


def _lengths(tokens, logit_rows):
    """Each row's real positions: the row sampled is the last of them."""
    if logit_rows is None:
        return jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    return jnp.asarray(logit_rows, jnp.int32) + 1


def prefill(params: Params, tokens, cfg: NemotronHConfig, lora=None,
            ids=None, logit_rows=None):
    """tokens [B, S] (right-padded) -> (logits [B, S, vocab] float32, or
    [B, vocab] of `logit_rows`; the attention keys [L_attn, B, S, kv, hd];
    {"v": the values, "ssm": [L_ssm, B, H, P, N] and "conv": [L_ssm, B,
    K-1, conv_dim], the states at each row's length: `logit_rows` + 1, or
    S})."""
    _no_adapters(lora)
    x = params["embed"].astype(cfg.dtype)[tokens]
    x, ks, vs = _prefill_layers(params, x, _lengths(tokens, logit_rows), cfg)
    return lm_head(params, x, cfg, logit_rows), ks, vs


def prefill_continue(params: Params, tail_tokens, k_prefix, v_prefix,
                     cfg: NemotronHConfig, lora=None, ids=None,
                     logit_rows=None):
    """The TAIL of a prompt whose first P positions are in the slot:
    k_prefix / v_prefix as `extract_prefix` gives them. Returns the tail's
    logits, its own attention rows and the states at its end."""
    _no_adapters(lora)
    x = params["embed"].astype(cfg.dtype)[tail_tokens]
    x, ks, vs = _prefill_layers(params, x, _lengths(tail_tokens, logit_rows),
                                cfg, k_prefix, v_prefix)
    return lm_head(params, x, cfg, logit_rows), ks, vs


def apply(params: Params, tokens, cfg: NemotronHConfig, **_):
    """tokens [B, S] -> logits [B, S, vocab] float32: the plain forward
    pass (the einsum attention), for tests."""
    return prefill(params, tokens, dataclasses.replace(
        cfg, prefill_attention_impl="xla"))[0]


def loss_fn(params: Params, batch: dict[str, jax.Array],
            cfg: NemotronHConfig):
    """Next-token cross-entropy of the plain forward pass (the registry's
    contract; this family is served, no training cell runs it)."""
    tokens = batch["tokens"]
    logp = jax.nn.log_softmax(apply(params, tokens[:, :-1], cfg), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    return loss, {"loss": loss, "tokens": jnp.asarray(nll.size, jnp.float32)}


# ---------------------------------------------------------------------------
# the cache: attention rows and recurrent states
# ---------------------------------------------------------------------------

def init_cache(cfg: NemotronHConfig, n_slots: int, max_len: int,
               kv_quantize: str | None = None,
               chunk: int | None = None) -> Params:
    """{"k", "v": [L_attn, slots, max_len, kv, hd], "ssm": [L_ssm, slots,
    H, P, N], "conv": [L_ssm, slots, K-1, conv_dim]}, all in the model
    dtype."""
    if kv_quantize is not None:
        raise ValueError("the nemotron_h family keeps its cache in the "
                         "model dtype (no int8 cache)")
    kv = (cfg.count("attn"), n_slots, max_len, cfg.num_key_value_heads,
          cfg.head_dim)
    m = cfg.count("mamba")
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            "ssm": jnp.zeros((m, n_slots, cfg.mamba_num_heads,
                              cfg.mamba_head_dim, cfg.ssm_state_size),
                             cfg.dtype),
            "conv": jnp.zeros((m, n_slots, cfg.conv_kernel - 1,
                               cfg.conv_dim), cfg.dtype)}


def cache_stats(cache: Params) -> dict[str, Any]:
    """metrics(): the bytes of each kind of state."""
    return {"ssm_state_bytes": int(cache["ssm"].nbytes),
            "ssm_conv_bytes": int(cache["conv"].nbytes),
            "kv_bytes_full": int(cache["k"].nbytes + cache["v"].nbytes)}


def cache_write(cache: Params, slot, start: int, count: int, ks, vs, *,
                kv_quantize: str | None = None) -> Params:
    """One prompt's attention rows [L_attn, rows, kv, hd] into a slot at
    positions [start, start + rows), and its states WHOLE (the slot's
    previous ones are gone: a reused slot starts clean)."""
    out = dict(cache)
    for name, rows in (("k", ks), ("v", vs["v"])):
        if rows is not None:
            out[name] = jax.lax.dynamic_update_slice(
                cache[name], rows[:, None].astype(cache[name].dtype),
                (0, slot, start, 0, 0))
    for name in ("ssm", "conv"):
        if vs[name] is not None:
            out[name] = cache[name].at[:, slot].set(
                vs[name].astype(cache[name].dtype))
    return out


def extract_prefix(cfg: NemotronHConfig, cache: Params, slot, p: int, *,
                   kv_quantize: str | None = None, dtype=None):
    """A slot's first `p` positions as prefill_continue takes its prefix:
    (k [L_attn, 1, p, kv, hd], {"v", "ssm" [L_ssm, 1, ...], "conv"}); the
    states are the slot's, which stand at position p when the slot's last
    write was a prompt's first p positions (the engine's chain)."""
    def take(name, rows=None):
        got = jax.lax.dynamic_index_in_dim(cache[name], slot, axis=1,
                                           keepdims=False)
        return (got if rows is None else got[:, :rows])[:, None]
    return take("k", p), {"v": take("v", p), "ssm": take("ssm"),
                          "conv": take("conv")}


def prompt_counters(cfg: NemotronHConfig, tokens: int) -> dict[str, float]:
    """What the prompt tokens computed so far count: `ssm_scan_tokens`,
    real prompt tokens times the state-space layers their scans took them
    through (the bucket's pad not counted)."""
    return {"ssm_scan_tokens": float(tokens * cfg.count("mamba"))}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

#: a decode step's counts: (name in metrics(), how the engine folds the
#: steps: "sum", or "last" seen). The first four over the expert layers;
#: `ssm_state_rows` the live slots' states the step updated, over the
#: state-space layers
STEP_COUNTERS = (("moe_assignments", "sum"), ("moe_expert_visits", "sum"),
                 ("moe_rows_dropped", "sum"),
                 ("moe_load_max_over_mean", "last"),
                 ("ssm_state_rows", "sum"))


def _fold_counters(seen: list[dict], state_rows) -> jax.Array:
    if not seen:
        return jnp.stack([jnp.zeros((), jnp.float32)] * 4 + [state_rows])
    col = lambda k: jnp.stack([c[k] for c in seen])   # noqa: E731
    return jnp.stack([jnp.sum(col("rows_here")),
                      jnp.sum(col("experts_touched")),
                      jnp.sum(col("rows_dropped")),
                      jnp.max(col("load_max_over_mean")), state_rows])


def decode_step(params: Params, last_tokens, cache: Params, lengths,
                cfg: NemotronHConfig, span: int | None = None, lora=None,
                ids=None, active=None):
    """One continuous-batching decode step over all cache slots:
    last_tokens [B], lengths [B] (where this step's attention row is
    written) -> (logits [B, vocab] float32, the new cache, with the step's
    STEP_COUNTERS under "counters"). Every slot's states advance; a slot
    that is not `active` attends nothing, and its junk lands only in its
    own rows and states, which its next prefill overwrites. `span` bounds
    the rows the attention covers."""
    _no_adapters(lora)
    b = last_tokens.shape[0]
    x = params["embed"].astype(cfg.dtype)[last_tokens][:, None]   # [B,1,D]
    max_len = cache["k"].shape[2]
    span = max_len if span is None else min(span, max_len)
    rows = jnp.arange(b)
    positions = lengths[:, None]                                  # [B, 1]
    attn_positions = positions if active is None else jnp.where(
        active[:, None], positions, -1)
    impl = resolve_decode_attn(cfg)
    cache = dict(cache)
    seen = []
    for layer in plan(cfg):
        p, i = params[layer.kind], layer.at
        n = _norm(cfg, x, p["norm"][i])
        if layer.kind == "mamba":
            out, cache = _mamba_decode(cfg, p, i, n, cache)
        elif layer.kind == "attn":
            with jax.named_scope("attn_full"):
                q, k, v = _qkv(cfg, p, i, n)
                # drop mode: a dead slot's stale length may sit at max_len
                for name, new in (("k", k), ("v", v)):
                    cache[name] = cache[name].at[i, rows[:, None],
                                                 positions].set(
                        new.astype(cache[name].dtype), mode="drop")
                o = llama.decode_attention(
                    _dims(cfg), q, {"k": cache["k"], "v": cache["v"]}, i,
                    attn_positions, span=span, impl=impl)
                out = _mm(o.reshape(b, 1, -1), p["w_o"][i], cfg.dtype)
        else:
            out, counters = _moe(cfg, p, i, n)
            seen.append(counters)
        x = x + out.astype(x.dtype)
    live = b if active is None else jnp.sum(active)
    cache["counters"] = _fold_counters(
        seen, jnp.asarray(live * cfg.count("mamba"), jnp.float32))
    return lm_head(params, x, cfg)[:, 0], cache


def verify_step(*_, **__):
    raise NotImplementedError(
        "the nemotron_h family has no speculative verify step: a recurrent "
        "state cannot take back rejected drafts without snapshots, and its "
        "drafter would be the MTP module, which is not served")


# ---------------------------------------------------------------------------
# the rest of the seam
# ---------------------------------------------------------------------------

#: no leaf of this family is served quantized, so none takes an adapter
QUANT_LEAVES: tuple[str, ...] = ()


def _no_int8(*_, **__):
    raise NotImplementedError("the nemotron_h family keeps its cache in "
                              "the model dtype")


quantize_kv = dequantize_kv = _no_int8


def _no_adapters(lora) -> None:
    if lora is not None:
        raise NotImplementedError("the nemotron_h family serves no adapters")


def quantize_params(params: Params) -> Params:
    raise NotImplementedError(
        "the nemotron_h family is served in its published bfloat16: int8 "
        "experts need a grouped matmul that dequantizes its groups")


def logical_axes_for(params: Params, cfg: NemotronHConfig) -> Params:
    raise NotImplementedError("the nemotron_h family is served on one chip: "
                              "its experts have no exchange")


def cache_kv_spec(name: str, axis: str = "tensor"):
    raise NotImplementedError("the nemotron_h family is served on one chip: "
                              "its cache has no mesh layout")


def resolve_decode_attn(cfg: NemotronHConfig) -> str:
    from kubeflow_tpu.ops import flash_decode

    return flash_decode.resolve_impl(cfg.decode_attention_impl,
                                     head_dim=cfg.head_dim,
                                     n_kv_heads=cfg.num_key_value_heads)


def resolve_prefill_attn(cfg: NemotronHConfig) -> str:
    from kubeflow_tpu.ops import flash_prefill

    return flash_prefill.resolve_impl(cfg.prefill_attention_impl,
                                      head_dim=cfg.head_dim,
                                      n_kv_heads=cfg.num_key_value_heads)

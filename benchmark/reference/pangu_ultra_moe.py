"""Plain reference for openPangu Ultra MoE (`model_type: pangu_ultra_moe`):
latent attention with a decoupled rotary key, sandwich norms, a leading
dense layer, sigmoid-routed expert layers with a shared expert, on the
expert-parallel rank that holds experts [first_expert, first_expert +
n_routed_experts) of a router over all the published experts. Float32
jax.numpy at the highest matmul precision, attention in its EXPANDED form
only: no kernel, no cache, no absorption, no sort, no grouped matmul, one
sequence at a time. It imports nothing of the program and takes nothing the
program made. The configuration is the benchmark's JSON, the published
`config.json` keys under their own names.

    n1 = rmsnorm(x)
    c_q = rmsnorm(n1 W_DQ);          [q_nope | q_rope]_h = (c_q W_UQ)_h
    [c | k_r] = n1 W_DKV;            c_kv = rmsnorm(c)
    [k_nope | v]_h = (c_kv W_UKV)_h; k_rope = rope(k_r), one for all heads
    a_h = softmax((q_nope_h k_nope_h + rope(q_rope_h) k_rope) / sqrt(192),
                  causal) v_h
    h  = x + rmsnorm_post(concat_h(a_h) W_O)
    y  = h + rmsnorm_post(ffn(rmsnorm(h)))
    logits = head(rmsnorm(y_L))

`ffn` is a SwiGLU of `intermediate_size` in the first
`first_k_dense_replace` layers; after them it is one shared SwiGLU of
`n_shared_experts * moe_intermediate_size`, unweighted, plus
`routed_scaling_factor` times the chosen experts' SwiGLUs (of
`moe_intermediate_size`), each output weighted by its score over the sum of
the chosen scores, where the scores are sigmoid(n2 W_r) over ALL the
published experts (`published.n_routed_experts`) and the
`num_experts_per_tok` largest are chosen; only the terms of the experts this
rank holds are added (what the rank computes before the exchange).

Departures from the published description, each because the config names
the thing and does not spell it out (the configuration's `assumed` says the
same): the router's score is a sigmoid with no selection bias and no group
limit; the rotary pairs are (i, i + 32) of the 64 rotary dims at
`rope_theta`, no scaling; scores are over sqrt(qk_nope + qk_rope) with no
mscale; the post-norms' gains are `post_norm_gain` (the depth-scaled
sandwich norm), the other norms' ones.

Weights are random and drawn HERE, one layer at a time: normal /
sqrt(fan_in) in float32 from `fold_in(fold_in(key(seed), leaf), i)`, `leaf`
the leaf's number (`leaf_numbers`, norms numbered too) and `i` the layer's
index among the layers of its kind, rounded to the dtype the
configuration's `precision` says the weights are served in, then computed
with in float32. The embedding's rows are unit normal; the router float32.
`W_UKV` is drawn whole, `[kv_lora_rank, heads * (qk_nope + v)]`, a head's
key columns before its value columns.

`lower="fp8"`, the control: every matmul's two operands but the router's
rounded to float8 e4m3, each tensor scaled so that its largest magnitude is
e4m3's largest (448), accumulated in float32: the precision one step under
the bfloat16 the configuration computes in (the Kimi-Linear reference's
control). The attention's score and value products stay float32.
`fault=`, the planted faults (FAULTS): the comparison that decides
`correct` has to tell every one from the sound model. A fault is a set of
numbers the layer program takes (`knobs`), so one compiled program per
layer serves the sound model, the control and every fault.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

_GROUPS = (("attn", ("attn_norm", "w_dq", "q_norm", "w_uq", "w_dkv",
                     "kv_norm", "w_ukv", "w_o", "post_attn_norm")),
           ("dense_ffn", ("pre_mlp_norm", "w_gate", "w_up", "w_down",
                          "post_mlp_norm")),
           ("experts", ("pre_mlp_norm", "router", "w_gate", "w_up", "w_down",
                        "shared_gate", "shared_up", "shared_down",
                        "post_mlp_norm")))

#: planted faults: what each changes of the model above
FAULTS = {
    "no_post_norm": "the sandwich post-norms left out (pre-norm only)",
    "rope_unrotated": "neither the query's nor the key's rotary part "
                      "rotated",
    "rope_theta_1e4": "the rotary frequencies at theta 10000, not the "
                      "published 2.56e7",
    "no_shared": "the shared expert left out",
    "scale_1": "the routed experts' weights not multiplied by the scaling "
               "factor",
    "kv_unnormed": "the KV latent not normed before its up-projection",
}


def knobs(cfg: dict, fault: str | None = None) -> dict:
    """The numbers a planted fault changes, as the layer program takes
    them (arguments, not constants: one compiled program serves the sound
    model and every fault): `turn` multiplies the rotary angles (0: no
    rotation), `theta` the rotary base, `post` and `kv_norm` select the
    post-norms and the latent's norm, `shared` multiplies the shared
    expert, `scale` is the routed experts' scaling factor."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {sorted(FAULTS)}")
    out = {"turn": 1.0, "theta": float(cfg["rope_theta"]),
           "post": 1.0 if cfg.get("sandwich_norm", True) else 0.0,
           "kv_norm": 1.0, "shared": 1.0,
           "scale": float(cfg["routed_scaling_factor"])}
    out.update({"no_post_norm": {"post": 0.0},
                "rope_unrotated": {"turn": 0.0},
                "rope_theta_1e4": {"theta": 10000.0},
                "no_shared": {"shared": 0.0},
                "scale_1": {"scale": 1.0},
                "kv_unnormed": {"kv_norm": 0.0}}.get(fault, {}))
    return {k: jnp.float32(v) for k, v in out.items()}


def n_dense(cfg: dict) -> int:
    return min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def router_width(cfg: dict) -> int:
    """The router scores every PUBLISHED expert."""
    return (cfg.get("published") or {}).get("n_routed_experts",
                                           cfg["n_routed_experts"])


def leaf_numbers(cfg: dict) -> dict[tuple[str, str], int]:
    used = {"attn"}
    if n_dense(cfg):
        used.add("dense_ffn")
    if cfg["num_hidden_layers"] > n_dense(cfg):
        used.add("experts")
    out, n = {}, 2          # 0 the embedding, 1 the head
    for group, leaves in _GROUPS:
        if group in used:
            for leaf in leaves:
                out[group, leaf] = n
                n += 1
    return out


def _shapes(cfg: dict, group: str) -> dict[str, tuple]:
    """{leaf: (shape, fan_in; None a norm of ones, "gain" a post-norm)}."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rot, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    qr, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    if group == "attn":
        return {"attn_norm": ((d,), None), "w_dq": ((d, qr), d),
                "q_norm": ((qr,), None),
                "w_uq": ((qr, h * (nope + rot)), qr),
                "w_dkv": ((d, r + rot), d), "kv_norm": ((r,), None),
                "w_ukv": ((r, h * (nope + vd)), r),
                "w_o": ((h * vd, d), h * vd),
                "post_attn_norm": ((d,), "gain")}
    if group == "dense_ffn":
        f = cfg["intermediate_size"]
        return {"pre_mlp_norm": ((d,), None), "w_gate": ((d, f), d),
                "w_up": ((d, f), d), "w_down": ((f, d), f),
                "post_mlp_norm": ((d,), "gain")}
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    return {"pre_mlp_norm": ((d,), None),
            "router": ((d, router_width(cfg)), d),
            "w_gate": ((e, d, f), d), "w_up": ((e, d, f), d),
            "w_down": ((e, f, d), f), "shared_gate": ((d, fs), d),
            "shared_up": ((d, fs), d), "shared_down": ((fs, d), fs),
            "post_mlp_norm": ((d,), "gain")}


def _served_dtype(cfg: dict):
    return jnp.dtype((cfg.get("precision") or {}).get("weights",
                                                       "bfloat16"))


def _draw(key, shape, fan_in, dtype):
    # the barrier: the scale is one multiplication of the finished normal,
    # whatever the compiler would rather fuse (the program does the same)
    unit = jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))
    return (unit * (fan_in ** -0.5)).astype(dtype).astype(jnp.float32)


def draw_layer(seed, cfg: dict, l: int, lower=None) -> dict:
    """Layer l's weights, {"attn": {...}, "ffn": {...}}, float32 values the
    served dtype can hold (with `lower`, every matmul weight but the
    router as the lower precision holds it)."""
    root = jax.random.key(seed)
    numbers = leaf_numbers(cfg)
    dense = l < n_dense(cfg)
    out = {}
    for part, group, i in (("attn", "attn", l),
                           ("ffn", "dense_ffn" if dense else "experts",
                            l if dense else l - n_dense(cfg))):
        out[part] = {}
        for leaf, (shape, fan_in) in _shapes(cfg, group).items():
            if fan_in is None or fan_in == "gain":
                out[part][leaf] = jnp.full(
                    shape, 1.0 if fan_in is None else cfg["post_norm_gain"],
                    jnp.float32)
                continue
            key = jax.random.fold_in(
                jax.random.fold_in(root, numbers[group, leaf]), i)
            out[part][leaf] = _draw(
                key, shape, fan_in,
                jnp.float32 if leaf == "router" else _served_dtype(cfg))
    return lowered_layer(out, lower)


def draw_ends(seed, cfg: dict) -> dict:
    root = jax.random.key(seed)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    dt = _served_dtype(cfg)
    return {"embed": _draw(jax.random.fold_in(root, 0), (v, d), 1, dt),
            "lm_head": _draw(jax.random.fold_in(root, 1), (d, v), d, dt),
            "final_norm": jnp.ones((d,), jnp.float32)}


def init_params(seed, cfg: dict) -> dict:
    """The whole model at once: for toy sizes only."""
    return dict(draw_ends(seed, cfg),
                layers=[draw_layer(seed, cfg, l)
                        for l in range(cfg["num_hidden_layers"])])


# -- lower precision (the control) -------------------------------------------

def _fp8(x, axes):
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True),
                            1e-12)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def lowered(w, lower):
    """A matmul weight [..., in, out] as the lower precision holds it (an
    expert's matrix scaled on its own)."""
    if lower is None:
        return w
    if lower == "fp8":
        return _fp8(w, (-2, -1))
    raise ValueError(lower)


def lowered_layer(w: dict, lower) -> dict:
    """A layer's weights as the lower precision holds them: every matmul
    weight, not the router, not a norm."""
    return {part: {leaf: a if leaf == "router" or a.ndim < 2
                   else lowered(a, lower) for leaf, a in group.items()}
            for part, group in w.items()}


def _mm(x, w, lower=None):
    """x @ w; with `lower` x is rounded as the lower precision holds it (w
    arrives lowered)."""
    if lower == "fp8":
        x = _fp8(x, tuple(range(x.ndim)))
    return jnp.matmul(x, w, precision=HIGHEST)


# -- the block ---------------------------------------------------------------

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta, turn=1.0):
    """x [T, H, r]: pairs (i, i + r / 2) turn by position * theta **
    (-2 i / r) (times `turn`)."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = turn * positions.astype(jnp.float32)[:, None] * inv  # [T, r/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _post(cfg: dict, y, gain, k: dict):
    return jnp.where(k["post"] > 0, rmsnorm(y, gain, cfg["rms_norm_eps"]), y)


def attention(cfg: dict, x, w: dict, k: dict, lower=None):
    """x [T, d] -> the attention sublayer's output [T, d] (post-normed, no
    residual), expanded, the queries a block at a time."""
    t = x.shape[0]
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rot, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    pos = jnp.arange(t)

    def turn(z):
        return rope(z, pos, k["theta"], k["turn"])
    n1 = rmsnorm(x, w["attn_norm"], eps)
    c_q = rmsnorm(_mm(n1, w["w_dq"], lower), w["q_norm"], eps)
    q = _mm(c_q, w["w_uq"], lower).reshape(t, h, nope + rot)
    q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], -1)
    ckr = _mm(n1, w["w_dkv"], lower)
    c_kv = jnp.where(k["kv_norm"] > 0,
                     rmsnorm(ckr[:, :r], w["kv_norm"], eps), ckr[:, :r])
    k_rope = turn(ckr[:, None, r:])                            # [T, 1, rot]
    kv = _mm(c_kv, w["w_ukv"], lower).reshape(t, h, nope + vd)
    keys = jnp.concatenate([kv[..., :nope],
                            jnp.broadcast_to(k_rope, (t, h, rot))], -1)
    v = kv[..., nope:]
    block = math.gcd(t, 128)

    def rows(i):                      # one block of queries, every head
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        s = jnp.einsum("qhd,khd->hqk", qb, keys, precision=HIGHEST)
        seen = i * block + jnp.arange(block)[:, None] >= pos[None, :]
        s = jnp.where(seen[None], s / math.sqrt(nope + rot), -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    o = jax.lax.map(rows, jnp.arange(t // block)).reshape(t, h * vd)
    return _post(cfg, _mm(o, w["w_o"], lower), w["post_attn_norm"], k)


def swiglu(x, gate, up, down, lower=None):
    return _mm(jax.nn.silu(_mm(x, gate, lower)) * _mm(x, up, lower), down,
               lower)


def route(cfg: dict, n2, router, scale):
    """-> per token and PUBLISHED expert, the weight its output takes (0:
    not chosen), [T, E_published]."""
    scores = jax.nn.sigmoid(_mm(n2, router))
    top, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * scale
    rows = jnp.arange(n2.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(top)


def ffn(cfg: dict, l: int, h, w: dict, k: dict, lower=None):
    n2 = rmsnorm(h, w["pre_mlp_norm"], cfg["rms_norm_eps"])
    if l < n_dense(cfg):
        y = swiglu(n2, w["w_gate"], w["w_up"], w["w_down"], lower)
        return _post(cfg, y, w["post_mlp_norm"], k)
    weight = route(cfg, n2, w["router"], k["scale"])      # [T, E_published]
    first = cfg.get("first_expert", 0)

    def expert(total, e):
        # every expert held over every row, kept where the router chose it
        y = swiglu(n2, w["w_gate"][e], w["w_up"][e], w["w_down"][e], lower)
        return total + y * weight[:, first + e][:, None], None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(n2),
                        jnp.arange(cfg["n_routed_experts"]))
    y = y + k["shared"] * swiglu(n2, w["shared_gate"], w["shared_up"],
                                 w["shared_down"], lower)
    return _post(cfg, y, w["post_mlp_norm"], k)


def layer(cfg: dict, l: int, x, w: dict, k: dict, lower=None):
    """x [T, d] -> [T, d], one sequence; `k` the knobs."""
    h = x + attention(cfg, x, w["attn"], k, lower)
    return h + ffn(cfg, l, h, w["ffn"], k, lower)


@functools.lru_cache(maxsize=None)
def _compiled(what: str, cfg_json: str, l: int = 0, lower=None):
    """One compiled program per (configuration, layer, precision): the
    faults are the layer program's arguments, so a second call with the
    same configuration compiles nothing."""
    cfg = json.loads(cfg_json)
    if what == "ends":
        return jax.jit(lambda s: draw_ends(s, cfg))
    if what == "draw":
        return jax.jit(lambda s: draw_layer(s, cfg, l, lower))
    if what == "layer":
        return jax.jit(lambda x, w, k: layer(cfg, l, x, w, k, lower))
    return jax.jit(lambda x, n: rmsnorm(x, n, cfg["rms_norm_eps"]))


def ends(params_or_seed, cfg: dict) -> dict:
    """The embedding, the final norm and the head."""
    if isinstance(params_or_seed, dict):
        return params_or_seed
    return _compiled("ends", json.dumps(cfg, sort_keys=True))(params_or_seed)


#: the sound model's activations of the sequences seen last, on the host:
#: every fault and control is judged against them (served_gaps asks for
#: them again beside each stand-in)
_SOUND: dict = {}


class BySequence:
    """`hidden`'s result: `[i]` is sequence i's final-normed activations
    [T, d], computed when asked (the weights drawn a layer at a time and
    dropped after use), so that one sequence's activations and one layer's
    float32 weights are all that is ever held: six sequences of 11,264
    positions at width 7,680 would be 2 GB at once, the weights 14 GB."""

    def __init__(self, params_or_seed, tokens, cfg, lower, fault):
        self._p, self._tokens, self._cfg = params_or_seed, tokens, cfg
        self._lower, self._fault = lower, fault
        self.shape = tuple(tokens.shape) + (cfg["hidden_size"],)

    def __len__(self):
        return self.shape[0]

    def __getitem__(self, i):
        cfg, p = self._cfg, self._p
        drawn = isinstance(p, dict)
        sound = json.dumps(cfg, sort_keys=True)
        tokens = np.asarray(self._tokens[i])
        key = None
        if not drawn and self._lower is None and self._fault is None:
            key = (p, sound, tokens.tobytes())
            if key in _SOUND:
                return jnp.asarray(_SOUND[key])
        e = ends(p, cfg)
        k = knobs(cfg, self._fault)
        x = e["embed"][tokens]
        for l in range(cfg["num_hidden_layers"]):
            w = (lowered_layer(p["layers"][l], self._lower) if drawn
                 else _compiled("draw", sound, l, self._lower)(p))
            x = _compiled("layer", sound, l, self._lower)(x, w, k)
            del w
        out = _compiled("norm", sound)(x, e["final_norm"])
        if key is not None:
            while len(_SOUND) >= 16:
                _SOUND.pop(next(iter(_SOUND)))
            _SOUND[key] = np.asarray(out)
        return out


def hidden(params_or_seed, tokens, cfg: dict, lower=None, fault=None):
    """tokens [B, T] -> the final-normed activations, [B, T, d] as a
    sequence is asked for (BySequence). With a seed (an int) the weights
    are drawn from it; a dict is `init_params`' result."""
    return BySequence(params_or_seed, tokens, cfg, lower, fault)


def head(ends: dict, hidden, cfg: dict, lower=None):
    """[..., d] -> [..., vocab]: position t scores token t + 1."""
    return _mm(hidden, lowered(ends["lm_head"], lower), lower)


def logits(params_or_seed, tokens, cfg: dict, lower=None, fault=None):
    """tokens [B, T] -> [B, T, vocab] (toy sizes: every logit at once)."""
    e = ends(params_or_seed, cfg)
    h = hidden(params_or_seed, tokens, cfg, lower, fault)
    return jnp.stack([head(e, h[i], cfg, lower) for i in range(len(h))])

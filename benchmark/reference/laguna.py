"""Plain reference for the Laguna family (poolside `model_type: laguna`):
window and full attention mixed, a gate on every attention head, a
sigmoid-routed expert layer with a shared expert. Float32 jax.numpy at the
highest matmul precision: no kernel, no cache, no sort, no grouped matmul,
one sequence at a time. It imports nothing of the program and takes nothing
the program made. The configuration is the benchmark's JSON, the published
`config.json` keys under their own names.

    h = x + Wo (g * attn(rope(Wq n1), rope(Wk n1), Wv n1)),  n1 = rmsnorm(x)
        g = sigmoid(Wg n1), one number a head
    y = h + ffn(n2),                                          n2 = rmsnorm(h)
    logits = head(rmsnorm(y_L))

Layer `l`: `layer_types[l]` says whether query i sees every key j <= i or
only those with i - j < `sliding_window`; `num_attention_heads_per_layer[l]`
query heads over `num_key_value_heads` KV heads of `head_dim`, scores over
sqrt(head_dim); `rope_parameters[<kind>]` says which leading share of a head
rotates (`partial_rotary_factor`; the pairs are (i, i + rotated / 2), the
rest passes through) and with which frequencies: theta ** (-2 i / rotated),
and for `rope_type: yarn` HF `_compute_yarn_parameters`: every frequency a
blend of itself and itself / factor by a ramp linear in the pair's index
between floor(pair that turns beta_fast times over the original positions)
and ceil(pair that turns beta_slow times), and cos and sin times
`attention_factor`. `mlp_layer_types[l]`: `dense` is a SwiGLU of
`intermediate_size`; `sparse` is: scores = sigmoid(n2 Wr) over
`num_experts`, the `num_experts_per_tok` largest chosen, their scores over
their sum times `moe_routed_scaling_factor`, each chosen expert a SwiGLU of
`moe_intermediate_size` whose OUTPUT takes the weight
(`moe_apply_router_weight_on_input: false`), plus one shared SwiGLU of
`shared_expert_intermediate_size` on every token, unweighted.

Departures from the published description, each because the config names
the thing and does not spell it out (the configuration's `assumed` says the
same): the gate is per head with a sigmoid (`gating: true`; the sibling
config of the same model_type says "per-head"); the router's score is a
sigmoid, its chosen scores normalised (no score function is given); there
is no q/k normalisation; the activation is SiLU.

Weights are random and drawn HERE, one layer at a time, so that float32
fits the chip at the published widths (3.4 GB a sparse layer): normal /
sqrt(fan_in) in float32 from `fold_in(fold_in(key(seed), leaf), i)`, `leaf`
the leaf's number (`leaf_numbers` below) and `i` the layer's index among the
layers of its kind, rounded to the dtype the configuration's `precision`
says the weights are served in (`bfloat16`: the values the program holds,
then computed with in float32). The embedding's rows are unit normal; norms
are ones; the router is float32.

`lower="int8"`, the control: every matmul weight but the router rounded to
8-bit symmetric per output channel, one step under the served bfloat16.

`fault=`, the planted faults, each a way the program could be wrong and
still run (FAULTS below): the comparison that decides `correct` has to
tell every one of them from the sound model.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FULL, SLIDING = "full_attention", "sliding_attention"

#: the draw's order: (group, leaf) -> its number; a group with no layer in
#: the (cut) model is left out and the numbers close up
_ATTN = ("attn_norm", "wq", "wk", "wv", "wg", "wo")
_GROUPS = (("full", _ATTN), ("sliding", _ATTN),
           ("dense_ffn", ("mlp_norm", "w_gate", "w_up", "w_down")),
           ("experts", ("mlp_norm", "router", "w_gate", "w_up", "w_down",
                        "shared_gate", "shared_up", "shared_down")))


#: planted faults: what each changes of the model above
FAULTS = {
    "no_gate": "the attention output gate dropped (g = 1)",
    "window_511": "a sliding layer sees 511 keys, not 512",
    "window_513": "a sliding layer sees 513 keys",
    "no_shared": "the shared expert left out",
    "scale_1": "the routed experts' weights not multiplied by the scaling "
               "factor",
    "rope_all_dims": "a full layer rotates the whole head, not its first "
                     "half",
}


def faulty(cfg: dict, fault: str | None) -> dict:
    """The configuration as the fault would have it (flags under "_")."""
    if fault is None:
        return cfg
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {sorted(FAULTS)}")
    cfg = dict(cfg, _=fault)
    if fault.startswith("window_"):   # named after the published 512
        cfg["sliding_window"] += -1 if fault == "window_511" else 1
    if fault == "scale_1":
        cfg["moe_routed_scaling_factor"] = 1.0
    if fault == "rope_all_dims":
        rp = {k: dict(v) if isinstance(v, dict) else v
              for k, v in cfg["rope_parameters"].items()}
        rp[FULL]["partial_rotary_factor"] = 1
        cfg["rope_parameters"] = rp
    return cfg


def layer_kinds(cfg: dict) -> list[tuple[str, int, str, int]]:
    """Per layer: (attention group, index in it, ffn group, index in it)."""
    n = cfg["num_hidden_layers"]
    seen = {g: 0 for g, _ in _GROUPS}
    out = []
    for lt, mt in zip(cfg["layer_types"][:n], cfg["mlp_layer_types"][:n]):
        a = "full" if lt == FULL else "sliding"
        f = "dense_ffn" if mt == "dense" else "experts"
        out.append((a, seen[a], f, seen[f]))
        seen[a] += 1
        seen[f] += 1
    return out


def leaf_numbers(cfg: dict) -> dict[tuple[str, str], int]:
    used = {g for a, _, f, _ in layer_kinds(cfg) for g in (a, f)}
    out, n = {}, 2          # 0 the embedding, 1 the head
    for group, leaves in _GROUPS:
        if group in used:
            for leaf in leaves:
                out[group, leaf] = n
                n += 1
    return out


def _shapes(cfg: dict, group: str, heads: int) -> dict[str, tuple]:
    """{leaf: (shape, fan_in or None for a norm)} of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * hd
    if group in ("full", "sliding"):
        return {"attn_norm": ((d,), None), "wq": ((d, heads * hd), d),
                "wk": ((d, kv), d), "wv": ((d, kv), d),
                "wg": ((d, heads), d), "wo": ((heads * hd, d), heads * hd)}
    if group == "dense_ffn":
        f = cfg["intermediate_size"]
        return {"mlp_norm": ((d,), None), "w_gate": ((d, f), d),
                "w_up": ((d, f), d), "w_down": ((f, d), f)}
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    return {"mlp_norm": ((d,), None), "router": ((d, e), d),
            "w_gate": ((e, d, f), d), "w_up": ((e, d, f), d),
            "w_down": ((e, f, d), f), "shared_gate": ((d, fs), d),
            "shared_up": ((d, fs), d), "shared_down": ((fs, d), fs)}


def _served_dtype(cfg: dict):
    return jnp.dtype((cfg.get("precision") or {}).get("weights",
                                                       "bfloat16"))


def _draw(key, shape, fan_in, dtype):
    # the barrier: the scale is one multiplication of the finished normal,
    # whatever the compiler would rather fuse (the program does the same)
    unit = jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))
    return (unit * (fan_in ** -0.5)).astype(dtype).astype(jnp.float32)


def draw_layer(seed, cfg: dict, l: int) -> dict:
    """Layer l's weights, {"attn": {...}, "ffn": {...}}, float32 values the
    served dtype can hold."""
    root = jax.random.key(seed)
    numbers = leaf_numbers(cfg)
    a, ai, f, fi = layer_kinds(cfg)[l]
    heads = cfg["num_attention_heads_per_layer"][l]
    out = {}
    for part, group, i in (("attn", a, ai), ("ffn", f, fi)):
        out[part] = {}
        for leaf, (shape, fan_in) in _shapes(cfg, group, heads).items():
            if fan_in is None:
                out[part][leaf] = jnp.ones(shape, jnp.float32)
                continue
            key = jax.random.fold_in(
                jax.random.fold_in(root, numbers[group, leaf]), i)
            out[part][leaf] = _draw(
                key, shape, fan_in,
                jnp.float32 if leaf == "router" else _served_dtype(cfg))
    return out


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

def lowered(w, lower):
    """A matmul weight [..., in, out] as the lower precision holds it."""
    if lower is None:
        return w
    if lower == "int8":     # per output channel: over the contracted axis
        s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                        1e-8) / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    raise ValueError(lower)


def _mm(x, w, lower=None):
    return jnp.matmul(x, lowered(w, lower), precision=HIGHEST)


# -- the block ---------------------------------------------------------------

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def inv_frequencies(rotated: int, r: dict):
    """The rotated pairs' inverse frequencies and the factor on cos and
    sin, from one entry of `rope_parameters`."""
    base = float(r["rope_theta"])
    pos_freqs = base ** (jnp.arange(0, rotated, 2, dtype=jnp.float32)
                         / rotated)
    if r.get("rope_type", "default") == "default":
        return 1.0 / pos_freqs, 1.0
    factor, orig = float(r["factor"]), r["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (rotated * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(r.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(r.get("beta_slow", 1))), rotated - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rotated // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    extrapolation = 1 - ramp
    inv = (1.0 / (factor * pos_freqs) * (1 - extrapolation)
           + 1.0 / pos_freqs * extrapolation)
    scale = r.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv, float(scale)


def rope(x, positions, r: dict):
    """x [T, H, hd]: the first `partial_rotary_factor` of a head rotates,
    pairs (i, i + rotated / 2); the rest passes through."""
    hd = x.shape[-1]
    rotated = int(hd * r.get("partial_rotary_factor", 1))
    inv, scale = inv_frequencies(rotated, r)
    ang = positions.astype(jnp.float32)[:, None] * inv       # [T, rot/2]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :rotated // 2], x[..., rotated // 2:rotated]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotated:]], -1)


def attention(cfg: dict, l: int, x, w: dict, lower):
    """x [T, d] -> the attention sublayer's output [T, d] (no residual)."""
    t = x.shape[0]
    kind = cfg["layer_types"][l]
    nh = cfg["num_attention_heads_per_layer"][l]
    nkv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    r = cfg["rope_parameters"][kind]
    pos = jnp.arange(t)
    n1 = rmsnorm(x, w["attn_norm"], cfg["rms_norm_eps"])
    q = rope(_mm(n1, w["wq"], lower).reshape(t, nh, hd), pos, r)
    k = rope(_mm(n1, w["wk"], lower).reshape(t, nkv, hd), pos, r)
    v = _mm(n1, w["wv"], lower).reshape(t, nkv, hd)
    gate = jax.nn.sigmoid(_mm(n1, w["wg"], lower))            # [T, nh]
    if cfg.get("_") == "no_gate":
        gate = jnp.ones_like(gate)
    seen = pos[:, None] >= pos[None, :]
    if kind == SLIDING:
        seen &= pos[:, None] - pos[None, :] < cfg["sliding_window"]

    def group(qkv):            # one KV head and its query heads at a time
        qg, kg, vg = qkv       # [T, g, hd], [T, hd], [T, hd]
        s = jnp.einsum("tgh,sh->gts", qg, kg, precision=HIGHEST)
        s = jnp.where(seen[None], s / math.sqrt(hd), -jnp.inf)
        return jnp.einsum("gts,sh->tgh", jax.nn.softmax(s, axis=-1), vg,
                          precision=HIGHEST)

    g = nh // nkv
    o = jax.lax.map(group, (q.reshape(t, nkv, g, hd).swapaxes(0, 1),
                            k.swapaxes(0, 1), v.swapaxes(0, 1)))
    o = o.swapaxes(0, 1).reshape(t, nh, hd) * gate[..., None]
    return _mm(o.reshape(t, nh * hd), w["wo"], lower)


def swiglu(x, gate, up, down, lower):
    return _mm(jax.nn.silu(_mm(x, gate, lower)) * _mm(x, up, lower), down,
               lower)


def route(cfg: dict, n2, router):
    """-> per token and expert, the weight its output takes (0: not
    chosen), [T, E]."""
    scores = jax.nn.sigmoid(_mm(n2, router))
    top, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    top = top / jnp.sum(top, axis=-1, keepdims=True) \
        * cfg["moe_routed_scaling_factor"]
    rows = jnp.arange(n2.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(top)


def ffn(cfg: dict, l: int, h, w: dict, lower):
    n2 = rmsnorm(h, w["mlp_norm"], cfg["rms_norm_eps"])
    if cfg["mlp_layer_types"][l] == "dense":
        return swiglu(n2, w["w_gate"], w["w_up"], w["w_down"], lower)
    weight = route(cfg, n2, w["router"])                      # [T, E]

    def expert(total, e):
        # every expert over every row, kept where the router chose it
        y = swiglu(n2, w["w_gate"][e], w["w_up"][e], w["w_down"][e], lower)
        return total + y * weight[:, e][:, None], None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(n2),
                             jnp.arange(cfg["num_experts"]))
    if cfg.get("_") == "no_shared":
        return routed
    return routed + swiglu(n2, w["shared_gate"], w["shared_up"],
                           w["shared_down"], lower)


def layer(cfg: dict, l: int, x, w: dict, lower):
    """x [B, T, d] -> [B, T, d], a sequence at a time."""
    def one(x):
        h = x + attention(cfg, l, x, w["attn"], lower)
        return h + ffn(cfg, l, h, w["ffn"], lower)
    return jax.lax.map(one, x)


@functools.lru_cache(maxsize=None)
def _compiled(what: str, cfg_json: str, l: int = 0, lower=None):
    """One compiled program per (configuration, layer, precision): a
    second call with the same configuration compiles nothing."""
    cfg = json.loads(cfg_json)
    if what == "ends":
        return jax.jit(lambda s: draw_ends(s, cfg))
    if what == "draw":
        return jax.jit(lambda s: draw_layer(s, cfg, l))
    if what == "layer":
        return jax.jit(lambda x, w: layer(cfg, l, x, w, lower))
    return jax.jit(lambda x, n: rmsnorm(x, n, cfg["rms_norm_eps"]))


def ends(params_or_seed, cfg: dict) -> dict:
    """The embedding, the final norm and the head."""
    if isinstance(params_or_seed, dict):
        return params_or_seed
    return _compiled("ends", json.dumps(cfg, sort_keys=True))(params_or_seed)


def hidden(params_or_seed, tokens, cfg: dict, lower=None, fault=None):
    """tokens [B, T] -> final-normed activations [B, T, d]. With a seed
    (an int) the weights are drawn a layer at a time and dropped after
    use; a dict is `init_params`' result."""
    drawn = isinstance(params_or_seed, dict)
    sound = json.dumps(cfg, sort_keys=True)   # the draw: the sound model's
    run = json.dumps(faulty(cfg, fault), sort_keys=True)
    e = ends(params_or_seed, cfg)
    x = e["embed"][tokens]
    for l in range(cfg["num_hidden_layers"]):
        w = (params_or_seed["layers"][l] if drawn
             else _compiled("draw", sound, l)(params_or_seed))
        x = _compiled("layer", run, l, lower)(x, w)
        del w
    return _compiled("norm", sound)(x, e["final_norm"])


def head(ends: dict, hidden, cfg: dict, lower=None):
    """[..., d] -> [..., vocab]: position t scores token t + 1."""
    return _mm(hidden, ends["lm_head"], lower)


def logits(params_or_seed, tokens, cfg: dict, lower=None, fault=None):
    """tokens [B, T] -> [B, T, vocab] (toy sizes: every logit at once)."""
    return head(ends(params_or_seed, cfg),
                hidden(params_or_seed, tokens, cfg, lower, fault), cfg,
                lower)

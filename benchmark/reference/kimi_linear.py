"""Plain reference for the Kimi-Linear family: KDA linear-attention layers
(a gated delta rule with a per-channel decay), NoPE latent attention every
fourth layer, a dense SwiGLU first layer and sigmoid-routed experts with one
shared expert in the others. Written from the layer equations of the Kimi
Linear technical report and the `fla` `KimiDeltaAttention` layer (ISSUE 27
states them): float32 jax.numpy at the highest matmul precision, no kernel,
no chunking of the recurrence, no batching. It imports nothing of the
program and takes nothing the program made.

Pre-norm residual blocks, eps from the configuration, untied head, SiLU:

    h = x + Attn(rmsnorm(x)),   y = h + FFN(rmsnorm(h))

KDA layer (H heads of dk = dv = head_dim), n = rmsnorm(x):
    q, k, v = silu(conv4(n Wq)), silu(conv4(n Wk)), silu(conv4(n Wv))
        conv4: causal, depthwise, kernel 4, no bias (tap 3 is the current token)
    q = l2norm(q) * dk^-0.5, k = l2norm(k)            (per head, eps 1e-6)
    g_t = -exp(A_log_h) * softplus((n Wf_a) Wf_b + dt_bias)    [H, dk], <= 0
    beta_t = sigmoid(n Wb)                                     [H]
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    out = [rmsnorm_head(o_t) * sigmoid((n Wg_a) Wg_b)] Wo
  followed position by position (a scan over positions, rematerialised in
  blocks of 64 so that its backward fits: that changes no arithmetic).

Latent attention (`mla_use_nope`, no q_lora): q = n Wq (H x 192);
    [c ; k_r] = n Wkva (512 + 64); [k_nope ; v] = rmsnorm(c) Wkvb; k = [k_nope ;
    k_r], k_r shared by the heads, NO rotary on its 64 channels; causal
    softmax(q k^T / sqrt(192)) v in blocks of queries; Wo.

FFN: layer 1 SwiGLU(intermediate_size); the others
    shared(n) + routed_scaling_factor * sum_{e in top-k} w_e expert_e(n),
    s = sigmoid(n Wr) over ALL `n_router_experts`, top-k of s + bias (one
    group: plain top-k; the bias is a buffer, zero here), w = s / sum over
    the chosen. THE SHARE: the configuration's `num_experts` is how many
    experts this rank holds (`published.num_experts` is the router's width);
    the rank adds only the chosen experts among those it holds, from
    `first_expert` on, as a loop over them with a mask. What the absent
    experts would add is left out; no auxiliary loss.

Departures from the published model, all stated in the configuration under
`assumed`: the gate rank (head_dim), no conv bias, A_log = log U(1, 16) and
dt_bias = softplus^-1 of log-uniform(1e-3, 1e-1) as `fla` initialises them,
the zero router bias.

Weights are random: `init_params` draws every matrix as normal / sqrt(fan_in)
from a key of its own: `split(key(seed), layers + 1)`, the first for the
embedding (head: fold_in(., 1)), layer i's folded with the leaf's index in
`LEAVES`. The program's `init` makes the same draw, and a test pins the two
bit for bit.

Lower precisions, for the controls only (`lower`): "fp8" rounds weights and
matmul inputs to e4m3, straight through (one step under the trained
bfloat16), which `correct` refuses; "bf16_state" rounds the KDA state to
bfloat16 at every position and the gate's pre-activation and log-decay once
(the program keeps them in float32 beside its bfloat16 compute). It is a
LOGGED control: on the chip it reads inside the program's own range on every
compared number (PERF.md section 6), so the configuration states no
precision for the state. `fault="no_routed"` leaves the routed sum out (a
planted fault).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 64          # positions per rematerialised block of the recurrence
Q_BLOCK = 128       # queries per block of the latent attention
CE_BLOCK = 2048     # positions per block of the cross-entropy

#: every leaf a layer can have, in the order that keys its draw
LEAVES = ("attn_norm", "mlp_norm",
          "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "f_a", "f_b",
          "A_log", "dt_bias", "wb", "g_a", "g_b", "o_norm", "wo",
          "wkva", "kv_norm", "wkvb",
          "w_gate", "w_up", "w_down",
          "router", "router_bias", "e_gate", "e_up", "e_down",
          "s_gate", "s_up", "s_down")


def dims(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    pub = cfg.get("published", {})
    L = cfg["num_hidden_layers"]
    return dict(
        d=cfg["hidden_size"], v=cfg["vocab_size"], L=L,
        eps=float(cfg["rms_norm_eps"]),
        # KDA
        h=lin["num_heads"], dk=lin["head_dim"], conv=lin["short_conv_kernel_size"],
        kda=[i in lin["kda_layers"] for i in range(1, L + 1)],
        # latent attention
        nh=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
        rank=cfg["kv_lora_rank"],
        # FFN
        f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        dense=[i < cfg["first_k_dense_replace"] for i in range(L)],
        held=cfg["num_experts"],
        experts=pub.get("num_experts", cfg["num_experts"]),
        first=cfg.get("first_expert", 0), topk=cfg["num_experts_per_token"],
        scale=float(cfg["routed_scaling_factor"]),
        renorm=bool(cfg["moe_renormalize"]))


def layer_shapes(c: dict, i: int) -> dict:
    """{leaf: (shape, fan_in or a rule's name)} of layer i (from 0)."""
    d, h, dk = c["d"], c["h"], c["dk"]
    out = {"attn_norm": ((d,), "one"), "mlp_norm": ((d,), "one")}
    if c["kda"][i]:
        out.update({
            "wq": ((d, h * dk), d), "wk": ((d, h * dk), d),
            "wv": ((d, h * dk), d),
            "conv_q": ((c["conv"], h * dk), c["conv"]),
            "conv_k": ((c["conv"], h * dk), c["conv"]),
            "conv_v": ((c["conv"], h * dk), c["conv"]),
            "f_a": ((d, dk), d), "f_b": ((dk, h * dk), dk),
            "A_log": ((h,), "a_log"), "dt_bias": ((h * dk,), "dt_bias"),
            "wb": ((d, h), d),
            "g_a": ((d, dk), d), "g_b": ((dk, h * dk), dk),
            "o_norm": ((dk,), "one"), "wo": ((h * dk, d), h * dk)})
    else:
        nh, qk = c["nh"], c["nope"] + c["rope"]
        out.update({
            "wq": ((d, nh * qk), d),
            "wkva": ((d, c["rank"] + c["rope"]), d),
            "kv_norm": ((c["rank"],), "one"),
            "wkvb": ((c["rank"], nh * (c["nope"] + c["vd"])), c["rank"]),
            "wo": ((nh * c["vd"], d), nh * c["vd"])})
    if c["dense"][i]:
        f = c["f"]
        out.update({"w_gate": ((d, f), d), "w_up": ((d, f), d),
                    "w_down": ((f, d), f)})
    else:
        e, f = c["held"], c["fe"]
        out.update({
            "router": ((d, c["experts"]), d),
            "router_bias": ((c["experts"],), "zero"),
            "e_gate": ((e, d, f), d), "e_up": ((e, d, f), d),
            "e_down": ((e, f, d), f),
            "s_gate": ((d, f), d), "s_up": ((d, f), d),
            "s_down": ((f, d), f)})
    return out


def draw(key, shape, rule):
    if rule == "one":
        return jnp.ones(shape, jnp.float32)
    if rule == "zero":
        return jnp.zeros(shape, jnp.float32)
    if rule == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if rule == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1(dt)
    return jax.random.normal(key, shape, jnp.float32) / (rule ** 0.5)


def init_params(seed, cfg: dict) -> dict:
    c = dims(cfg)
    keys = jax.random.split(jax.random.key(seed), c["L"] + 1)
    layers = {}
    for i in range(c["L"]):
        layers[f"{i:02d}"] = {
            name: draw(jax.random.fold_in(keys[i + 1], LEAVES.index(name)),
                       shape, rule)
            for name, (shape, rule) in layer_shapes(c, i).items()}
    return {"embed": draw(keys[0], (c["v"], c["d"]), c["d"]),
            "layers": layers,
            "final_norm": jnp.ones((c["d"],), jnp.float32),
            "lm_head": draw(jax.random.fold_in(keys[0], 1),
                            (c["d"], c["v"]), c["d"])}


def leaf_of(program_leaf: str) -> str:
    """The reference's name of a program leaf ("layers/03/wq"): the two
    trees are named alike."""
    return program_leaf


# -- lower precisions (the controls) ------------------------------------------

def _round_fp8(x):
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    r = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(r - x)   # straight through


def _round_bf16(x):
    """To bfloat16's 8 bits of mantissa, straight through. `reduce_precision`
    and not a pair of casts: XLA may drop a cast to a narrower type and back
    (`xla_allow_excess_precision`), and on the TPU it does."""
    return x + jax.lax.stop_gradient(
        jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) - x)


def _mm(x, w, lower):
    if lower == "fp8":
        x, w = _round_fp8(x), _round_fp8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


# -- the block ------------------------------------------------------------------

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def conv4(x, w):
    """x [T, C], w [K, C]: y_t = sum_j w[j] x_{t - (K-1) + j}, zeros before
    the first position."""
    k = w.shape[0]
    pad = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(pad[j:j + x.shape[0]] * w[j] for j in range(k))


def delta_rule(q, k, v, g, beta, lower=None):
    """The recurrence, one position at a time: q, k, g [T, H, dk], v
    [T, H, dv], beta [T, H] -> o [T, H, dv]."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    low = _round_bf16 if lower == "bf16_state" else (lambda x: x)

    def step(s, x):
        q, k, v, g, b = x
        s = s * jnp.exp(g)[:, :, None]
        u = v - jnp.einsum("hd,hde->he", k, s, precision=HIGHEST)
        s = low(s + (b[:, None] * k)[:, :, None] * u[:, None, :])
        return s, jnp.einsum("hd,hde->he", q, s, precision=HIGHEST)

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(step, s, xs)

    pad = -t % BLOCK
    xs = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
          for x in (q, k, v, g, beta)]
    xs = [x.reshape(-1, BLOCK, *x.shape[1:]) for x in xs]
    _, o = jax.lax.scan(block, jnp.zeros((h, dk, dv), jnp.float32), xs)
    return o.reshape(-1, h, dv)[:t]


def kda(c, n, w, lower):
    """n [T, d] (normed) -> [T, d]. The branches around the recurrence are
    rematerialised one by one (that changes no arithmetic): a row's two dozen
    float32 [T, H dk] intermediates do not fit beside the parameters and
    their gradient otherwise."""
    t = n.shape[0]
    h, dk = c["h"], c["dk"]

    @jax.checkpoint
    def branch(n, proj, conv):
        return jax.nn.silu(conv4(_mm(n, proj, lower), conv)).reshape(t, h, dk)

    @jax.checkpoint
    def decay(n, f_a, f_b, dt_bias, a_log):
        pre = _mm(_mm(n, f_a, lower), f_b, lower) + dt_bias
        if lower == "bf16_state":
            pre = _round_bf16(pre)
        g = -jnp.exp(a_log)[None, :, None] * jax.nn.softplus(
            pre).reshape(t, h, dk)
        return _round_bf16(g) if lower == "bf16_state" else g

    @jax.checkpoint
    def gated(o, n, g_a, g_b, o_norm):
        gate = jax.nn.sigmoid(_mm(_mm(n, g_a, lower), g_b, lower))
        return (rmsnorm(o, o_norm, c["eps"]) * gate.reshape(t, h, dk)
                ).reshape(t, h * dk)

    q = l2norm(branch(n, w["wq"], w["conv_q"])) * dk ** -0.5
    k = l2norm(branch(n, w["wk"], w["conv_k"]))
    v = branch(n, w["wv"], w["conv_v"])
    g = decay(n, w["f_a"], w["f_b"], w["dt_bias"], w["A_log"])
    beta = jax.nn.sigmoid(_mm(n, w["wb"], lower))
    o = delta_rule(q, k, v, g, beta, lower)
    return _mm(gated(o, n, w["g_a"], w["g_b"], w["o_norm"]), w["wo"], lower)


def mla(c, n, w, lower):
    t = n.shape[0]
    nh, nope, rope, vd, rank = (c[k] for k in ("nh", "nope", "rope", "vd",
                                               "rank"))
    q = _mm(n, w["wq"], lower).reshape(t, nh, nope + rope)
    ckr = _mm(n, w["wkva"], lower)
    kv = _mm(rmsnorm(ckr[:, :rank], w["kv_norm"], c["eps"]), w["wkvb"],
             lower).reshape(t, nh, nope + vd)
    k = jnp.concatenate(
        [kv[:, :, :nope],
         jnp.broadcast_to(ckr[:, None, rank:], (t, nh, rope))], axis=-1)
    v = kv[:, :, nope:]
    pos = jnp.arange(t)

    @jax.checkpoint
    def rows(args):
        qb, pb = args                                  # [Bq, nh, qk], [Bq]
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST)
        s = s / math.sqrt(nope + rope)
        s = jnp.where(pb[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    pad = -t % Q_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, nh,
                                                        nope + rope)
    pp = jnp.pad(pos, (0, pad), constant_values=t - 1).reshape(-1, Q_BLOCK)
    o = jax.lax.map(rows, (qp, pp)).reshape(-1, nh * vd)[:t]
    return _mm(o, w["wo"], lower)


def swiglu(n, gate, up, down, lower):
    return _mm(jax.nn.silu(_mm(n, gate, lower)) * _mm(n, up, lower), down,
               lower)


def route(c, n, w):
    """-> (ids [T, k], weights [T, k]) over all the router's experts."""
    s = jax.nn.sigmoid(jnp.matmul(n, w["router"], precision=HIGHEST))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(w["router_bias"]),
                           c["topk"])
    p = jnp.take_along_axis(s, idx, axis=1)
    if c["renorm"]:
        p = p / jnp.sum(p, axis=1, keepdims=True)
    return idx, p * c["scale"]


def routed(c, n, w, lower, first=None):
    """The part of the routed sum that experts [first, first + held) give."""
    first = c["first"] if first is None else first
    idx, p = route(c, n, w)
    out = jnp.zeros_like(n)
    for e in range(c["held"]):
        mine = jnp.sum(jnp.where(idx == first + e, p, 0.0), axis=1)
        out = out + mine[:, None] * swiglu(n, w["e_gate"][e], w["e_up"][e],
                                           w["e_down"][e], lower)
    return out


def ffn(c, i, n, w, lower, fault=None):
    if c["dense"][i]:
        return swiglu(n, w["w_gate"], w["w_up"], w["w_down"], lower)
    out = swiglu(n, w["s_gate"], w["s_up"], w["s_down"], lower)
    if fault == "no_routed":
        return out
    return out + routed(c, n, w, lower)


def layer(c, i, x, w, lower, fault=None):
    """x [T, d] -> [T, d]: one row of the batch."""
    n = rmsnorm(x, w["attn_norm"], c["eps"])
    h = x + (kda if c["kda"][i] else mla)(c, n, w, lower)
    return h + ffn(c, i, rmsnorm(h, w["mlp_norm"], c["eps"]), w, lower, fault)


def hidden(params: dict, tokens, cfg: dict, lower=None, fault=None,
           remat=False):
    """tokens [B, T] -> final-normed activations [B, T, d]."""
    c = dims(cfg)

    def row(toks):
        x = params["embed"][toks]
        for i in range(c["L"]):
            f = lambda x, w, i=i: layer(c, i, x, w, lower, fault)
            x = (jax.checkpoint(f) if remat else f)(
                x, params["layers"][f"{i:02d}"])
        return rmsnorm(x, params["final_norm"], c["eps"])

    # rows side by side (not one after the other: a loop over rows would
    # hold a second gradient tree while it adds each row's to the sum)
    return jax.vmap(row)(tokens)


def logits(params: dict, tokens, cfg: dict, lower=None):
    """tokens [B, T] -> [B, T, vocab]: position t scores token t + 1."""
    return _mm(hidden(params, tokens, cfg, lower), params["lm_head"], lower)


# -- training: loss, gradient, AdamW ----------------------------------------------

def loss_sum(params: dict, tokens, cfg: dict, lower=None, fault=None):
    """Sum over rows and positions of the next-token cross-entropy, and the
    number of targets."""
    h = hidden(params, tokens, cfg, lower, fault, remat=True)
    b, t = tokens.shape

    @jax.checkpoint
    def ce(args):       # a block of positions: its targets' summed loss
        h, targets, valid = args
        lg = _mm(h, params["lm_head"], lower)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(valid * (lse - jnp.take_along_axis(
            lg, targets[..., None], axis=-1)[..., 0]))

    # in blocks so that the [positions, vocabulary] logits are never whole;
    # padded positions count nothing
    pad = -(t - 1) % CE_BLOCK
    blocks = lambda x: jnp.pad(
        x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)).reshape(
            -1, CE_BLOCK, *x.shape[2:])
    total = jnp.sum(jax.lax.map(ce, (
        blocks(h[:, :-1]), blocks(tokens[:, 1:]),
        blocks(jnp.ones((b, t - 1), jnp.float32)))))
    return total, b * (t - 1)


def tree_norms(tree: dict) -> dict:
    """{"a/b": l2 norm} of every leaf."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(k.key) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for path, leaf in flat}


def learning_rate(opt: dict, count: int) -> float:
    """Linear warm-up from 0 to the peak over `warmup_steps`; the reference
    follows only steps inside the warm-up."""
    if count >= opt["warmup_steps"]:
        raise ValueError("the reference follows warm-up steps only")
    return opt["learning_rate"] * count / opt["warmup_steps"]


def clip(grads: dict, max_norm: float):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                      for g in jax.tree.leaves(grads)))
    scale = 1.0 / jnp.maximum(1.0, gn / max_norm)
    return jax.tree.map(lambda g: g * scale, grads), gn


def adamw(params, mu, nu, grads, t: int, opt: dict):
    """Step t (from 1) of AdamW, decay on every leaf, learning rate read at
    count t - 1."""
    b1, b2, eps = opt["b1"], opt["b2"], 1e-8
    lr = learning_rate(opt, t - 1)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)

    def step(p, m, n):
        mh, nh = m / (1 - b1 ** t), n / (1 - b2 ** t)
        return p - lr * (mh / (jnp.sqrt(nh) + eps) + opt["weight_decay"] * p)

    return jax.tree.map(step, params, mu, nu), mu, nu

"""Plain reference for the Mistral-7B family (dense GQA + RoPE + SwiGLU),
written from the published block (mistralai/mistral-inference `model.py`,
HF `modeling_mistral.py`): float32 jax.numpy, highest matmul precision, no
kernel, no cache, no batching of requests. It imports nothing of the program
and takes nothing the program made.

    h = x + wo(attn(rope(wq n1), rope(wk n1), wv n1)),  n1 = rmsnorm(x, attention_norm)
    y = h + w2(silu(w1 n2) * w3 n2),                    n2 = rmsnorm(h, ffn_norm)
    logits = output(rmsnorm(y_L, norm))

RoPE is the rotate-half form HF uses (pairs (i, i + hd/2)), theta from the
configuration; attention is causal softmax over all earlier positions (no
sliding window in v0.3), KV heads repeated over their query groups.
Parameter names are Mistral's own; per-layer tensors are stacked on a
leading layer axis.

Weights are random. `init_params` draws them by the convention the
benchmark's configurations state under "weights": normal / sqrt(fan_in)
from jax.random.key(seed) split eight ways, in the order below. It is the
same draw the program's random initialisation makes, which is what lets a
reference that takes nothing from the program hold the same model; a test
(benchmark/tests) pins the two to each other bit for bit.

Lower precisions, for the control only, as an argument `lower` of the same
functions: `lower="int4"` rounds every matmul
weight to 4-bit symmetric per output channel and K, V to 4 bits per token
and head (one step under the served int8); `lower="fp8"` rounds weights
and matmul inputs to e4m3 with a per-tensor scale (one step under the
trained bfloat16), straight-through in the backward pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return dict(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                nh=cfg["num_attention_heads"], nkv=cfg["num_key_value_heads"],
                hd=hd, v=cfg["vocab_size"], L=cfg["num_hidden_layers"],
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def init_params(seed: int, cfg: dict) -> dict:
    c = dims(cfg)
    d, f, hd, nh, nkv, L, v = (c[k] for k in ("d", "f", "hd", "nh", "nkv",
                                              "L", "v"))
    keys = jax.random.split(jax.random.key(seed), 8)

    def dense(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) / (fan_in ** 0.5)

    return {
        "tok_embeddings": dense(keys[0], (v, d), d),
        "layers": {
            "wq": dense(keys[1], (L, d, nh * hd), d),
            "wk": dense(keys[2], (L, d, nkv * hd), d),
            "wv": dense(keys[3], (L, d, nkv * hd), d),
            "wo": dense(keys[4], (L, nh * hd, d), nh * hd),
            "w1": dense(keys[5], (L, d, f), d),       # gate
            "w3": dense(keys[6], (L, d, f), d),       # up
            "w2": dense(keys[7], (L, f, d), f),       # down
            "attention_norm": jnp.ones((L, d), jnp.float32),
            "ffn_norm": jnp.ones((L, d), jnp.float32),
        },
        "norm": jnp.ones((d,), jnp.float32),
        "output": dense(jax.random.fold_in(keys[0], 1), (d, v), d),
    }


# -- lower precisions (the control) -------------------------------------------

def _round_int(x, axis, levels):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-8) / levels
    return jnp.clip(jnp.round(x / s), -levels, levels) * s


def _round_fp8(x):
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    r = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(r - x)   # straight through


def lowered(w, lower: str | None):
    """A matmul weight as the lower precision would hold it. Applied where
    the weight is used, one layer at a time, so that no second copy of the
    model is ever held."""
    if lower is None:
        return w
    if lower == "int4":   # per output channel: over the contracted axis
        return _round_int(w, axis=-2, levels=7.0)
    if lower == "fp8":
        return _round_fp8(w)
    raise ValueError(lower)


# -- the block ------------------------------------------------------------------

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [..., T, H, hd]; rotate-half pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv          # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(x, w, lower):
    if lower == "fp8":
        x = _round_fp8(x)
    return jnp.matmul(x, lowered(w, lower), precision=HIGHEST)


def layer(c: dict, x, w: dict, lower):
    """x [B, T, d] -> [B, T, d]."""
    b, t, _ = x.shape
    nh, nkv, hd = c["nh"], c["nkv"], c["hd"]
    pos = jnp.arange(t)
    n1 = rmsnorm(x, w["attention_norm"], c["eps"])
    q = rope(_mm(n1, w["wq"], lower).reshape(b, t, nh, hd), pos, c["theta"])
    k = rope(_mm(n1, w["wk"], lower).reshape(b, t, nkv, hd), pos, c["theta"])
    v = _mm(n1, w["wv"], lower).reshape(b, t, nkv, hd)
    if lower == "int4":
        k, v = (_round_int(a, axis=-1, levels=7.0) for a in (k, v))
    g = nh // nkv
    q = q.reshape(b, t, nkv, g, hd)
    s = jnp.einsum("btkgh,bskh->bkgts", q, k, precision=HIGHEST)
    s = s / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskh->btkgh", p, v, precision=HIGHEST)
    h = x + _mm(o.reshape(b, t, nh * hd), w["wo"], lower)
    n2 = rmsnorm(h, w["ffn_norm"], c["eps"])
    up = jax.nn.silu(_mm(n2, w["w1"], lower)) * _mm(n2, w["w3"], lower)
    return h + _mm(up, w["w2"], lower)


def hidden(params: dict, tokens, cfg: dict, lower=None, remat=False):
    """tokens [B, T] -> final-normed activations [B, T, d]."""
    c = dims(cfg)
    x = params["tok_embeddings"][tokens]
    body = lambda x, w: (layer(c, x, w, lower), None)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["norm"], c["eps"])


def logits(params: dict, tokens, cfg: dict, lower=None):
    """tokens [B, T] -> [B, T, vocab]: position t scores token t + 1."""
    return _mm(hidden(params, tokens, cfg, lower), params["output"], lower)


# -- training: loss, gradient, AdamW ----------------------------------------------

def loss_sum(params: dict, tokens, cfg: dict, lower=None):
    """Sum over rows and positions of the next-token cross-entropy, and
    the number of targets: tokens [B, S] gives B * (S - 1) targets."""
    h = hidden(params, tokens, cfg, lower, remat=True)

    @jax.checkpoint
    def ce(h, targets):
        lg = _mm(h, params["output"], lower)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(
            lg, targets[..., None], axis=-1)[..., 0])

    return ce(h[:, :-1], tokens[:, 1:]), tokens.shape[0] * (tokens.shape[1] - 1)


def tree_norms(tree: dict) -> dict:
    """{"a/b": l2 norm} of every leaf."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(k.key) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for path, leaf in flat}


def learning_rate(opt: dict, count: int) -> float:
    """Linear warm-up from 0 to the peak over `warmup_steps`; the reference
    follows only steps inside the warm-up, so the decay never enters."""
    if count >= opt["warmup_steps"]:
        raise ValueError("the reference follows warm-up steps only")
    return opt["learning_rate"] * count / opt["warmup_steps"]


def clip(grads: dict, max_norm: float):
    """Global-norm clipping: what the optimizer gets."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                      for g in jax.tree.leaves(grads)))
    scale = 1.0 / jnp.maximum(1.0, gn / max_norm)
    return jax.tree.map(lambda g: g * scale, grads), gn


def adamw(params, mu, nu, grads, t: int, opt: dict):
    """Step t (from 1) of AdamW (Loshchilov & Hutter), decay on every leaf,
    learning rate read at count t - 1."""
    b1, b2, eps = opt["b1"], opt["b2"], 1e-8
    lr = learning_rate(opt, t - 1)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)

    def step(p, m, n):
        mh, nh = m / (1 - b1 ** t), n / (1 - b2 ** t)
        return p - lr * (mh / (jnp.sqrt(nh) + eps) + opt["weight_decay"] * p)

    return jax.tree.map(step, params, mu, nu), mu, nu

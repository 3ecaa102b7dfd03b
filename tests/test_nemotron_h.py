"""Nemotron-H as a served family, at a toy size on the CPU: the weights and
the forward pass against the benchmark's plain reference, prefill then
decode through the state slab (a prompt padded inside a larger bucket, a
chained continuation, a slot reused by a second request), the chunked scan
and the one-step state kernel in the interpreter against the recurrence,
the latent squared-ReLU experts and the expert-parallel share, the planted
faults, the engine, and what the family refuses by name.

The toy keeps every kind of the cell (a state-space layer, an expert layer,
the attention layer) with a router over 16 experts of which a rank holds 4,
float32 so that a gap is the code's and not rounding's."""

import functools
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeflow_tpu.models import nemotron_h as nh
from kubeflow_tpu.obs.metrics import render_metrics
from kubeflow_tpu.ops import moe, ssd
from kubeflow_tpu.ops.moe import ShareArgs, moe_share_mlp
from kubeflow_tpu.serving.llm import LLMEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from reference import nemotron_h as ref  # noqa: E402

TOY = json.load(open(os.path.join(
    ROOT, "benchmark", "tests", "toy_nemotron.json")))["config"]
PUBLISHED = json.load(open(os.path.join(
    ROOT, "benchmark", "configs",
    "nemotron-3-super-120b-a12b-serve-ep4.json")))
#: the reference's configuration: the cell's file under the toy's sizes,
#: one layer of each kind and a second state-space layer
RCFG = {**PUBLISHED, **{k: v for k, v in TOY.items() if k != "system"},
        "num_hidden_layers": 5, "hybrid_override_pattern": "ME*EM"}
KEYS = PUBLISHED["system"]["model_keys"]
SEED = 7


def _cfg(**kw):
    return nh.NemotronHConfig(**{**{k: RCFG[k] for k in KEYS},
                                 "n_router_experts": 16,
                                 "dtype": jnp.float32, **kw})


@pytest.fixture(scope="module")
def params():
    return nh.init(jax.random.key(SEED), _cfg())


def _tokens(n, seed=3, batch=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, RCFG["vocab_size"], (batch, n)), jnp.int32)


def _ref_logits(toks, fault=None, lower=None, cfg=RCFG):
    return np.asarray(ref.logits(SEED, toks, cfg, lower=lower, fault=fault))


# -- the plain forward pass ---------------------------------------------------

@pytest.mark.parametrize("layer,kind,leaves", [
    (0, "mamba", ("in_proj", "conv_w", "conv_b", "dt_bias", "a_log",
                  "d_skip", "out_proj")),
    (2, "attn", ("w_q", "w_k", "w_v", "w_o")),
    (3, "moe", ("router", "router_bias", "latent_down", "w_up", "w_down",
                "latent_up", "shared_up", "shared_down"))])
def test_weights_are_the_references_bit_for_bit(params, layer, kind, leaves):
    """The program draws what the reference draws, leaf by leaf: the
    experts held, the router over all 16, A_log and dt_bias by Mamba-2's
    recipe."""
    # the draw the reference computes with: one compiled program a layer
    w = ref._compiled("draw", json.dumps(RCFG, sort_keys=True), layer)(SEED)
    at = ref.pattern(RCFG)[layer][1]
    for leaf in leaves:
        np.testing.assert_array_equal(params[kind][leaf][at], w[leaf],
                                      err_msg=leaf)
    if kind == "mamba":
        a = np.exp(np.asarray(w["a_log"]))
        assert (a >= 1).all() and (a <= 16).all()
        dt = np.log1p(np.exp(np.asarray(w["dt_bias"])))
        assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    if kind == "moe":
        assert params["moe"]["router"].shape[-1] == 16


def test_prefill_logits_are_the_references(params):
    toks = _tokens(40)
    got = np.asarray(nh.apply(params, toks, _cfg()))
    np.testing.assert_allclose(got, _ref_logits(toks), atol=2e-4)


def _write(cache, slot, start, count, ks, vs, i):
    return nh.cache_write(cache, slot, start, count, ks[:, i],
                          jax.tree.map(lambda a: a[:, i], vs))


def _serve(params, cfg, toks, first, bucket, chunks, steps, cache=None,
           slots=None):
    """Prefill the first `first` tokens padded to `bucket`, continue chunk
    by chunk against the slot, then decode `steps` tokens (teacher-forced),
    row i in slot slots[i]: the logits of every position from the first
    chunk's last."""
    b = toks.shape[0]
    slots = list(range(b)) if slots is None else slots
    cache = nh.init_cache(cfg, 4, 96) if cache is None else cache
    padded = jnp.pad(toks[:, :first], ((0, 0), (0, bucket - first)))
    lg, ks, vs = nh.prefill(params, padded, cfg,
                            logit_rows=jnp.full((b,), first - 1))
    out = [lg]
    for i, s in enumerate(slots):
        cache = _write(cache, s, 0, bucket, ks, vs, i)
    at = first
    for n in chunks:
        got = [nh.extract_prefix(cfg, cache, s, at) for s in slots]
        kp = jnp.concatenate([g[0] for g in got], axis=1)
        vp = jax.tree.map(lambda *a: jnp.concatenate(a, axis=1),
                          *[g[1] for g in got])
        lg, ks, vs = nh.prefill_continue(params, toks[:, at:at + n], kp, vp,
                                         cfg)
        out.append(lg[:, -1])
        for i, s in enumerate(slots):
            cache = _write(cache, s, at, n, ks, vs, i)
        at += n
    lengths = jnp.zeros((4,), jnp.int32).at[jnp.asarray(slots)].set(at)
    active = jnp.zeros((4,), bool).at[jnp.asarray(slots)].set(True)
    for t in range(steps):
        last = jnp.zeros((4,), jnp.int32).at[jnp.asarray(slots)].set(
            toks[:, at + t])
        lg, cache = nh.decode_step(params, last, cache, lengths, cfg,
                                   active=active)
        cache.pop("counters")
        out.append(lg[jnp.asarray(slots)])
        lengths = lengths + active
    return np.stack([np.asarray(o) for o in out], 1), cache


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "interpret"])
def test_prefill_chain_then_decode_through_the_state_slab(params, kernels,
                                                          monkeypatch):
    """13 tokens padded to a bucket of 32, chunks of 8 and 11 after them,
    then 8 decode steps: every position's logits against the reference's
    one pass over the whole sequence (the pad changed no state)."""
    monkeypatch.setattr(ssd, "FORCE_INTERPRET", kernels)
    cfg = _cfg()
    toks = _tokens(40)
    got, _ = _serve(params, cfg, toks, 13, 32, (8, 11), 8)
    want = _ref_logits(toks)
    at = [12, 20, 31] + list(range(32, 40))
    np.testing.assert_allclose(got, want[:, at], atol=3e-4)


def test_a_reused_slot_starts_clean(params):
    """A first request decodes in slots 1 and 2; a second prefills into
    the same slots: its logits are those of a fresh cache."""
    cfg = _cfg()
    first, second = _tokens(30, seed=1), _tokens(30, seed=2)
    _, cache = _serve(params, cfg, first, 17, 32, (), 6, slots=[1, 2])
    reused, _ = _serve(params, cfg, second, 9, 16, (), 6, cache=cache,
                       slots=[1, 2])
    fresh, _ = _serve(params, cfg, second, 9, 16, (), 6, slots=[1, 2])
    np.testing.assert_allclose(reused, fresh, atol=1e-6)
    np.testing.assert_allclose(
        reused, _ref_logits(second)[:, 8:15], atol=3e-4)


# -- the two kernels against the recurrence ----------------------------------

def _ssm_inputs(b, s, h=8, p=16, g=2, n=16, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)  # noqa
    x, bm, cm = f(b, s, h, p), f(b, s, g, n), f(b, s, g, n)
    dt = jnp.asarray(np.log1p(np.exp(rng.standard_normal((b, s, h)) - 3)),
                     jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, h), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((b, h, p, n)), jnp.float32)
    return x, dt, a, bm, cm, h0


@pytest.mark.parametrize("lengths", [(200, 77), (128, 1), (57, 256)])
def test_scan_kernel_matches_the_recurrence(lengths, monkeypatch):
    """Rows of lengths that are not multiples of 128 (and one of a single
    position) in one call of 256 positions: y up to each length and the
    state AT each length, from a given start state."""
    x, dt, a, bm, cm, h0 = _ssm_inputs(2, 256)
    lens = jnp.asarray(lengths)
    monkeypatch.setattr(ssd, "FORCE_INTERPRET", True)
    y, h = ssd.ssd_scan(x, dt, a, bm, cm, h0, lens)
    for i, n in enumerate(lengths):
        wy, wh = ssd.ssd_recurrence(x[i:i + 1, :n], dt[i:i + 1, :n], a,
                                    bm[i:i + 1, :n], cm[i:i + 1, :n],
                                    h0[i:i + 1])
        np.testing.assert_allclose(y[i, :n], wy[0], atol=5e-5, rtol=1e-5)
        np.testing.assert_allclose(h[i], wh[0], atol=5e-5, rtol=1e-5)


def test_scan_kernel_in_bfloat16_stays_near_the_recurrence(monkeypatch):
    """The model dtype's operands (the state carried in float32): within
    bfloat16's rounding of the float32 recurrence on the same inputs."""
    x, dt, a, bm, cm, h0 = _ssm_inputs(1, 300, dtype=jnp.bfloat16)
    monkeypatch.setattr(ssd, "FORCE_INTERPRET", True)
    y, h = ssd.ssd_scan(x, dt, a, bm, cm, h0, jnp.asarray([300]))
    wy, wh = ssd.ssd_recurrence(x.astype(jnp.float32), dt, a,
                                bm.astype(jnp.float32),
                                cm.astype(jnp.float32), h0)
    scale = float(np.abs(np.asarray(wy)).max())
    assert float(np.abs(np.asarray(y, np.float32) - wy).max()) < 2e-2 * scale
    assert float(np.abs(np.asarray(h) - wh).max()) < 2e-2 * float(
        np.abs(np.asarray(wh)).max())


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16])
def test_state_step_kernel_matches_one_recurrence_step(state_dtype):
    """Layer 1 of a 3-layer slab over 5 slots, in place: one step of the
    recurrence on every slot, the other layers untouched, and a slot's
    junk (a dead slot's inputs) lands in its own state only."""
    x, dt, a, bm, cm, _ = _ssm_inputs(5, 1, seed=4)
    x, dt, bm, cm = x[:, 0], dt[:, 0], bm[:, 0], cm[:, 0]
    rng = np.random.default_rng(5)
    slab = jnp.asarray(rng.standard_normal((3, 5, 8, 16, 16)), state_dtype)
    wh, wy = ssd.ssm_step_xla(slab[1], x, dt, a, bm, cm)
    got, y = ssd._step_pallas(slab, 1, x, dt, jnp.exp(dt * a), bm, cm,
                              interpret=True)
    tol = 1e-5 if state_dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got[1], np.float32),
                               np.asarray(wh.astype(state_dtype), np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(y, wy, atol=tol * 10, rtol=tol)
    np.testing.assert_array_equal(got[0], slab[0])
    np.testing.assert_array_equal(got[2], slab[2])
    # slot 3 fed junk: slots 0-2 and 4 come out as they would without it
    junk = x.at[3].set(1e3)
    got2, y2 = ssd._step_pallas(slab, 1, junk, dt, jnp.exp(dt * a), bm, cm,
                                interpret=True)
    keep = np.asarray([0, 1, 2, 4])
    np.testing.assert_array_equal(got2[1][keep], got[1][keep])
    np.testing.assert_array_equal(y2[keep], y[keep])


def test_decode_counts_live_state_rows_and_the_prompt_tokens(params):
    cfg = _cfg()
    cache = nh.init_cache(cfg, 3, 64)
    _, out = nh.decode_step(params, jnp.asarray([1, 2, 3]), cache,
                            jnp.asarray([5, 20, 63], jnp.int32), cfg,
                            active=jnp.asarray([True, False, True]))
    counts = dict(zip((n for n, _ in nh.STEP_COUNTERS),
                      np.asarray(out["counters"])))
    assert counts["ssm_state_rows"] == 2 * 2      # live slots x M layers
    assert counts["moe_rows_dropped"] == 0
    assert 0 <= counts["moe_assignments"] <= 2 * 3 * 4
    assert nh.prompt_counters(cfg, 100) == {"ssm_scan_tokens": 200.0}
    assert nh.cache_stats(cache) == {
        "ssm_state_bytes": 2 * 3 * 8 * 16 * 16 * 4,
        "ssm_conv_bytes": 2 * 3 * 3 * (128 + 2 * 2 * 16) * 4,
        "kv_bytes_full": 2 * 3 * 64 * 2 * 16 * 4}


# -- the latent experts and the expert-parallel share ------------------------

def _expert_weights(seed=5, d=32, lat=16, f=24, e=16, fs=40):
    rng = np.random.default_rng(seed)
    w = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]),  # noqa
                               jnp.float32)
    return {"norm": jnp.ones((d,)), "router": w(d, e),
            "router_bias": jnp.zeros((e,)), "latent_down": w(d, lat),
            "w_up": w(e, lat, f), "w_down": w(e, f, lat),
            "latent_up": w(lat, d), "shared_up": w(d, fs),
            "shared_down": w(fs, d)}


def test_the_ranks_shares_and_the_shared_expert_add_up_to_the_layer():
    """Four ranks of 4 experts each (16 in all), the router over all 16,
    top 4: the program's routed parts (in the latent, then up) plus the
    shared expert counted once are the uncut layer, the reference's with
    every expert held."""
    w = _expert_weights()
    x = jnp.asarray(np.random.default_rng(6).standard_normal((12, 32)),
                    jnp.float32)
    cfg = {**RCFG, "n_routed_experts": 16, "num_experts_per_tok": 4,
           "published": {"n_routed_experts": 16}}
    u = ref.rmsnorm(x, w["norm"], cfg["layer_norm_epsilon"])[None]
    lat = u @ w["latent_down"]
    total = jnp.square(jax.nn.relu(u @ w["shared_up"])) @ w["shared_down"]
    for rank in range(4):
        held = slice(4 * rank, 4 * rank + 4)
        part, _ = moe_share_mlp(
            u, w["router"], w["router_bias"], None, w["w_up"][held],
            w["w_down"][held], ShareArgs(16, 4, 4, 4 * rank, scale=5.0),
            jnp.float32, expert_x=lat)
        total = total + part @ w["latent_up"]
    np.testing.assert_allclose(np.asarray(total[0]),
                               np.asarray(ref.moe(cfg, x, w,
                                                  ref.knobs(cfg))),
                               atol=1e-4)


def test_latent_relu2_experts_match_a_plain_loop(monkeypatch):
    """moe_share_mlp with w_gate None and the experts fed a latent, both
    off the TPU and with megablox in the interpreter, against each held
    expert over every row, kept where the router chose it."""
    # widths of whole lane tiles: megablox tiles K and N by 128
    w = _expert_weights(lat=128, f=128)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((1, 12, 32)), jnp.float32)
    lat = x @ w["latent_down"]
    args = ShareArgs(16, 4, 4, 8, scale=5.0)
    idx, wt = moe.sigmoid_route(x[0], w["router"], w["router_bias"], args)
    want = jnp.zeros((12, 128))
    for j in range(4):
        y = jnp.square(jax.nn.relu(lat[0] @ w["w_up"][8 + j])) \
            @ w["w_down"][8 + j]
        weight = jnp.sum(jnp.where(idx == 8 + j, wt, 0.0), axis=1)
        want = want + y * weight[:, None]
    for interpret in (False, True):
        monkeypatch.setattr(moe, "FORCE_INTERPRET", interpret)
        got, counters = moe_share_mlp(
            x, w["router"], w["router_bias"], None, w["w_up"][8:12],
            w["w_down"][8:12], args, jnp.float32, expert_x=lat)
        assert got.shape == (1, 12, 128)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                   atol=1e-5)
        assert counters["rows_dropped"] == 0


# the SwiGLU callers' program, before the relu^2 and latent path came
def _swiglu_share_mlp_before(x, router_w, router_bias, w_gate, w_up,
                             w_down, args, dtype=jnp.bfloat16,
                             layer=None):
    """`moe_share_mlp` as it was before the squared-ReLU experts and
    `expert_x` came, verbatim but for this docstring and the module
    prefixes: what the SwiGLU callers must still lower to."""
    b, s, d = x.shape
    t, k, held = b * s, args.top_k, args.n_held
    before = after = 0
    if layer is not None:
        before, after = layer * held, (w_gate.shape[0] - 1 - layer) * held
        w_gate, w_up, w_down = (w.reshape((-1,) + w.shape[2:])
                                for w in (w_gate, w_up, w_down))
    tile = moe.row_tile(t * k)
    xt = x.reshape(t, d)
    with jax.named_scope("moe_route"):
        idx, w = moe.sigmoid_route(xt, router_w, router_bias, args)
        local = idx - args.first_expert
        key = jnp.where((local >= 0) & (local < held), local, held)
        total = -(-t * k // tile) * tile
        key = jnp.pad(key.reshape(t * k), (0, total - t * k),
                      constant_values=held)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(key, held + 1, dtype=jnp.int32),
                        axis=0)
        rows_here = jnp.sum(sizes[:held])
        w_flat = jnp.pad(w.reshape(t * k), (0, total - t * k))

    # the sorted rows go through the experts a slice at a time: the first
    # slice holds what balanced routing sends here eight times over; the
    # others run only when the rows reach them, under a rematerialised scan,
    # so the worst case costs no memory until it happens
    m = -(-max(total // 8, 1) // tile) * tile
    if held == args.n_router_experts:   # every row is here, every time
        m = min(total, moe.ALL_HELD_SLICE)
    n_slices = -(-total // m)
    order = jnp.pad(order, (0, n_slices * m - total),
                    constant_values=t * k)      # past every row: no expert
    starts = jnp.cumsum(sizes[:held]) - sizes[:held]

    def slice_out(lo, xt, w_flat, w_gate, w_up, w_down):
        """Rows [lo, lo + m) of the sorted order -> their part of [T, D]
        (float32) and how many of them an expert here computed."""
        sel = jax.lax.dynamic_slice_in_dim(order, lo, m)
        tok = jnp.minimum(sel // k, t - 1)
        rows = xt[tok].astype(dtype)
        here = (jnp.clip(starts + sizes[:held], lo, lo + m)
                - jnp.clip(starts, lo, lo + m))
        # the order is sorted, so a slice is experts' rows (its first one
        # possibly the tail of an expert's) and then rows no expert here
        # takes: the groups start at the slice's first row
        groups = jnp.concatenate([
            jnp.zeros((before,), here.dtype), here,
            jnp.zeros((after,), here.dtype), (m - jnp.sum(here))[None]])
        mm = functools.partial(moe._grouped_matmul, group_sizes=groups,
                               dtype=dtype, tm=tile)
        gate, up = mm(rows, w_gate), mm(rows, w_up)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(dtype)
        wt = jnp.take(w_flat, jnp.minimum(sel, total - 1))
        y = mm(act, w_down).astype(jnp.float32) * wt[:, None]
        return (jnp.zeros((t, d), jnp.float32).at[tok].add(y),
                jnp.sum(here))

    operands = (xt, w_flat, w_gate, w_up, w_down)
    with jax.named_scope("moe_experts"):
        out, done = slice_out(0, *operands)
        if n_slices > 1:
            def rest(out, done, *operands):
                def body(carry, lo):
                    o, n = jax.checkpoint(slice_out)(lo, *operands)
                    return (carry[0] + o, carry[1] + n), None
                return jax.lax.scan(body, (out, done),
                                    m * jnp.arange(1, n_slices))[0]
            out, done = jax.lax.cond(
                rows_here > m, rest, lambda out, done, *_: (out, done),
                out, done, *operands)
        out, dropped = out.astype(dtype), rows_here - done
    load = sizes[:held].astype(jnp.float32)
    first = jnp.sum(jax.nn.one_hot(idx[:, 0], args.n_router_experts,
                                   dtype=jnp.int32), axis=0)
    counters = {
        "rows_here": rows_here.astype(jnp.float32),
        "rows_dropped": dropped.astype(jnp.float32),
        "load_max_over_mean": jnp.max(load) / jnp.maximum(jnp.mean(load),
                                                          1e-9),
        "top1_share_max": jnp.max(first).astype(jnp.float32) / t,
        "experts_touched": jnp.sum(sizes[:held] > 0).astype(jnp.float32),
    }
    return out.reshape(b, s, d), counters


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "megablox"])
@pytest.mark.parametrize("layer,held", [(None, 4), (1, 4), (None, 8)],
                         ids=["share", "stacked", "all-held"])
def test_swiglu_experts_are_what_they_were(monkeypatch, interpret, layer,
                                           held):
    """The SwiGLU path (Laguna, Pangu, Kimi) lowers to the StableHLO the
    body before the relu^2 and latent path lowered to, kept above, on the
    XLA path and through megablox; and taking the router's rows as the
    experts' rows, `expert_x=x`, changes no bit."""
    monkeypatch.setattr(moe, "FORCE_INTERPRET", interpret)
    # 320 assignments in tiles of 128: the slices past the first run too
    monkeypatch.setattr(moe, "ROW_TILE", 128)
    rng = np.random.default_rng(8)
    w = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)  # noqa
    stack = () if layer is None else (3,)
    ops = (w(1, 160, 128), w(128, 8).astype(jnp.float32), jnp.zeros((8,)),
           w(*stack, held, 128, 128), w(*stack, held, 128, 128),
           w(*stack, held, 128, 128))
    args = ShareArgs(8, 2, held, 0, scale=2.5)

    def lowered(fn):
        return jax.jit(lambda *a: fn(*a, args, layer=layer)).lower(
            *ops).as_text()
    assert lowered(moe_share_mlp) == lowered(_swiglu_share_mlp_before)
    a, ca = moe_share_mlp(*ops, args, layer=layer)
    b, cb = moe_share_mlp(*ops, args, layer=layer, expert_x=ops[0])
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
    assert float(ca["rows_here"]) == float(cb["rows_here"])


# -- the planted faults -------------------------------------------------------

@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_each_reference_fault_moves_the_logits(fault):
    toks = _tokens(40, batch=1)
    sound = _ref_logits(toks)
    bad = _ref_logits(toks, fault=fault)
    assert np.abs(sound - bad).max() > 1e-2
    low = _ref_logits(toks, lower="fp8")
    assert 0 < np.abs(sound - low).max() < np.abs(sound - bad).max() * 10


def test_the_bf16_state_control_moves_the_logits_a_little():
    toks = _tokens(40, batch=1)
    gap = np.abs(_ref_logits(toks) - _ref_logits(toks, lower="bf16_state"))
    assert 0 < gap.max() < 0.1


@pytest.mark.parametrize("fault", ["pad_advances_state", "state_not_reset"])
def test_each_program_fault_moves_the_decoded_logits(params, fault,
                                                     monkeypatch):
    """The benchmark driver's plants, on the family module: a prompt of 9
    padded to 16 after another request used the slot; the prompt's own
    logits stay, the decoded ones move."""
    from drivers import http_open_loop_state as drv

    cfg = _cfg()
    first, second = _tokens(30, seed=1), _tokens(30, seed=2)

    def run():
        _, cache = _serve(params, cfg, first, 17, 32, (), 6, slots=[1, 2])
        return _serve(params, cfg, second, 9, 16, (), 6, cache=cache,
                      slots=[1, 2])[0]
    sound = run()
    for name in ("ssd_scan", "cache_write"):
        monkeypatch.setattr(nh, name, getattr(nh, name))
    drv.family.PROGRAM_FAULTS[fault](nh)
    bad = run()
    np.testing.assert_allclose(bad[:, 0], sound[:, 0], atol=1e-5)
    assert np.abs(bad[:, 2:] - sound[:, 2:]).max() > 1e-2


# -- the engine and the InferenceService -------------------------------------

@pytest.fixture(scope="module")
def engine_run(params):
    eng = LLMEngine(params, _cfg(), n_slots=4, max_len=64, buckets=(8, 16),
                    decode_chunk=4, family=nh)
    prompts = [list(map(int, np.random.default_rng(i).integers(0, 128, n)))
               for i, n in enumerate((5, 12, 16, 37, 29, 7))]
    rids = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run_until_idle()
    eng._obs_publish()          # what a /metrics scrape runs first
    out = ([(p, eng.result(r)) for p, r in zip(prompts, rids)],
           eng.metrics(), render_metrics())
    eng.close()
    return out


def test_engine_greedy_tokens_are_the_references(engine_run):
    """Six prompts of 5-37 tokens over 4 slots (a chain of three for 37,
    two slots reused) through the engine's cache, continuous batching and
    chained prefill."""
    runs, _, _ = engine_run
    assert [len(s) for _, s in runs] == [10] * 6
    t = max(len(p) + len(s) for p, s in runs)
    toks = jnp.asarray([(p + s + [0] * t)[:t] for p, s in runs], jnp.int32)
    r = _ref_logits(toks)
    for i, (p, s) in enumerate(runs):
        pos = np.arange(len(p) - 1, len(p) + len(s) - 1)
        gap = r[i, pos].max(-1) - r[i, pos, np.asarray(s)]
        assert gap.max() <= 1e-4, (i, gap)


def test_engine_metrics_carry_the_states_and_the_counters(engine_run):
    runs, m, text = engine_run
    assert m["ssm_state_bytes"] == 2 * 4 * 8 * 16 * 16 * 4
    assert m["ssm_conv_bytes"] == 2 * 4 * 3 * 192 * 4
    assert m["kv_bytes_full"] == 2 * 4 * 64 * 2 * 16 * 4
    assert m["ssm_scan_tokens"] == 2 * sum(len(p) for p, _ in runs)
    assert m["ssm_state_rows"] > 0 and m["moe_rows_dropped"] == 0
    assert m["moe_assignments"] > 0
    for name in ("ssm_state_bytes", "ssm_scan_tokens", "ssm_state_rows"):
        assert f'name="{name}"' in text


@pytest.mark.parametrize("option,value", [
    ("speculative", 2), ("prefix_cache", True), ("kv_layout", "paged"),
    ("parallel", {"tensor": 2}), ("adapters", {"a": {"checkpoint": "/x"}}),
    ("mesh", {"tensor": 2}), ("lora", {"rank": 4}), ("quantize", "int8"),
    ("disaggregated", True)])
def test_load_refuses_by_name_what_the_family_does_not_serve(option, value):
    from kubeflow_tpu.serving.llm_runtime import LLMModel

    with pytest.raises(ValueError, match=f"does not serve `{option}`"):
        LLMModel("m", family="nemotron_h", **{option: value})
    LLMModel("m", family="nemotron_h", kv_layout="slab")


def test_what_the_seam_does_not_serve_raises(params):
    cfg = _cfg()
    with pytest.raises(NotImplementedError, match="snapshots"):
        nh.verify_step(params, None, None, None, cfg)
    with pytest.raises(NotImplementedError, match="adapters"):
        nh.prefill(params, _tokens(8), cfg, lora={})
    with pytest.raises(ValueError, match="model dtype"):
        nh.init_cache(cfg, 2, 16, kv_quantize="int8")
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        _cfg(hybrid_override_pattern="ME*E")


def test_registry_and_serving_runtime_know_the_family():
    from kubeflow_tpu.models import registry
    from kubeflow_tpu.serving.llm_runtime import FAMILIES

    assert registry.get("nemotron_h").config_cls is nh.NemotronHConfig
    assert FAMILIES["nemotron_h"].module == nh.__name__

"""ops/moe.py::moe_share_mlp: the routing that drops nothing and knows its
share, against a loop over the experts held with a mask."""

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.ops import moe

B, S, D, F, E, HELD, K = 2, 64, 128, 256, 16, 4, 4


def reference(x, rw, rb, wg, wu, wd, a):
    """sigmoid scores, top-k of score + bias, weights renormalised and
    scaled; the experts [first, first + held) one after the other."""
    xt = x.reshape(-1, x.shape[-1])
    mm = lambda p, q: jnp.matmul(p, q, precision="highest")
    sc = jax.nn.sigmoid(mm(xt, rw))
    _, idx = jax.lax.top_k(sc + rb, a.top_k)
    w = jnp.take_along_axis(sc, idx, 1)
    w = w / w.sum(1, keepdims=True) * a.scale
    out = jnp.zeros_like(xt)
    for e in range(a.n_held):
        mine = jnp.sum(jnp.where(idx == a.first_expert + e, w, 0), 1)
        h = jax.nn.silu(mm(xt, wg[e])) * mm(xt, wu[e])
        out = out + mine[:, None] * mm(h, wd[e])
    return out.reshape(x.shape)


def weights(n_experts=HELD):
    ks = jax.random.split(jax.random.key(0), 5)
    dense = lambda k, shape, fan: jax.random.normal(k, shape) / fan ** 0.5
    return (jax.random.normal(ks[0], (B, S, D)), dense(ks[1], (D, E), D),
            jnp.zeros((E,)), dense(ks[2], (n_experts, D, F), D),
            dense(ks[3], (n_experts, D, F), D),
            dense(ks[4], (n_experts, F, D), F))


@pytest.fixture(autouse=True)
def small_row_tile(monkeypatch):
    # 512 assignments in tiles of 128: four slices, so the scan past the
    # first slice runs
    monkeypatch.setattr(moe, "ROW_TILE", 128)


@pytest.fixture(params=[False, True], ids=["ragged_dot", "megablox"])
def grouped(request, monkeypatch):
    monkeypatch.setattr(moe, "FORCE_INTERPRET", request.param)


# float32 throughout: the orders of the sums differ, nothing else: 1e-5 of
# the largest value
def close(a, b):
    return float(jnp.max(jnp.abs(a - b))) < 1e-5 * float(jnp.max(jnp.abs(b)))


def test_a_share_matches_the_masked_loop_forward_and_backward(grouped):
    x, rw, rb, wg, wu, wd = weights()
    a = moe.ShareArgs(E, K, HELD, 4, 2.446, True)
    f = lambda *p: moe.moe_share_mlp(*p, a, dtype=jnp.float32)
    out, counters = jax.jit(f)(x, rw, rb, wg, wu, wd)
    assert close(out, reference(x, rw, rb, wg, wu, wd, a))
    assert float(counters["rows_dropped"]) == 0
    assert 0 < float(counters["rows_here"]) < B * S * K
    wt = jax.random.normal(jax.random.key(5), out.shape)
    got = jax.grad(lambda *p: jnp.sum(f(*p)[0] * wt),
                   argnums=(0, 1, 3, 4, 5))(x, rw, rb, wg, wu, wd)
    want = jax.grad(lambda *p: jnp.sum(reference(*p, a) * wt),
                    argnums=(0, 1, 3, 4, 5))(x, rw, rb, wg, wu, wd)
    for g, r in zip(got, want):
        assert close(g, r)


def test_no_row_is_dropped_when_every_choice_lands_here(grouped):
    """The imbalance test: a bias puts every token's top-k on the experts
    held, eight times what the first slice of rows is sized for; the rest
    of the slices run and the output is still the reference's."""
    x, rw, rb, wg, wu, wd = weights()
    rb = rb.at[:HELD].set(10.0)
    a = moe.ShareArgs(E, K, HELD, 0, 2.446, True)
    out, counters = jax.jit(lambda *p: moe.moe_share_mlp(
        *p, a, dtype=jnp.float32))(x, rw, rb, wg, wu, wd)
    assert float(counters["rows_here"]) == B * S * K
    assert float(counters["rows_dropped"]) == 0
    assert float(counters["top1_share_max"]) <= 1.0
    assert close(out, reference(x, rw, rb, wg, wu, wd, a))


def test_the_shares_add_up_to_the_whole_layer():
    """The guide's share test: the routed parts that all the ranks give add
    up to what one rank holding every expert gives."""
    x, rw, rb, wg, wu, wd = weights(E)
    whole = moe.ShareArgs(E, K, E, 0, 2.446, True)
    full, _ = moe.moe_share_mlp(x, rw, rb, wg, wu, wd, whole,
                                dtype=jnp.float32)
    parts = 0
    for first in range(0, E, HELD):
        a = moe.ShareArgs(E, K, HELD, first, 2.446, True)
        sl = slice(first, first + HELD)
        parts += moe.moe_share_mlp(x, rw, rb, wg[sl], wu[sl], wd[sl], a,
                                   dtype=jnp.float32)[0]
    assert close(parts, full)
    assert close(full, reference(x, rw, rb, wg, wu, wd, whole))


def test_the_bias_chooses_and_does_not_weigh():
    x, rw, rb, wg, wu, wd = weights()
    a = moe.ShareArgs(E, K, HELD, 0, 1.0, True)
    idx, w = moe.sigmoid_route(x.reshape(-1, D), rw, rb.at[3].set(5.0), a)
    assert bool(jnp.all(jnp.any(idx == 3, axis=1)))      # always chosen
    assert float(jnp.max(jnp.abs(jnp.sum(w, axis=1) - 1.0))) < 1e-6
    assert float(jnp.max(w)) < 1.0                       # 5.0 weighs nothing

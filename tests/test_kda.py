"""ops/kda.py: the chunked gated delta rule against the recurrence followed
one position at a time, forward and backward, at decays strong enough that
`exp(-cumsum(g))` over a chunk overflows float32 (the reason for the
sub-blocks with a local reference point)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops import kda


def recurrence(q, k, v, g, beta):
    """S_t = (I - b k k^T) Diag(exp g) S_{t-1} + b k v^T; o_t = S_t^T q_t,
    float32 at the highest precision. [B, S, H, d] in and out."""
    b, s, h, dk = q.shape

    def step(state, x):
        q, k, v, g, bt = x
        state = state * jnp.exp(g)[..., None]
        u = v - jnp.einsum("bhd,bhde->bhe", k, state, precision="highest")
        state = state + bt[..., None, None] * k[..., None] * u[..., None, :]
        return state, jnp.einsum("bhd,bhde->bhe", q, state,
                                 precision="highest")

    xs = jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def inputs(seed, b=1, s=192, h=2, dk=32, dv=32, strength=4.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -strength * jax.nn.softplus(jax.random.normal(ks[3], (b, s, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta


@pytest.fixture(params=["xla", "pallas"])
def path(request, monkeypatch):
    """The CPU's jax.numpy forward, or the kernels under the interpreter."""
    monkeypatch.setattr(kda, "FORCE_INTERPRET", request.param == "pallas")
    return request.param


@pytest.fixture(params=["xla", pytest.param("pallas", marks=pytest.mark.slow)])
def path_slow_kernels(request, monkeypatch):
    monkeypatch.setattr(kda, "FORCE_INTERPRET", request.param == "pallas")
    return request.param


def test_the_decays_would_overflow_a_naive_cumsum():
    g = inputs(0)[3]
    worst = float(jnp.min(jnp.cumsum(g[:, :kda.CHUNK], axis=1)))
    assert worst < -100.0            # exp(100) is past float32's 3.4e38
    assert not np.isfinite(np.exp(np.float32(-worst)))


# float32 operands: what separates the chunked form from the recurrence is
# the order of the sums alone, so 2e-5 of the largest value (measured 2e-6)
def test_forward_matches_the_recurrence(path):
    args = inputs(0)
    ref = recurrence(*args)
    out = kda.chunk_kda(*args, mm_dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5 * float(
        jnp.max(jnp.abs(ref)))


def test_backward_matches_the_recurrence(path_slow_kernels, monkeypatch):
    monkeypatch.setattr(kda, "BACKWARD_GROUP", 2)   # 3 chunks: groups of 1
    args = inputs(1)
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    f = lambda *a: jnp.sum(kda.chunk_kda(*a, mm_dtype=jnp.float32) * w)
    got = jax.grad(f, argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * w),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-5 * float(
            jnp.max(jnp.abs(b))), name


def test_a_length_off_the_chunk_grid_is_padded_with_inert_positions():
    args = inputs(2, s=100)
    out = kda.chunk_kda(*args, mm_dtype=jnp.float32)
    ref = recurrence(*args)
    assert out.shape == ref.shape
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5 * float(
        jnp.max(jnp.abs(ref)))


def test_bfloat16_operands_stay_within_their_rounding():
    # operands rounded to 8 bits before each matmul, sums in float32: 2 %
    # of the largest output (measured 0.6 %)
    q, k, v, g, beta = inputs(3, strength=0.5)
    ref = recurrence(q, k, v, g, beta)
    out = kda.chunk_kda(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                        v.astype(jnp.bfloat16), g, beta)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < 2e-2 * float(
        jnp.max(jnp.abs(ref)))


def test_the_kernels_run_off_the_tpu_only_when_interpreted(monkeypatch):
    assert kda._kernels() == (False, False)
    monkeypatch.setattr(kda, "FORCE_INTERPRET", True)
    assert kda._kernels() == (True, True)


def test_the_solve_and_the_backward_carry_their_scopes():
    """The benchmark splits the device's time by these names
    (benchmark/lib/xscopes.py reads them from a capture's operations)."""
    import re

    args = inputs(4, s=128)
    f = lambda *a: jnp.sum(kda.chunk_kda(*a, mm_dtype=jnp.float32))
    text = jax.jit(jax.grad(f, argnums=(0, 3))).lower(*args).as_text(
        debug_info=True)
    names = set(re.findall(r'"([^"]*kda_[^"]*)"', text))
    # the forward's solve, the backward's reverse walk over the chunks, and
    # the solve differentiated inside the backward's groups of chunks
    assert any(n.startswith("jit(<lambda>)/jvp(kda_solve)/") for n in names)
    assert any("jvp(kda_backward))/while/body/" in n for n in names)
    assert any(n.startswith("transpose(jvp(kda_solve))/") for n in names)

"""ops/kda.py: the chunked gated delta rule against the recurrence followed
one position at a time, forward and backward, at decays strong enough that
`exp(-cumsum(g))` over a chunk overflows float32 (the reason for the
sub-blocks with a local reference point)."""

import re

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


# jitted: the interpreter walks the backward kernels' grids in seconds then;
# 160 positions are padded to three chunks with inert positions
@pytest.mark.parametrize("s", [192, 160])
def test_backward_matches_the_recurrence(path, monkeypatch, s):
    monkeypatch.setattr(kda, "BACKWARD_GROUP", 2)   # 3 chunks: groups of 1
    args = inputs(1, s=s)
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    f = lambda *a: jnp.sum(kda.chunk_kda(*a, mm_dtype=jnp.float32) * w)
    got = jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4)))(*args)
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * w),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-5 * float(
            jnp.max(jnp.abs(b))), name


def test_each_traced_backward_records_its_path(path):
    """kda.TRACED_BACKWARD, which the Trainer's first record reads as
    `kda_backward_kernel_share`: one entry a traced backward."""
    seen = len(kda.TRACED_BACKWARD)
    args = inputs(4, s=128)
    f = lambda *a: jnp.sum(kda.chunk_kda(*a, mm_dtype=jnp.float32))
    jax.make_jaxpr(jax.grad(f, argnums=(0, 3)))(*args)
    assert kda.TRACED_BACKWARD[seen:] == [
        "kernel" if path == "pallas" else "xla"]


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
def test_each_traced_forward_records_its_solves_path(path, grad):
    """kda.TRACED_SOLVE, which the Trainer's first record reads as
    `kda_solve_kernel_share`: one entry a traced forward, differentiated
    or not."""
    seen = len(kda.TRACED_SOLVE)
    args = inputs(4, s=128)
    f = lambda *a: jnp.sum(kda.chunk_kda(*a, mm_dtype=jnp.float32))
    jax.make_jaxpr(jax.grad(f, argnums=(0, 3)) if grad else f)(*args)
    assert kda.TRACED_SOLVE[seen:] == ["kernel" if path == "pallas" else "xla"]


def path_output(what, args, interpret, monkeypatch):
    """chunk_kda's output or its five cotangents (float32 operands, jitted)
    on the CPU path or the kernels' path under the interpreter."""
    monkeypatch.setattr(kda, "FORCE_INTERPRET", interpret)
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    f = lambda *a: kda.chunk_kda(*a, mm_dtype=jnp.float32)
    if what == "grad":
        f = jax.grad(lambda *a: jnp.sum(
            kda.chunk_kda(*a, mm_dtype=jnp.float32) * w),
            argnums=(0, 1, 2, 3, 4))
    return jax.tree.leaves(jax.jit(f)(*args))


# the recurrence tests' tolerances: 160 positions, padded to three chunks
@pytest.mark.parametrize("what,tol", [("forward", 2e-5), ("grad", 5e-5)])
def test_the_kernels_path_matches_the_cpu_path(monkeypatch, what, tol):
    args = inputs(6, s=160)
    want = path_output(what, args, False, monkeypatch)
    got = path_output(what, args, True, monkeypatch)
    assert len(got) == len(want) == (5 if what == "grad" else 1)
    for x, y in zip(got, want):
        assert x.shape == y.shape
        assert float(jnp.max(jnp.abs(x - y))) <= tol * float(
            jnp.max(jnp.abs(y)))


def solve_inputs(nc, strength, dk, beta_min):
    """A as the forward's chunk sums make it from unit keys of dk channels
    under a decay of the given strength, and beta uniform in [beta_min, 1)
    but for one position of the last chunk, 0 as a padded one is: [1, nc,
    C, C] and [1, nc, C]."""
    c = kda.CHUNK
    kk, kg, kb = jax.random.split(jax.random.key(12), 3)
    k = jax.random.normal(kk, (nc, c, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -strength * jax.nn.softplus(jax.random.normal(kg, (nc, c, dk)))
    a = kda._intra_xla(k, k, jnp.cumsum(g, axis=1), jnp.float32)[0]
    beta = jax.random.uniform(kb, (nc, c), minval=beta_min, maxval=1.0)
    return a[None], beta.at[nc - 1, 40].set(0.0)[None]


# the substitution and one step by halves against the six steps by halves:
# the same inverse in float32, to 1e-5 of the largest entry (measured 5e-10
# to 2.4e-7 over these and stronger cases)
@pytest.mark.parametrize("nc,strength,dk,beta_min", [
    (3, 4.0, 32, 0.0),
    (1, 0.0, 2, 0.999),
    (20, 0.01, 4, 0.99)],
    ids=["strong-decay", "one-chunk-no-decay", "a-grid-step-past-the-end"])
def test_the_solve_kernel_matches_the_six_steps_it_replaces(
        nc, strength, dk, beta_min):
    """`_ut_pallas` (interpreted) against `_ut_transform`: a decay that
    leaves A near 0 across its sub-blocks; one chunk of nearly parallel keys
    with beta near 1, where X's entries reach 1; 20 chunks, two grid steps
    of UT_CHUNKS, the second past the last chunk."""
    assert nc < kda.UT_CHUNKS or nc % kda.UT_CHUNKS
    a, beta = solve_inputs(nc, strength, dk, beta_min)
    want = kda._ut_transform(a, beta)
    got = kda._ut_pallas(a, beta, interpret=True)
    for name, x, y in zip("MX", got, want):
        assert x.shape == y.shape and x.dtype == y.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(x - y))) <= 1e-5 * float(
            jnp.max(jnp.abs(y))), name
    # beta = 0 zeroes M's column, not X's
    assert not np.any(np.asarray(got[0][0, -1, :, 40]))
    assert float(jnp.max(jnp.abs(got[1][0, -1, 41:, 40]))) > 1e-3


def heads_first(x):
    b, s, h = x.shape[:3]
    return jnp.moveaxis(x, 2, 1).reshape(b * h, s, *x.shape[3:])


def stage3(seed, mm_dtype, strength, s=192, pad=0):
    """Heads-first inputs (the last `pad` positions inert, as chunk_kda pads
    them), `_prepare`'s results per chunk, the state at every chunk's start
    and stage 3's six cotangents from the reverse scan for an output
    cotangent that is 0 where the output is cut off."""
    q, k, v, g, beta = (heads_first(x) for x in inputs(seed, s=s,
                                                       strength=strength))
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    bh, n, _ = q.shape
    nc = n // kda.CHUNK
    per_head = lambda x: x.reshape(bh, nc, *x.shape[1:])
    chunked = [kda._chunks(x) for x in (q, k, v, g, beta)]
    ops = jax.tree.map(per_head, kda._prepare(*chunked, mm_dtype))
    st = kda._states_xla(ops, mm_dtype)[1]               # S [BH, NC, dk, dv]
    do = jax.random.normal(jax.random.key(7), v.shape)
    do = do.at[:, n - pad:].set(0.0) if pad else do
    d_ops = kda._state_bwd_xla(ops, st, per_head(kda._chunks(do)), mm_dtype)
    return (q, k, v, g, beta), chunked, st, do, d_ops


# the kernel rounds every operand where the scan does and sums in float32
# as it does: float32 to 1e-5 of the largest value, bfloat16 to one rounding
# of an operand (2^-8) over sums of 64 to 128 products of either sign
TOLERANCES = pytest.mark.parametrize("mm_dtype,strength,tol", [
    (jnp.float32, 4.0, 1e-5), (jnp.bfloat16, 0.5, 4e-3)],
    ids=["float32", "bfloat16"])


@TOLERANCES
@pytest.mark.parametrize("chunks", [1, 3])
def test_the_backward_kernel_matches_the_scan_it_replaces(
        mm_dtype, strength, tol, chunks):
    """Stage 3's six cotangents from `_state_bwd_pallas` (interpreted, fed
    the operands of the forward's kernel) against `_state_bwd_xla` fed
    `_prepare`'s results for the same chunks: 2 heads, dk = dv = 32, the
    state's cotangent carried across the chunks, heads first."""
    (q, k, v, g, beta), chunked, st, do, want = stage3(
        5, mm_dtype, strength, s=chunks * kda.CHUNK)
    bh, nc = st.shape[:2]
    gc = kda._cumulative(g)
    a, b = kda._intra_xla(chunked[0], chunked[1], kda._chunks(gc), mm_dtype)
    m = kda._ut_transform(a, chunked[4])[0]
    per_head = lambda x: x.reshape(bh, nc, *x.shape[1:])
    got = kda._state_bwd_pallas(
        q, k, v, gc, per_head(m), per_head(b), jnp.swapaxes(st, -1, -2), do,
        interpret=True, mm_dtype=mm_dtype)
    # dKd = U dS^T: nothing reaches the last chunk's state, the earlier
    # chunks' states take a cotangent from the chunks after them
    assert not np.any(np.asarray(want[4][:, -1]))
    if chunks > 1:
        assert float(jnp.max(jnp.abs(want[4][:, 0]))) > 1e-3
    for name, x, y in zip("Qg W Uv B Kd gamma".split(), got, want):
        assert x.shape == y.shape, name
        assert float(jnp.max(jnp.abs(x - y))) <= tol * float(
            jnp.max(jnp.abs(y))), name


def stages12(mm_dtype, strength, pad=0):
    """The cotangents of q, k, v, g and beta from `_prepare_bwd_pallas`
    (interpreted, fed X and stage 3's cotangents) and from `jax.vjp` of
    `_prepare` for the same chunks: 3 chunks, 2 heads, dk = dv = 32."""
    args, chunked, st, do, d_ops = stage3(5, mm_dtype, strength,
                                          s=192 - pad, pad=pad)
    q, k, v, g, beta = args
    bh, nc = st.shape[:2]
    a = kda._intra_xla(chunked[0], chunked[1],
                       kda._chunks(kda._cumulative(g)), mm_dtype)[0]
    x = kda._ut_transform(a, chunked[4])[1].reshape(bh, nc, *a.shape[1:])
    got = jax.jit(lambda *a: kda._prepare_bwd_pallas(
        *a[:6], a[6:], interpret=True, mm_dtype=mm_dtype))(
        q, k, v, kda._cumulative(g), beta, x, *d_ops)
    cts = jax.tree.map(lambda t: t.reshape(bh * nc, *t.shape[2:]), d_ops)
    want = jax.vjp(lambda *a: kda._prepare(*a, mm_dtype), *chunked)[1](cts)
    return got, [w.reshape(x.shape) for w, x in zip(want, args)]


@TOLERANCES
def test_the_stages_1_2_kernel_matches_the_autodiff_it_replaces(
        mm_dtype, strength, tol):
    got, want = stages12(mm_dtype, strength)
    for name, x, y in zip("q k v g beta".split(), got, want):
        assert x.shape == y.shape and x.dtype == y.dtype, name
        assert float(jnp.max(jnp.abs(x - y))) <= tol * float(
            jnp.max(jnp.abs(y))), name


def test_a_padded_tail_takes_finite_cotangents_and_zero_ones_where_inert():
    """The last 32 positions as chunk_kda pads them (beta = 0, k = 0, the
    output cut off): every cotangent finite, those of the padded positions
    0 (nothing depends on them), the rest as JAX's."""
    got, want = stages12(jnp.float32, 4.0, pad=32)
    for name, x, y in zip("q k v g beta".split(), got, want):
        assert np.all(np.isfinite(np.asarray(x))), name
        assert not np.any(np.asarray(x[:, -32:])), name
        assert float(jnp.max(jnp.abs(x - y))) <= 1e-5 * float(
            jnp.max(jnp.abs(y))), name


def test_the_closed_form_matches_the_transposed_solve():
    """dL = -X^T dX X^T (`_ut_cotangents`), turned into the cotangents of A
    and beta, against JAX's transpose of the six-step `_ut_transform`, in
    float32, with one column of beta 0 (a padded position)."""
    c = kda.CHUNK
    ka, kb, km = jax.random.split(jax.random.key(11), 3)
    a = jnp.tril(jax.random.normal(ka, (c, c)) * 0.3, -1)
    beta = jax.nn.sigmoid(jax.random.normal(kb, (c,))).at[40].set(0.0)
    dm = jax.random.normal(km, (c, c))
    m, vjp = jax.vjp(lambda a, b: kda._ut_transform(a, b)[0], a, beta)
    want_a, want_beta = vjp(dm)
    x = kda._ut_transform(a, beta)[1]
    dl, dbeta_c = kda._ut_cotangents(x, beta[None, :], dm)
    got_a = beta[:, None] * dl
    got_beta = dbeta_c[0] + jnp.sum(dl * a, axis=1)
    assert float(jnp.max(jnp.abs(jnp.triu(dl)))) == 0.0
    # beta = 0 still takes a cotangent through M's column (X is not M / beta)
    assert abs(float(want_beta[40])) > 1e-2
    for name, x, y in (("A", got_a, want_a), ("beta", got_beta, want_beta)):
        assert float(jnp.max(jnp.abs(x - y))) <= 1e-5 * float(
            jnp.max(jnp.abs(y))), name


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


def test_the_backward_kernels_carry_the_backwards_scope(monkeypatch):
    """On the kernels' path (lowered for a TPU target from here) the
    backward is TWO Mosaic calls under `kda_backward`, the chunk walk and
    the chunk sums and UT transform differentiated by hand, so the share
    the benchmark reads by that scope holds the whole backward: no loop is
    left under the scope, and no solve is re-run or transposed. The
    forward's solve is ONE Mosaic call of its own under `kda_solve`, so the
    share read by that scope holds the whole solve."""
    from kubeflow_tpu.ops import pallas_compat

    monkeypatch.setattr(pallas_compat, "target_platform", lambda: "tpu")
    assert kda._kernels() == (True, False)
    args = inputs(4, s=128)
    f = lambda *a: jnp.sum(kda.chunk_kda(*a, mm_dtype=jnp.float32))
    grad = jax.grad(f, argnums=(0, 3))

    def under_the_scope(jaxpr, scope, found):
        """(primitive, operands, reverse) of every loop and kernel whose
        name stack holds the scope, loops' bodies not entered."""
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if scope in str(eqn.source_info.name_stack) and name in (
                    "scan", "while", "pallas_call"):
                found.append((name, len(eqn.invars),
                              eqn.params.get("reverse")))
            elif name not in ("scan", "while"):
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    under_the_scope(sub, scope, found)
        return found

    jaxpr = jax.make_jaxpr(grad)(*args).jaxpr
    # q, k, v, gc, M, B, h, do into the walk; q, k, v, gc, beta, X and the
    # walk's six cotangents into the second kernel; A and beta into the solve
    assert sorted(under_the_scope(jaxpr, "kda_backward", [])) == [
        ("pallas_call", 8, None), ("pallas_call", 12, None)]
    assert under_the_scope(jaxpr, "kda_solve", []) == [
        ("pallas_call", 2, None)]
    text = jax.jit(grad).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = set(re.findall(r'"([^"]*kda_[^"]*)"', text))
    assert "jit(<lambda>)/transpose(jvp(kda_backward))/pallas_call" in names
    assert "jit(<lambda>)/jvp(kda_solve)/pallas_call" in names
    assert not any("kda_solve" in n and "kda_backward" in n for n in names)
    assert not any(n.startswith("transpose(jvp(kda_solve))/") for n in names)
    # intra, solve and walk forward, the two backward kernels
    assert text.count("tpu_custom_call") == 5

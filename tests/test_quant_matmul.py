"""Numerics for the experimental fused int8-dequant Pallas kernel
(ops/quant_matmul.py), exercised via the interpreter on the CPU mesh —
the same FORCE_INTERPRET pattern as the flash kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import llama
from kubeflow_tpu.ops import quant, quant_matmul


@pytest.fixture(autouse=True)
def _interpret():
    quant_matmul.FORCE_INTERPRET = True
    yield
    quant_matmul.FORCE_INTERPRET = False


@pytest.mark.parametrize("m,d,o", [
    (4, 512, 384),     # decode batch, lm-head-style 384-block o
    (1, 256, 128),     # single slot, smallest blocks
    (56, 1024, 512),   # spec-verify flattened rows
])
def test_kernel_matches_xla_dequant_path(m, d, o):
    rng = np.random.default_rng(0)
    w = rng.normal(size=(d, o)).astype(np.float32) / d ** 0.5
    wt = quant.quantize_int8(jnp.asarray(w))
    x = jnp.asarray(rng.normal(size=(m, d)), jnp.bfloat16)
    ref = ((x @ wt["q"].astype(jnp.bfloat16)).astype(jnp.float32)
           * wt["s"]).astype(jnp.bfloat16)
    got = quant_matmul.dequant_matmul(x, wt["q"], wt["s"], jnp.bfloat16)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) or 1.0
    assert err / scale < 0.02, (m, d, o, err, scale)


def test_quant_matmul_routes_through_kernel_under_force_interpret():
    """quant.matmul's gate sends decode-shaped quantized matmuls through
    the kernel when FORCE_INTERPRET is on (the CI stand-in for the TPU
    opt-in), including the leading-batch reshape and f32 lm-head path."""
    rng = np.random.default_rng(1)
    w = rng.normal(size=(256, 384)).astype(np.float32) / 16.0
    wt = quant.quantize_int8(jnp.asarray(w))
    x = jnp.asarray(rng.normal(size=(2, 3, 256)), jnp.bfloat16)
    ref = ((x @ wt["q"].astype(jnp.bfloat16)).astype(jnp.float32)
           * wt["s"]).astype(jnp.bfloat16)
    got = quant.matmul(x, wt, jnp.bfloat16)
    assert got.shape == ref.shape
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < 0.05
    ref32 = jnp.einsum("...d,dv->...v", x, wt["q"].astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32) * wt["s"]
    got32 = quant.matmul_f32_out(x, wt, jnp.bfloat16)
    assert got32.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got32 - ref32))) < 0.05


def test_kernel_gate_declines_unsupported_shapes():
    assert not quant_matmul.kernel_applicable(256, 4096, 14336)  # big m
    assert not quant_matmul.kernel_applicable(4, 100, 384)       # ragged d
    assert not quant_matmul.kernel_applicable(4, 512, 100)       # ragged o
    assert quant_matmul.kernel_applicable(4, 4096, 128256)       # lm head


# -- the stacked entry: the kernel reads its layer in place ------------------


def _stack(n_layers, d, o, seed=2):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(n_layers, d, o)).astype(np.float32) / d ** 0.5
    return rng, quant.quantize_int8(jnp.asarray(w))


@pytest.mark.parametrize("out_dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("m", [1, 16, 128])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_stacked_kernel_equals_2d_kernel_bit_for_bit(layer, m, out_dtype):
    rng, wt = _stack(3, 512, 384)
    assert wt["q"].shape == (3, 512, 384) and wt["s"].shape == (3, 384)
    x = jnp.asarray(rng.normal(size=(m, 512)), jnp.bfloat16)
    kw = dict(out_dtype=jnp.dtype(out_dtype), interpret=True)
    got = quant_matmul._dequant_matmul_stacked(
        jnp.asarray([layer], jnp.int32), x, wt["q"], wt["s"], **kw)
    ref = quant_matmul._dequant_matmul_2d(
        x, wt["q"][layer], wt["s"][layer], **kw)
    assert got.dtype == ref.dtype == jnp.dtype(out_dtype)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(ref, np.float32))


@pytest.mark.parametrize("fn", [quant.matmul, quant.matmul_f32_out],
                         ids=["matmul", "matmul_f32_out"])
@pytest.mark.parametrize("m,o", [(256, 384), (4, 100)],
                         ids=["rows_over_the_gate", "ragged_o"])
def test_stacked_leaf_outside_the_gate_is_the_per_layer_xla_expression(
        m, o, fn):
    rng, wt = _stack(3, 256, o)
    assert not quant_matmul.kernel_applicable(m, 256, o)
    x = jnp.asarray(rng.normal(size=(m, 256)), jnp.bfloat16)
    for layer in range(3):
        leaf = {"q": wt["q"][layer], "s": wt["s"][layer]}
        with quant.count_sites() as sites:
            got = fn(x, wt, jnp.bfloat16, jnp.int32(layer))
        assert dict(sites) == {"xla": 1}
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(fn(x, leaf, jnp.bfloat16), np.float32))
    with pytest.raises(ValueError, match="layer index"):
        fn(x, wt, jnp.bfloat16)


# -- the serving bodies hand the kernel the stacks ---------------------------

CFG = llama.LlamaConfig(vocab_size=512, d_model=256, n_layers=3, n_heads=2,
                        n_kv_heads=1, d_ff=256, max_seq_len=64,
                        remat=False, decode_attention_impl="xla",
                        prefill_attention_impl="xla")
SLOTS, MAX_LEN, BLOCK = 4, 32, 8


@pytest.fixture(scope="module")
def served():
    params = llama.quantize_params(llama.init(jax.random.key(0), CFG))
    rng = np.random.default_rng(3)
    lora = {}
    for t in ("wq", "wv", "w_down"):
        d_in, d_out = params["layers"][t]["q"].shape[1:]
        lora[t] = {
            "a": jnp.asarray(rng.normal(size=(3, 3, d_in, 4)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(3, 3, 4, d_out)) * 0.05,
                             jnp.float32).at[:, 0].set(0.0)}
    return params, lora


def _scan_slices_everything(layers):
    """The parent commit's path: every leaf rides `xs`, the scan slices
    the int8 stacks and the matmul sees a 2-D leaf."""
    n_layers = jax.tree.leaves(layers)[0].shape[0]
    return ((layers, jnp.arange(n_layers)),
            lambda inp: {**inp[0], "layer_idx": inp[1]})


def _cache(paged: bool):
    cache = llama.init_cache(CFG, SLOTS, MAX_LEN, kv_quantize="int8")
    if not paged:
        return cache
    per_slot = MAX_LEN // BLOCK
    # payloads [L, slots, T, kv, hd] -> [L, N, bt, kv, hd]; the lane-major
    # scale planes [L, slots, kv, T] -> [L, N, kv, bt]
    pool = {k: (jnp.moveaxis(v.reshape(CFG.n_layers, SLOTS, -1, per_slot,
                                       BLOCK), 3, 2).reshape(
                    CFG.n_layers, SLOTS * per_slot, -1, BLOCK)
                if k.endswith("_s") else
                v.reshape(CFG.n_layers, SLOTS * per_slot, BLOCK,
                          *v.shape[3:])) for k, v in cache.items()}
    # one spare block 0 (the pool's trash sentinel) ahead of the slots'
    pool = {k: jnp.concatenate([v[:, :1], v], axis=1)
            for k, v in pool.items()}
    pool["tbl"] = 1 + jnp.arange(SLOTS * per_slot, dtype=jnp.int32
                                 ).reshape(SLOTS, per_slot)
    return pool


def _program(kind, params, lora, ids, paged):
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        1, CFG.vocab_size, size=(SLOTS, 8)), jnp.int32)
    if kind == "prefill":
        return llama.prefill(params, tokens, CFG, lora, ids)[0]
    lengths = jnp.asarray([0, 3, 9, 17], jnp.int32)
    if kind == "decode_step":
        return llama.decode_step(params, tokens[:, 0], _cache(paged),
                                 lengths, CFG, lora=lora, ids=ids)[0]
    return llama.verify_step(params, tokens[:, :3], _cache(paged), lengths,
                             CFG, lora=lora, ids=ids)[0]


@pytest.mark.parametrize("with_lora", [False, True], ids=["base", "lora"])
@pytest.mark.parametrize("kind,paged", [
    ("prefill", False), ("decode_step", False), ("decode_step", True),
    ("verify_step", False), ("verify_step", True)],
    ids=["prefill", "decode-slab", "decode-paged", "verify-slab",
         "verify-paged"])
def test_serving_bodies_give_the_scan_sliced_logits(served, monkeypatch,
                                                    kind, paged, with_lora):
    params, lora = served
    lora, ids = ((lora, jnp.asarray([0, 1, 2, 1], jnp.int32))
                 if with_lora else (None, None))
    with quant.count_sites() as sites:
        got = _program(kind, params, lora, ids, paged)
    # every layer matmul reads its layer in place; the head is 2-D
    assert dict(sites) == {"stacked_kernel": 7, "kernel_2d": 1}
    monkeypatch.setattr(llama, "_scan_layers", _scan_slices_everything)
    with quant.count_sites() as sites:
        ref = _program(kind, params, lora, ids, paged)
    assert dict(sites) == {"kernel_2d": 8}
    assert np.isfinite(np.asarray(ref)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue     # the kernel's own body slices its VMEM blocks
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _is_weight_stack(var):
    aval = var.aval
    return (getattr(aval, "dtype", None) == jnp.int8
            and getattr(aval, "ndim", 0) == 3)


def test_decode_program_never_slices_an_int8_weight_stack(served):
    params, _ = served
    jaxpr = jax.make_jaxpr(
        lambda p, t, c, n: llama.decode_step(p, t, c, n, CFG))(
        params, jnp.zeros((SLOTS,), jnp.int32), _cache(False),
        jnp.zeros((SLOTS,), jnp.int32))
    kernels = 0
    for eqn in _walk(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in ("dynamic_slice", "gather", "slice"):
            assert not _is_weight_stack(eqn.invars[0]), eqn
        if name == "scan":   # a scanned operand is a slice per iteration
            skip = eqn.params["num_consts"] + eqn.params["num_carry"]
            assert not any(map(_is_weight_stack, eqn.invars[skip:])), eqn
        if name == "pallas_call":
            kernels += any(map(_is_weight_stack, eqn.invars))
    assert kernels == 7   # wq wk wv wo w_gate w_up w_down, whole stacks


def test_engine_reports_the_census_of_its_warmed_menu():
    """metrics()["quant_matmul_sites"]: the decode programs' layer matmuls
    all run the stacked kernel, every lm_head the 2-D one, and a prefill
    wave wider than the gate (160 rows) the XLA expression."""
    from kubeflow_tpu.serving.llm import LLMEngine

    eng = LLMEngine(llama.init(jax.random.key(0), CFG), CFG, n_slots=2,
                    max_len=256, buckets=(8, 160), quantize="int8",
                    decode_chunk=2, prefix_cache=False)
    assert "quant_matmul_sites" not in eng.metrics()   # nothing traced yet
    eng.warmup()
    programs = len(eng._decode_fns) + len(eng._prefill_fns)
    narrow = len(eng._decode_fns) + 2     # bucket 8 at widths 1 and 2
    assert eng.metrics()["quant_matmul_sites"] == {
        "stacked_kernel": 7 * narrow, "kernel_2d": programs,
        "xla": 7 * (programs - narrow)}
    assert len(eng.generate(list(range(1, 7)), 4)) == 4
    eng.close()


# -- and the KV cache: flash decode reads it where it lies (ISSUE 28) ---------

#: what XLA would have to stage a copy for ahead of a Mosaic custom call
_VIEWS = ("dynamic_slice", "gather", "slice", "reshape", "transpose",
          "convert_element_type", "squeeze", "pad", "copy", "copy_p")


@pytest.mark.parametrize("kind,paged", [
    ("decode_step", False), ("decode_step", True),
    ("verify_step", False), ("verify_step", True)],
    ids=["decode-slab", "decode-paged", "verify-slab", "verify-paged"])
def test_flash_decode_program_never_views_a_cache_array(served, kind, paged):
    """With the flash impl the decode and verify programs hand the cache
    arrays to the kernel as the layer scan carries them: outside the
    pallas_call nothing slices, reshapes, transposes or converts `k`, `v`,
    `k_s` or `v_s` (the payloads' per-step scatter and the carry itself are
    all that touch them), the kernel's operands are the benchmark reader's
    list (ONE s32 vector, the query, two payloads, two scale planes), and
    the planes come back aliased."""
    import dataclasses

    params, _ = served
    cfg = dataclasses.replace(CFG, decode_attention_impl="flash",
                              dtype=jnp.bfloat16)
    cache = _cache(paged)
    s_v = 1 if kind == "decode_step" else 3
    toks = jnp.zeros((SLOTS,) if s_v == 1 else (SLOTS, s_v), jnp.int32)
    step = getattr(llama, kind)
    jaxpr = jax.make_jaxpr(lambda p, t, c, n: step(p, t, c, n, cfg))(
        params, toks, cache, jnp.zeros((SLOTS,), jnp.int32))
    shapes = {(v.shape, v.dtype) for k, v in cache.items() if k != "tbl"}

    def is_cache(var):
        aval = getattr(var, "aval", None)
        return (getattr(aval, "shape", None), getattr(aval, "dtype",
                                                      None)) in shapes

    # the einsum reference DOES slice its layer out: the walk sees it
    ref = jax.make_jaxpr(lambda p, t, c, n: step(
        p, t, c, n, dataclasses.replace(cfg, decode_attention_impl="xla")))(
        params, toks, cache, jnp.zeros((SLOTS,), jnp.int32))
    assert any(eqn.primitive.name in _VIEWS and any(map(is_cache,
                                                        eqn.invars))
               for eqn in _walk(ref.jaxpr))
    kernels = []
    for eqn in _walk(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in _VIEWS:
            assert not any(map(is_cache, eqn.invars)), eqn
        if name == "scan":   # a scanned operand is a slice per iteration
            skip = eqn.params["num_consts"] + eqn.params["num_carry"]
            assert not any(map(is_cache, eqn.invars[skip:])), eqn
        if name == "pallas_call" and any(map(is_cache, eqn.invars)):
            kernels.append(eqn)
    assert len(kernels) == 1      # one call site, inside the layer scan
    avals = [v.aval for v in kernels[0].invars]
    if paged:                     # the tables ride a second s32 operand
        assert avals[1].dtype == jnp.int32 and avals[1].ndim == 2
        del avals[1]
    assert [(a.dtype, a.ndim) for a in avals] == [
        (jnp.int32, 1), (jnp.bfloat16, 4), (jnp.int8, 5), (jnp.int8, 5),
        (jnp.float32, 4), (jnp.float32, 4)]
    assert all(map(is_cache, kernels[0].invars[-4:]))
    aliases = dict(kernels[0].params["input_output_aliases"])
    n_in = len(kernels[0].invars)
    assert aliases == {n_in - 2: 1, n_in - 1: 2}

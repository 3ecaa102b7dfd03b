"""Every Pallas entry point lowers for TPU — compiled (interpret=False),
at the shapes chip_smoke.py serves and trains at — from this CPU process.

Interpret-mode parity (test_flash_decode, test_flash_prefill,
test_quant_matmul, test_attention) says a kernel computes the right
numbers; it says nothing about whether Mosaic accepts its block shapes.
r14/r17/r20 shipped int8-KV kernels that were byte-exact in the
interpreter and that the TPU lowering refused ("the last two dimensions of
your block shape are divisible by 8 and 128 … or equal to the respective
dimensions of the overall array"). That check lives in JAX's own
Pallas→Mosaic lowering, so `lowering_platforms=("tpu",)` raises it here in
milliseconds, with no libtpu and no chip. What only the Mosaic compiler
itself can refuse is covered by the slow-lane topology compiles in
test_contract_serving.py.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.models import llama
from kubeflow_tpu.ops import (flash_decode, flash_pallas, flash_prefill, kda,
                              mla_decode, pallas_compat, quant_matmul, ssd)

#: pallas_call sites per ops module that this file lowers for TPU.
#: scripts/check_kernels.py requires the counts to match the source, so a
#: new kernel (or a new call site) cannot land without a case here.
PALLAS_CALL_SITES = {
    "flash_decode": 1,
    "flash_prefill": 1,
    "quant_matmul": 1,   # one call site, two entries: both lowered below
    "flash_pallas": 3,
    "kda": 5,
    "mla_decode": 1,
    "ssd": 2,
}

# chip_smoke.py's serving shapes: Llama-3-8B heads, 16 slots x 2048
SLOTS, SPAN, HEADS, KV_HEADS, HEAD_DIM = 16, 2048, 32, 8, 128
BLOCK_TOKENS = 128   # paged pool block = gcd of the 128/512/1024 buckets


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def mosaic_calls(fn, *args) -> int:
    """Lower fn for TPU from abstract args; count its Mosaic custom calls."""
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    return lowered.as_text().count("tpu_custom_call")


def kv_operands(batch, tokens, quantized, paged):
    """(k, scale, tables) abstract operands in the slab or pool layout,
    as flash_prefill takes them: ONE layer's rows."""
    dtype = jnp.int8 if quantized else jnp.bfloat16
    if paged:
        n_pool = batch * tokens // BLOCK_TOKENS + 1
        k = sds((n_pool, BLOCK_TOKENS, KV_HEADS, HEAD_DIM), dtype)
        scale = sds((n_pool, BLOCK_TOKENS, KV_HEADS), jnp.float32)
        tables = sds((batch, tokens // BLOCK_TOKENS), jnp.int32)
    else:
        k = sds((batch, tokens, KV_HEADS, HEAD_DIM), dtype)
        scale = sds((batch, tokens, KV_HEADS), jnp.float32)
        tables = None
    return k, (scale if quantized else None), tables


LAYERS = 8   # the served cut's depth: the cache the layer scan carries


def cache_operands(quantized, paged, span):
    """(k, scale, tables) as flash_decode takes them: the WHOLE cache,
    `[L, slots, max_len, kv, hd]` with lane-major scales `[L, slots, kv,
    max_len]`, or the pool `[L, N, bt, kv, hd]` / `[L, N, kv, bt]` behind
    tables clipped to the span."""
    dtype = jnp.int8 if quantized else jnp.bfloat16
    if paged:
        n_pool = SLOTS * SPAN // BLOCK_TOKENS + 1
        k = sds((LAYERS, n_pool, BLOCK_TOKENS, KV_HEADS, HEAD_DIM), dtype)
        scale = sds((LAYERS, n_pool, KV_HEADS, BLOCK_TOKENS), jnp.float32)
        tables = sds((SLOTS, span // BLOCK_TOKENS), jnp.int32)
    else:
        k = sds((LAYERS, SLOTS, SPAN, KV_HEADS, HEAD_DIM), dtype)
        scale = sds((LAYERS, SLOTS, KV_HEADS, SPAN), jnp.float32)
        tables = None
    return k, (scale if quantized else None), tables


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("span", [1024, 2048])
@pytest.mark.parametrize("s_v", [1, 4, 7])   # decode; verify k=3; k=6
def test_flash_decode_lowers(s_v, span, quantized, paged):
    """The in-place entry at the serving shapes: 8 layers of 16 x 2048,
    8 KV heads of 128; the layer a traced index, the span 1024 or the
    whole 2048; int8 with the step's scales handed in and the planes
    coming back aliased (decode and verify both store them)."""
    k, scale, tables = cache_operands(quantized, paged, span)
    new = sds((SLOTS, s_v, KV_HEADS), jnp.float32) if quantized else None

    def fn(q, k, v, lengths, layer, ks, vs, new, tables):
        return flash_decode.flash_decode_attention(
            q, k, v, lengths, layer=layer, span=span, k_scale=ks,
            v_scale=vs, new_scales=(new, new) if quantized else None,
            tables=tables, interpret=False)

    assert mosaic_calls(
        fn, sds((SLOTS, s_v, HEADS, HEAD_DIM), jnp.bfloat16), k, k,
        sds((SLOTS,), jnp.int32), sds((), jnp.int32), scale, scale, new,
        tables) == 1


def test_flash_decode_lowers_for_a_microbatch_of_slots():
    """StageShardedEngine's call: 4 rows of q against slots 8..11 of the
    stage's full-slot slab; the planes read, not stored (the probe's)."""
    k, scale, _ = cache_operands(True, False, 1024)

    def fn(q, k, v, lengths, layer, ks, vs):
        return flash_decode.flash_decode_attention(
            q, k, v, lengths, layer=layer, span=1024, slot_start=8,
            k_scale=ks, v_scale=vs, interpret=False)

    assert mosaic_calls(
        fn, sds((4, 1, HEADS, HEAD_DIM), jnp.bfloat16), k, k,
        sds((4,), jnp.int32), sds((), jnp.int32), scale, scale) == 1


@pytest.mark.parametrize("heads", [48, 64])   # 6 and 8 to a KV head
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_flash_decode_lowers_over_a_ring_with_a_window(heads, quantized):
    """The sliding layers' call of the laguna cell: 3 layers of 32 slots x
    a ring of 1536 rows, a window of 512, the layer a static index; int8
    with the step's scales stored by the kernel."""
    dtype = jnp.int8 if quantized else jnp.bfloat16
    k = sds((3, 32, 1536, KV_HEADS, HEAD_DIM), dtype)
    scale = sds((3, 32, KV_HEADS, 1536), jnp.float32) if quantized else None
    new = sds((32, 1, KV_HEADS), jnp.float32) if quantized else None

    def fn(q, k, v, lengths, ks, vs, new):
        return flash_decode.flash_decode_attention(
            q, k, v, lengths, layer=2, k_scale=ks, v_scale=vs,
            new_scales=(new, new) if quantized else None, window=512,
            interpret=False)

    assert mosaic_calls(
        fn, sds((32, 1, heads, HEAD_DIM), jnp.bfloat16), k, k,
        sds((32,), jnp.int32), scale, scale, new) == 1


@pytest.mark.parametrize("heads", [48, 64])
@pytest.mark.parametrize("q_offset", [0, 512])
def test_flash_prefill_lowers_with_a_window(q_offset, heads):
    """A chunk of 1024 rows after the last `window` rows of its prefix
    (the continuation chain's sliding layers), and a first chunk."""
    width, chunk = 4, 1024
    k = sds((width, q_offset + chunk, KV_HEADS, HEAD_DIM), jnp.bfloat16)

    def fn(q, k, v):
        return flash_prefill.flash_prefill_attention(
            q, k, v, q_offset=q_offset, window=512, interpret=False)

    assert mosaic_calls(
        fn, sds((width, chunk, heads, HEAD_DIM), jnp.bfloat16), k, k) == 1


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("q_offset", [0, 1024])
def test_flash_prefill_lowers(q_offset, quantized, paged):
    width, chunk = 4, 512
    k, scale, tables = kv_operands(width, q_offset + chunk, quantized,
                                   paged)

    def fn(q, k, v, ks, vs, tables):
        return flash_prefill.flash_prefill_attention(
            q, k, v, q_offset=q_offset, k_scale=ks, v_scale=vs,
            tables=tables, interpret=False)

    assert mosaic_calls(
        fn, sds((width, chunk, HEADS, HEAD_DIM), jnp.bfloat16), k, k,
        scale, scale, tables) == 1


@pytest.mark.parametrize("rows,d_in,d_out", [
    (16, 4096, 4096),       # wq / wo, one decode step of 16 slots
    (112, 4096, 1024),      # wk / wv, a k=6 verify round (16 x 7 rows)
    (16, 4096, 14336),      # w_gate / w_up
    (16, 14336, 4096),      # w_down
    (112, 4096, 128256),    # lm_head at the Llama-3 vocabulary
])
def test_quant_matmul_lowers(rows, d_in, d_out):
    assert quant_matmul.kernel_applicable(rows, d_in, d_out)
    assert mosaic_calls(
        lambda x, q, s: quant_matmul._dequant_matmul_2d(
            x, q, s, out_dtype=jnp.dtype(jnp.bfloat16), interpret=False),
        sds((rows, d_in), jnp.bfloat16), sds((d_in, d_out), jnp.int8),
        sds((d_out,), jnp.float32)) == 1


@pytest.mark.parametrize("rows,d_in,d_out", [
    (16, 4096, 4096),       # wq / wo of a stack of 8 layers
    (112, 4096, 1024),      # wk / wv, a k=6 verify round
    (16, 4096, 14336),      # w_gate / w_up
    (16, 14336, 4096),      # w_down
    (1, 4096, 4096),        # one row, padded to the sublane floor
])
def test_quant_matmul_stacked_lowers(rows, d_in, d_out):
    assert quant_matmul.kernel_applicable(rows, d_in, d_out)
    assert mosaic_calls(
        lambda layer, x, q, s: quant_matmul._dequant_matmul_stacked(
            layer, x, q, s, out_dtype=jnp.dtype(jnp.bfloat16),
            interpret=False),
        sds((1,), jnp.int32), sds((rows, d_in), jnp.bfloat16),
        sds((8, d_in, d_out), jnp.int8),
        sds((8, d_out), jnp.float32)) == 1


def test_flash_pallas_forward_and_backward_lower():
    # chip_smoke.py's train proxy: batch 6, seq 2048, 16 heads of 128
    q = sds((6, 2048, 16, 128), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_pallas.pallas_flash_attention(q, k, v, causal=True,
                                                   interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    assert mosaic_calls(fwd, q, q, q) == 1
    # value_and_grad: the forward kernel, then the dq and the dk/dv kernels
    assert mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), q, q, q) == 3


def test_flash_pallas_lowers_with_qk_192_beside_v_128():
    # latent attention at the Kimi-Linear cut: 2 rows of 8192, 32 heads
    q = sds((2, 8192, 32, 192), jnp.bfloat16)
    v = sds((2, 8192, 32, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_pallas.pallas_flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32))

    assert mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), q, q, v) == 3


@pytest.mark.parametrize("span", [128, 2048])
def test_mla_decode_lowers(span):
    # the openPangu cell's slab at a smaller length: 64 slots, 128 heads,
    # latent rows of 512 + 64 padded to 640 lanes
    q = sds((64, 128, 640), jnp.bfloat16)
    slab = sds((5, 64, 2048, 640), jnp.bfloat16)

    def fn(q, slab, lengths):
        return mla_decode.mla_decode_attention(
            q, slab, lengths, layer=2, latent=512, scale=192 ** -0.5,
            span=span, interpret=False)

    assert mosaic_calls(fn, q, slab, sds((64,), jnp.int32)) == 1


def test_flash_pallas_lowers_for_a_latent_continuation_chunk():
    # the served latent prefill: a 1024-row chunk after 2048 cached rows
    q = sds((1, 1024, 128, 192), jnp.bfloat16)
    k = sds((1, 3072, 128, 192), jnp.bfloat16)
    v = sds((1, 3072, 128, 128), jnp.bfloat16)

    def fn(q, k, v):
        return flash_pallas.pallas_flash_attention(
            q, k, v, causal=True, q_offset=2048, interpret=False)

    assert mosaic_calls(fn, q, k, v) == 1


def _flash_grad_lowered(q, v):
    def loss(q, k, v):
        return jnp.sum(flash_pallas.pallas_flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, q, v).lower(
        lowering_platforms=("tpu",))


def test_flash_pallas_kernels_keep_the_operands_the_benchmark_reads():
    """`flash_attention_roofline` and `mla_attention_roofline` tell the
    forward, dQ and dK/dV kernels by their operand lists and count
    layer-steps by the dK/dV kernel's calls: at both training cells' shapes
    each lowered call is matched by its own pattern and by no other."""
    fa, mla = opcount_module("flash_attention"), opcount_module(
        "mla_attention")
    # train_fsdp2tp2: a chip's island is 4 rows x 16 heads of 2048 x 128
    dense = sds((4, 2048, 16, 128), jnp.bfloat16)
    fwd, dq, dkv = kernel_event_names(
        _flash_grad_lowered(dense, dense), "shard_map.3601")
    assert fwd == ("shard_map.3601(s32[1],bf16[64,2048,128],"
                   "bf16[64,2048,128],bf16[64,2048,128])->"
                   "bf16[64,2048,128],f32[64,2,1,1024]")
    assert fa.FORWARD.match(fwd) and not fa.BACKWARD_Q.match(fwd)
    assert fa.BACKWARD_Q.match(dq) and not fa.BACKWARD_KV.match(dq)
    assert fa.BACKWARD_KV.match(dkv) and not fa.BACKWARD_Q.match(dkv)
    assert not any(mla.kernel(name) for name in (fwd, dq, dkv))
    # train_kimi_linear_ep32_s8k: 2 rows x 32 heads, q/k 192 (padded to
    # 256 lanes) beside v 128
    names = kernel_event_names(_flash_grad_lowered(
        sds((2, 8192, 32, 192), jnp.bfloat16),
        sds((2, 8192, 32, 128), jnp.bfloat16)), "mla.4")
    assert [mla.kernel(name).re for name in names] == [
        mla.FORWARD, mla.BACKWARD_Q, mla.BACKWARD_KV]
    assert names[2].endswith("->bf16[64,8192,256],bf16[64,8192,128]")


# the Kimi-Linear cut: 2 rows x 32 heads of 8192 positions, dk = dv = 128
KDA_ROWS = sds((64, 8192, 128), jnp.bfloat16)
KDA_DECAY = sds((64, 8192, 128), jnp.float32)
KDA_SQUARE = sds((64, 128, 64, 64), jnp.float32)     # A, B, M of every chunk
KDA_STATES = sds((64, 128, 128, 128), jnp.float32)   # S^T at every chunk's start


@pytest.mark.parametrize("emit_states", [False, True])
def test_kda_kernels_lower(emit_states):
    rows, decay, square = KDA_ROWS, KDA_DECAY, KDA_SQUARE
    assert mosaic_calls(
        lambda q, k, gc: kda._intra_pallas(
            q, k, gc, interpret=False, mm_dtype=jnp.bfloat16),
        rows, rows, decay) == 1
    assert mosaic_calls(
        lambda q, k, v, gc, m, b: kda._state_pallas(
            q, k, v, gc, m, b, emit_states=emit_states, interpret=False,
            mm_dtype=jnp.bfloat16),
        rows, rows, rows, decay, square, square) == 1


KDA_COTANGENTS = sds((64, 128, 64, 128), jnp.float32)   # [BH, NC, C, dk]


def kda_backward_call(kernel="state"):
    """One of the backward's two kernels at the Kimi-Linear cut, lowered for
    TPU: the chunk walk (`state`: the operands of the forward's walk, the
    state at every chunk's start and the output's cotangent), or the chunk
    sums and UT transform differentiated (`prepare`: q, k, v, the decay,
    beta, X and the walk's six cotangents)."""
    if kernel == "state":
        return jax.jit(
            lambda q, k, v, gc, m, b, h, do: kda._state_bwd_pallas(
                q, k, v, gc, m, b, h, do, interpret=False,
                mm_dtype=jnp.bfloat16)).trace(
            KDA_ROWS, KDA_ROWS, KDA_ROWS, KDA_DECAY, KDA_SQUARE, KDA_SQUARE,
            KDA_STATES, KDA_ROWS).lower(lowering_platforms=("tpu",))
    d_ops = (KDA_COTANGENTS,) * 3 + (KDA_SQUARE, KDA_COTANGENTS,
                                     sds((64, 128, 128), jnp.float32))
    return jax.jit(
        lambda q, k, v, gc, beta, x, *d: kda._prepare_bwd_pallas(
            q, k, v, gc, beta, x, d, interpret=False,
            mm_dtype=jnp.bfloat16)).trace(
        KDA_ROWS, KDA_ROWS, KDA_ROWS, KDA_DECAY,
        sds((64, 8192), jnp.float32), KDA_SQUARE,
        *d_ops).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("kernel,shapes", [
    ("state", [(64, 128, 64, 128)] * 3 + [(64, 128, 64, 64),
                                          (64, 128, 64, 128), (64, 128, 128)]),
    ("prepare", [(64, 8192, 128)] * 4 + [(64, 8192)])])
def test_kda_backward_kernel_lowers(kernel, shapes):
    # the walk's six cotangents heads first, then those of q, k, v, g, beta
    lowered = kda_backward_call(kernel)
    assert lowered.as_text().count("tpu_custom_call") == 1
    assert [tuple(x.shape) for x in jax.tree.leaves(lowered.out_info)] == (
        shapes)


def kda_solve_call():
    """The forward's solve at the Kimi-Linear cut, lowered for TPU: A of
    every chunk as the chunk-sum kernel writes it and beta [BH, NC, C]."""
    return jax.jit(lambda a, beta: kda._ut_pallas(a, beta, interpret=False)
                   ).trace(KDA_SQUARE, sds((64, 128, 64), jnp.float32)
                           ).lower(lowering_platforms=("tpu",))


def test_kda_solve_kernel_lowers():
    # M and X of every chunk, float32, as the walk and the backward read them
    lowered = kda_solve_call()
    assert lowered.as_text().count("tpu_custom_call") == 1
    assert [(tuple(x.shape), x.dtype) for x in jax.tree.leaves(
        lowered.out_info)] == [((64, 128, 64, 64), jnp.float32)] * 2


def opcount_module(name):
    """benchmark/opcount/<name>.py, whose patterns tell a kernel in a
    capture by its operand list."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_opcount_{name}", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", "opcount", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def kernel_event_names(lowered, name):
    """Each Mosaic call of a lowered program as lib/tracered.short_name
    writes a kernel's event: `name(operand shapes)->result shapes`."""
    def shapes(types):    # tensor<64x8192x128xbf16>, .. -> bf16[64,8192,128],..
        return ",".join(   # HLO writes MLIR's i32 as s32
            f"{re.sub('^i', 's', t)}[{dims.replace('x', ',')}]" for dims, t
            in re.findall(r"tensor<([\dx]+)x(\w+)>", types))

    return [f"{name}({shapes(operands)})->{shapes(results)}"
            for operands, results in re.findall(
                r"tpu_custom_call[^\n]*:\s*\(([^)]*)\)\s*->\s*\(?([^)\n]*)",
                lowered.as_text())]


@pytest.mark.parametrize("kernel,operands", [
    ("state", "bf16[64,8192,128],bf16[64,8192,128],bf16[64,8192,128],"
              "f32[64,8192,128],f32[64,128,64,64],f32[64,128,64,64],"
              "f32[64,128,128,128],bf16[64,8192,128])->f32["),
    ("prepare", "bf16[64,8192,128],bf16[64,8192,128],bf16[64,8192,128],"
                "f32[64,8192,128],f32[64,128,1,64],f32[64,128,64,64],"
                "f32[64,128,64,128],f32[64,128,64,128],f32[64,128,64,128],"
                "f32[64,128,64,64],f32[64,128,64,128],f32[64,128,1,128])"
                "->bf16[64,8192,128],bf16[64,8192,128],bf16[64,8192,128],"
                "f32[64,8192,128],f32[64,128,1,64]")])
def test_kda_backward_kernels_are_not_read_as_forward_kernels(kernel,
                                                              operands):
    """The benchmark tells the two forward kernels by their operands
    (benchmark/opcount/kda_chunk.py, three and six) and computes a roofline
    share of the FORWARD from their time: the backward's kernels take eight
    and twelve, so neither pattern may take either for one of them."""
    kc = opcount_module("kda_chunk")
    name, = kernel_event_names(kda_backward_call(kernel), "kda_backward.4")
    assert name.startswith(f"kda_backward.4({operands}")
    assert not kc.INTRA.match(name) and not kc.STATE.match(name)


def test_kda_solve_kernel_is_not_read_as_a_forward_kernel():
    """Nor is the forward's solve (two operands, both float32) one of the
    two kernels whose time `kda_chunk_roofline` divides its count by."""
    kc = opcount_module("kda_chunk")
    name, = kernel_event_names(kda_solve_call(), "kda_solve.4")
    assert name == ("kda_solve.4(f32[64,128,64,64],f32[64,128,64])"
                    "->f32[64,128,64,64],f32[64,128,64,64]")
    assert not kc.INTRA.match(name) and not kc.STATE.match(name)


def pallas_grids(fn, *args):
    """The grid of each pallas_call that fn traces to, in order."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [eqn.params["grid_mapping"].grid for eqn in jaxpr.eqns
            if eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("emit_states", [False, True])
def test_kda_forward_kernels_take_eight_chunks_or_heads_a_step(emit_states):
    """At the cut the chunk sums take 8 chunks of a head a grid step and the
    walk 8 heads: 64 x 16 and 8 x 128 steps, not 64 x 128 each. Their
    operands and results are those of one chunk (one head) a step, so
    `kda_chunk_roofline` still tells each by its own pattern and not by the
    other's (benchmark/opcount/kda_chunk.py)."""
    kc = opcount_module("kda_chunk")
    rows, decay, square = KDA_ROWS, KDA_DECAY, KDA_SQUARE
    intra = lambda q, k, gc: kda._intra_pallas(
        q, k, gc, interpret=False, mm_dtype=jnp.bfloat16)
    state = lambda q, k, v, gc, m, b: kda._state_pallas(
        q, k, v, gc, m, b, emit_states=emit_states, interpret=False,
        mm_dtype=jnp.bfloat16)
    assert pallas_grids(intra, rows, rows, decay) == [(64, 16)]
    assert pallas_grids(state, rows, rows, rows, decay, square, square) == [
        (8, 128)]
    lower = lambda fn, *a: jax.jit(fn).trace(*a).lower(
        lowering_platforms=("tpu",))
    name, = kernel_event_names(lower(intra, rows, rows, decay), "kda.1")
    assert name == ("kda.1(bf16[64,8192,128],bf16[64,8192,128],"
                    "f32[64,8192,128])->f32[64,128,64,64],f32[64,128,64,64]")
    assert kc.INTRA.match(name) and not kc.STATE.match(name)
    name, = kernel_event_names(
        lower(state, rows, rows, rows, decay, square, square), "kda.3")
    assert name.endswith("->bf16[64,8192,128]" + (
        ",f32[64,128,128,128]" if emit_states else ""))
    assert kc.STATE.match(name) and not kc.INTRA.match(name)


def test_kda_training_step_at_the_cut_is_five_kernels_two_of_them_read(
        monkeypatch):
    """A KDA layer's forward and backward at the cut, lowered for a TPU
    target: five Mosaic calls (chunk sums, solve, walk; the backward's
    two), of which `kda_chunk_roofline` reads exactly the chunk sums and
    the walk (the walk writing the states, as `custom_vjp`'s forward rule
    has it)."""
    monkeypatch.setattr(pallas_compat, "target_platform", lambda: "tpu")
    kc = opcount_module("kda_chunk")
    heads = sds((2, 8192, 32, 128), jnp.bfloat16)
    decay = sds((2, 8192, 32, 128), jnp.float32)
    f = lambda q, k, v, g, beta: jnp.sum(kda.chunk_kda(q, k, v, g, beta))
    lowered = jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4))).trace(
        heads, heads, heads, decay, sds((2, 8192, 32), jnp.float32)).lower(
        lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") == 5
    names = kernel_event_names(lowered, "kda.4")
    assert len(names) == 5
    assert [n for n in names if kc.INTRA.match(n)] == [
        "kda.4(bf16[64,8192,128],bf16[64,8192,128],f32[64,8192,128])"
        "->f32[64,128,64,64],f32[64,128,64,64]"]
    assert [n.partition("->")[2] for n in names if kc.STATE.match(n)] == [
        "bf16[64,8192,128],f32[64,128,128,128]"]


def test_unsupported_head_dim_is_refused_at_engine_construction(
        monkeypatch):
    """head_dim 32 (examples/llm-inference-service.yaml) cannot be tiled
    by the serving flash kernels. On a TPU target `auto` must resolve to
    xla at construction — visibly, in what metrics()/healthz report — and
    an explicit `flash` must raise there with the reason, not die in the
    compiler at the first prefill."""
    from kubeflow_tpu.serving.llm import LLMEngine

    monkeypatch.setattr(pallas_compat, "target_platform", lambda: "tpu")
    cfg = llama.LlamaConfig(vocab_size=128, d_model=128, n_layers=1,
                            n_heads=4, n_kv_heads=2, d_ff=128,
                            max_seq_len=64, remat=False)
    assert cfg.head_dim == 32
    params = llama.init(jax.random.key(0), cfg)
    kw = dict(n_slots=2, max_len=32, buckets=(8,))
    with pytest.raises(ValueError, match="head_dim 32"):
        LLMEngine(params, cfg, decode_attention_impl="flash", **kw)
    with pytest.raises(ValueError, match="head_dim 32"):
        LLMEngine(params, cfg, prefill_attention_impl="flash", **kw)
    eng = LLMEngine(params, cfg, **kw)
    assert eng.cfg.decode_attention_impl == "xla"
    assert eng.cfg.prefill_attention_impl == "xla"
    # and at a head_dim the kernels tile, auto takes them
    wide = llama.LlamaConfig(vocab_size=128, d_model=256, n_layers=1,
                             n_heads=2, n_kv_heads=2, d_ff=128,
                             max_seq_len=64, remat=False)
    eng = LLMEngine(llama.init(jax.random.key(0), wide), wide, **kw)
    assert eng.cfg.decode_attention_impl == "flash"
    assert eng.cfg.prefill_attention_impl == "flash"


# -- the state-space layer's two kernels at the Nemotron-H cut ---------------
# a prompt wave of 4 x 1024 positions, 128 heads of 64, 8 groups of B and C
# of 128; a decode step of 96 slots over the 5 layers' state slab

def ssd_scan_lowered():
    def scan(x, dt, cum, bm, cm, h0):
        return ssd._scan_pallas(x, dt, cum, bm, cm, h0, interpret=False)
    return jax.jit(scan).trace(
        sds((4, 1024, 128, 64), jnp.bfloat16), sds((4, 1024, 128), jnp.float32),
        sds((4, 1024, 128), jnp.float32), sds((4, 1024, 8, 128), jnp.bfloat16),
        sds((4, 1024, 8, 128), jnp.bfloat16),
        sds((4, 128, 64, 128), jnp.float32)).lower(lowering_platforms=("tpu",))


def ssm_step_lowered(state_dtype):
    def step(states, x, dt, decay, bm, cm):
        return ssd._step_pallas(states, 3, x, dt, decay, bm, cm,
                                interpret=False)
    return jax.jit(step).trace(
        sds((5, 96, 128, 64, 128), state_dtype),
        sds((96, 128, 64), jnp.bfloat16), sds((96, 128), jnp.float32),
        sds((96, 128), jnp.float32), sds((96, 8, 128), jnp.bfloat16),
        sds((96, 8, 128), jnp.bfloat16)).lower(lowering_platforms=("tpu",))


#: every kernel pattern an accepted reader tells its kernel by
def _accepted_patterns():
    fa, ma, ms, kc = (opcount_module(n) for n in (
        "flash_attention", "mla_attention", "mla_serve", "kda_chunk"))
    gm, mo = opcount_module("grouped_matmul"), opcount_module("moe_serve")
    return [fa.FORWARD, fa.BACKWARD_KV, fa.BACKWARD_Q, ma.FORWARD,
            ma.BACKWARD_KV, ma.BACKWARD_Q, ms.DECODE, ms.PREFILL, kc.INTRA,
            kc.STATE, gm.GMM, gm.TGMM, mo.GMM]


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernels_lower_and_are_told_by_their_names(state_dtype):
    """Both kernels lower at the cut; the benchmark's readers find each by
    its own name, and no accepted reader's pattern takes either."""
    o = opcount_module("ssd")
    scan = kernel_event_names(ssd_scan_lowered(),
                              ssd.SCAN_KERNEL + ".1")
    step = kernel_event_names(ssm_step_lowered(state_dtype),
                              ssd.STEP_KERNEL + ".1")
    assert len(scan) == len(step) == 1
    assert o.SCAN.match(scan[0]) and not o.step_call(scan[0])
    assert o.step_call(step[0]) == jnp.dtype(state_dtype).itemsize
    assert not o.SCAN.match(step[0])
    for name in scan + step:
        assert not any(p.match(name) for p in _accepted_patterns()), name


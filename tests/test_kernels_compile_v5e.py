"""The KDA kernels compiled by the REAL v5e compiler at the Kimi-Linear cut,
the served latent attention's two kernels at the openPangu cell's shapes and
the state-space layer's two at the Nemotron-H cell's, from this CPU process: a v5e topology described, not attached (the
on-chip-measurement guide, section 2). tests/test_kernels_lower_tpu.py runs
JAX's own Pallas->Mosaic lowering, which the interpreter's parity tests do
not; this file runs Mosaic itself, which refused a slice of an iota that
both of those accepted (PR 37). Nothing runs: no result, no time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU's library, and a worker that cannot
skips these tests instead of leaving the suite uncollected.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.ops import flash_pallas, kda, mla_decode, ssd


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def kimi_cut(sharding):
    """2 rows x 32 heads of 8192 positions, dk = dv = 128: rows, decay,
    beta (also by chunk), square ([BH, NC, 64, 64]), per-chunk rows, states,
    gamma."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=sharding)
    return dict(rows=sds((64, 8192, 128), jnp.bfloat16),
                decay=sds((64, 8192, 128), jnp.float32),
                beta=sds((64, 8192), jnp.float32),
                beta_chunks=sds((64, 128, 64), jnp.float32),
                square=sds((64, 128, 64, 64), jnp.float32),
                chunk_rows=sds((64, 128, 64, 128), jnp.float32),
                states=sds((64, 128, 128, 128), jnp.float32),
                gamma=sds((64, 128, 128), jnp.float32))


def kernel_call(kernel, s):
    """(function, abstract operands) of one of the five KDA kernels."""
    kw = dict(interpret=False, mm_dtype=jnp.bfloat16)
    if kernel == "solve":
        return (lambda a, beta: kda._ut_pallas(a, beta, interpret=False),
                (s["square"], s["beta_chunks"]))
    if kernel == "intra":
        return (lambda q, k, gc: kda._intra_pallas(q, k, gc, **kw),
                (s["rows"], s["rows"], s["decay"]))
    if kernel == "state":
        return (lambda q, k, v, gc, m, b: kda._state_pallas(
                    q, k, v, gc, m, b, emit_states=True, **kw),
                (s["rows"],) * 3 + (s["decay"], s["square"], s["square"]))
    if kernel == "state_bwd":
        return (lambda *a: kda._state_bwd_pallas(*a, **kw),
                (s["rows"],) * 3 + (s["decay"], s["square"], s["square"],
                                    s["states"], s["rows"]))
    return (lambda q, k, v, gc, beta, x, *d: kda._prepare_bwd_pallas(
                q, k, v, gc, beta, x, d, **kw),
            (s["rows"],) * 3 + (s["decay"], s["beta"], s["square"])
            + (s["chunk_rows"],) * 3 + (s["square"], s["chunk_rows"],
                                        s["gamma"]))


@pytest.mark.parametrize("kernel", ["intra", "state", "state_bwd",
                                    "prepare_bwd", "solve"])
def test_kda_kernel_compiles_for_v5e(one_chip, kernel):
    fn, args = kernel_call(kernel, kimi_cut(one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


@pytest.mark.parametrize("emit_states", [False, True])
def test_kda_forward_kernels_compile_blocked_and_stay_readable(one_chip,
                                                               emit_states):
    """The chunk sums at 8 chunks a grid step and the walk at 8 heads (its
    states [8, 128, 128] float32 in VMEM) fit the v5e's scoped VMEM, and
    the compiled calls carry the operands and results `kda_chunk_roofline`
    tells them by (benchmark/opcount/kda_chunk.py)."""
    s = kimi_cut(one_chip)
    kw = dict(interpret=False, mm_dtype=jnp.bfloat16)
    assert (kda._per_step(128), kda._per_step(64)) == (8, 8)
    kc = bench_module("opcount/kda_chunk")
    intra = jax.jit(lambda q, k, gc: kda._intra_pallas(q, k, gc, **kw)).lower(
        s["rows"], s["rows"], s["decay"]).compile()
    state = jax.jit(lambda q, k, v, gc, m, b: kda._state_pallas(
        q, k, v, gc, m, b, emit_states=emit_states, **kw)).lower(
        *(s["rows"],) * 3, s["decay"], s["square"], s["square"]).compile()
    event, = kernel_events(intra)
    assert kc.INTRA.match(event) and not kc.STATE.match(event)
    event, = kernel_events(state)
    assert kc.STATE.match(event) and not kc.INTRA.match(event)
    assert event.endswith(",f32[64,128,128,128]") == emit_states


# -- the served latent attention at the openPangu cell's shapes ---------------
# 64 slots, 128 heads, a slab of 5 layers x 11,264 rows of 512 + 64 values
# padded to 640 lanes

def bench_module(path):
    """benchmark/<path>.py, loaded by path."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + path.replace("/", "_"), os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", path + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def kernel_events(compiled):
    """Each Mosaic call of a compiled program as the trace reader names its
    event, `name(operand shapes)->result shapes` (lib/tracered.short_name):
    the compiled text names the operands by reference, and their shapes
    stand in `operand_layout_constraints`."""
    shape = re.compile(r"\b([a-z]+\d*\[[\d,]*\])")
    out = []
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        head, _, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        result = rest.partition(" custom-call(")[0]
        operands = re.search(r"operand_layout_constraints=\{(.*?)\}, "
                             r"(?:frontend_attributes|metadata)", rest)
        out.append(f"{head.lstrip('%')}("
                   f"{','.join(shape.findall(operands[1]))})"
                   f"->{','.join(shape.findall(result))}")
    return out


@pytest.mark.parametrize("span", [128, 4096, 11264])
def test_mla_decode_kernel_compiles_for_v5e(one_chip, span):
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)

    def fn(q, slab, lengths):
        return mla_decode.mla_decode_attention(
            q, slab, lengths, layer=3, latent=512, scale=192 ** -0.5,
            span=span, interpret=False)

    compiled = jax.jit(fn).lower(sds((64, 128, 640), jnp.bfloat16),
                                 sds((5, 64, 11264, 640), jnp.bfloat16),
                                 sds((64,), jnp.int32)).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    # the slab is the operand: no copy of it round the call (at 576 lanes
    # the compiler copied all 4.6 GB of it into the layout Mosaic reads)
    assert not [line for line in text.splitlines()
                if " copy(" in line and "bf16[5,64,11264,640]" in line]
    # mla_latent_decode_roofline finds the call by its operands
    told = bench_module("opcount/mla_serve").decode_call
    assert [told(e) for e in kernel_events(compiled)] == [
        (64, 128, 640, 512)]


@pytest.mark.parametrize("q_offset", [0, 7168])
def test_mla_prefill_kernel_compiles_for_v5e(one_chip, q_offset):
    """A 1024-row chunk after a cached prefix of `q_offset` rows: q and k
    of 192 (padded to 256 lanes inside) beside values of 128."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    t = q_offset + 1024

    def fn(q, k, v):
        return flash_pallas.pallas_flash_attention(
            q, k, v, causal=True, scale=192 ** -0.5, q_offset=q_offset,
            interpret=False)

    compiled = jax.jit(fn).lower(sds((1, 1024, 128, 192), jnp.bfloat16),
                                 sds((1, t, 128, 192), jnp.bfloat16),
                                 sds((1, t, 128, 128), jnp.bfloat16)
                                 ).compile()
    # mla_prefill_roofline finds the call by its operands
    told = bench_module("opcount/mla_serve").prefill_call
    assert [told(e) for e in kernel_events(compiled)] == [(128, 1024, t)]


# -- the state-space layer's two kernels at the Nemotron-H cell's shapes ------
# a prompt wave of 4 x 1024, 128 heads of 64, state 128, 8 groups; a decode
# step of 96 slots over the slab of 5 layers. Mosaic refused here, not in
# the lowering test, a [1, 1] broadcast into both axes and a slice of a
# column of the chunk's last decay

def test_ssd_scan_kernel_compiles_for_v5e(one_chip):
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)

    def fn(x, dt, cum, bm, cm, h0):
        return ssd._scan_pallas(x, dt, cum, bm, cm, h0, interpret=False)

    compiled = jax.jit(fn).lower(
        sds((4, 1024, 128, 64), jnp.bfloat16), sds((4, 1024, 128), jnp.float32),
        sds((4, 1024, 128), jnp.float32), sds((4, 1024, 8, 128), jnp.bfloat16),
        sds((4, 1024, 8, 128), jnp.bfloat16),
        sds((4, 128, 64, 128), jnp.float32)).compile()
    told = bench_module("opcount/ssd")
    events = kernel_events(compiled)
    assert len(events) == 1 and told.SCAN.match(events[0])


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_state_step_compiles_for_v5e_in_place(one_chip, state_dtype):
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)

    def fn(states, x, dt, decay, bm, cm):
        return ssd._step_pallas(states, 3, x, dt, decay, bm, cm,
                                interpret=False)

    slab = (5, 96, 128, 64, 128)
    compiled = jax.jit(fn, donate_argnums=0).lower(
        sds(slab, state_dtype), sds((96, 128, 64), jnp.bfloat16),
        sds((96, 128), jnp.float32), sds((96, 128), jnp.float32),
        sds((96, 8, 128), jnp.bfloat16),
        sds((96, 8, 128), jnp.bfloat16)).compile()
    text = compiled.as_text()
    # the slab is the operand and the result: no copy of it round the call
    assert not [line for line in text.splitlines()
                if " copy(" in line and "[5,96,128,64,128]" in line]
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        5 * 96 * 128 * 64 * 128 * jnp.dtype(state_dtype).itemsize)
    told = bench_module("opcount/ssd")
    events = kernel_events(compiled)
    assert [told.step_call(e) for e in events] == [
        jnp.dtype(state_dtype).itemsize]


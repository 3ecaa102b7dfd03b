"""BASELINE config #5 contract proofs: Llama-3-8B InferenceService on v5e.

The serving twin of test_contract_8b.py (VERDICT r2 missing #3): the
engine's prefill/decode program menu at true 8B dims, sharded KV cache and
weights on a tensor=8 mesh, proven against the real v5e compiler via PJRT
topology AOT — bf16 and weight-only int8 variants — plus the single-chip
menu with the Pallas kernels selected, which is what chip_smoke.py serves.
"""

import pytest

from kubeflow_tpu.serving.contract import aot_serving_report


def _require_v5e(topology="v5e:2x4"):
    try:
        from jax.experimental import topologies
        topologies.get_topology_desc(topology)
    except Exception as e:  # no TPU PJRT plugin on this host
        pytest.skip(f"v5e topology unavailable: {e}")


# Llama-3-8B widths at depth 2: every program is a scan over layers, so
# depth changes what a program holds, not what the compiler must accept
W8B_L2 = dict(vocab_size=128256, d_model=4096, n_layers=2, n_heads=32,
              n_kv_heads=8, d_ff=14336, max_seq_len=2048)


def test_8b_serving_programs_lower_on_8_device_mesh(devices8):
    # lower-only on the virtual CPU mesh: proves sharding propagation
    # through the REAL engine program methods at true 8B dims — including
    # the speculative verify program and the multi-adapter prefill/decode
    # (r3 advisor: these used to be asserted in range, not lowered)
    report = aot_serving_report(topology=None, n_devices=8, do_compile=False,
                                speculative=4, n_adapters=2)
    assert report["lowered"]
    assert report["speculative"] == 4 and report["n_adapters"] == 2
    assert report["n_params"] == 8030261248
    assert report["tensor_parallel"] == 8
    # bf16 weights over 8 chips: ~2.01 GB/device
    assert report["weight_bytes_per_device"] < 2.2 * 1024**3
    # KV cache: L32 x 8 slots x 8192 x (8/8) kv-heads x 128 x bf16 x {k,v}
    assert report["kv_cache_bytes_per_device"] == \
        32 * 8 * 8192 * 1 * 128 * 2 * 2


@pytest.mark.slow
@pytest.mark.parametrize("quantize,kv_quantize,spec,n_adapters", [
    (None, None, None, 0),       # bf16 weights, bf16 KV
    ("int8", None, None, 0),     # int8 weights
    ("int8", "int8", 4, 2),      # full production decode config, plus the
                                 # speculative + multi-adapter programs
])
def test_8b_serving_menu_compiles_for_real_v5e8_within_hbm(
        quantize, kv_quantize, spec, n_adapters):
    _require_v5e()
    report = aot_serving_report(quantize=quantize, kv_quantize=kv_quantize,
                                speculative=spec, n_adapters=n_adapters)
    assert report["compiled"]
    assert report["fits_v5e_hbm"], report
    # int8 halves weight residency vs bf16 (scales add ~1%)
    if quantize == "int8":
        assert report["weight_bytes_per_device"] < 1.2 * 1024**3
    if kv_quantize == "int8":
        # int8 payload + f32/128-per-head scales: ~0.53x the bf16 cache
        bf16_cache = 32 * 8 * 8192 * 1 * 128 * 2 * 2
        assert report["kv_cache_bytes_per_device"] < 0.6 * bf16_cache
    peaks = report["peak_bytes_per_device"]
    expected = {"prefill_b2048_w4", "decode_x8",
                "cont_p2048_t2048",   # prefix-hit / 1st boundary
                "cont_p6144_t2048",   # largest chain boundary
                "extract_p6144"}      # the extract feeding it
    if spec:
        expected.add(f"spec_k{spec}_x8")
    if n_adapters:
        expected.add(f"adapter_prefill_a{n_adapters}_r16")
        expected.add(f"adapter_decode_a{n_adapters}_r16")
    if spec and n_adapters:   # the combined decode program
        expected.add(f"spec_k{spec}_adapter_a{n_adapters}_x8")
    if spec or n_adapters:    # worst-boundary continuation, full feature set
        expected.add("cont_p6144_t2048"
                     + (f"_spec{spec}" if spec else "")
                     + (f"_a{n_adapters}" if n_adapters else ""))
    assert set(peaks) == expected
    assert all(p > 0 for p in peaks.values())


@pytest.mark.slow
def test_single_chip_int8_menu_compiles_for_v5e_with_flash_selected():
    """The configuration chip_smoke.py serves (int8 weights + int8 KV +
    speculative, 16 slots x 2048) on ONE v5e topology device: no mesh, so
    `auto` resolves both attention kernels to flash and the int8 matmuls
    to the Pallas dequant kernel — and Mosaic must accept all of them.
    At the parent of r21 lowering stopped at the int8 scale blocks."""
    _require_v5e("v5e:2x2")
    report = aot_serving_report(
        "v5e:2x2", tensor=1, quantize="int8", kv_quantize="int8",
        speculative=4, n_slots=16, max_len=2048, bucket=512, width=4,
        model_overrides=W8B_L2)
    assert report["compiled"] and report["fits_v5e_hbm"], report
    assert report["decode_attention_impl"] == "flash"
    assert report["prefill_attention_impl"] == "flash"
    calls = report["mosaic_calls"]
    # decode/verify: flash-decode + the dequant matmuls; prefill waves
    # carry too many rows for the matmul kernel, so exactly flash-prefill
    assert calls["decode_x8"] > 1 and calls["spec_k4_x8"] > 1
    assert calls["prefill_b512_w4"] >= 1
    assert calls["cont_p512_t512"] >= 1 and calls["cont_p1536_t512"] >= 1


@pytest.mark.slow
def test_tensor4_int8_menu_compiles_for_v5e_2x2():
    """tensor=4 + quantize int8 (the pair examples/llm-inference-service
    .yaml recommends): under the GSPMD mesh no program may carry a Mosaic
    custom call — XLA cannot partition one ("Mosaic kernels cannot be
    automatically partitioned"), which is what the int8 matmul handed it
    at the parent of r21."""
    _require_v5e("v5e:2x2")
    report = aot_serving_report(
        "v5e:2x2", quantize="int8", kv_quantize="int8", speculative=4,
        n_slots=16, max_len=2048, bucket=512, width=4,
        model_overrides=W8B_L2)
    assert report["compiled"] and report["fits_v5e_hbm"], report
    assert report["tensor_parallel"] == 4
    assert report["decode_attention_impl"] == "xla"
    assert report["prefill_attention_impl"] == "xla"
    assert set(report["mosaic_calls"].values()) == {0}

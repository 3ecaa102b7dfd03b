"""The stacked int8 matmul's roofline reader on signature lines recorded
from a traced chip run of serve_chat_open (as lib/tracered.short_name
writes them): one layer's bytes, None where the kernel did not run, and
the two-dimensional kernel's reader skipping the stacked calls."""

import importlib

import pytest

import conftest  # noqa: F401  (puts benchmark/ on sys.path)
from lib import tracered
from opcount import quant_matmul

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
W_DOWN = ("_dequant_matmul_stacked.73(s32[1],bf16[16,14336],"
          "s8[8,14336,4096],f32[8,1,4096])->bf16[16,4096]")
W_K = ("_dequant_matmul_stacked.79(s32[1],bf16[16,4096],"
       "s8[8,4096,1024],f32[8,1,1024])->bf16[16,1024]")
LM_HEAD = ("_dequant_matmul_2d.7(bf16[16,4096],s8[4096,32768],"
           "f32[1,32768])->f32[16,32768]")


def reader(name):
    return importlib.import_module(f"metrics.{name}")


def test_short_name_writes_the_signature_the_reader_parses():
    hlo = ("%_dequant_matmul_stacked.79 = bf16[16,1024]{1,0:T(8,128)(2,1)"
           "S(1)} custom-call(s32[1]{0:T(128)S(1)} %dynamic_slice.82, "
           "bf16[16,4096]{1,0:T(8,128)(2,1)} %fusion.188, "
           "s8[8,4096,1024]{2,1,0:T(8,128)(4,1)} %get-tuple-element.2161, "
           "f32[8,1,1024]{2,1,0:T(1,128)} %get-tuple-element.2175), "
           'custom_call_target="tpu_custom_call", operand_layout_constraints'
           "={s32[1]{0}, bf16[16,4096]{1,0}, s8[8,4096,1024]{2,1,0}, "
           "f32[8,1,1024]{2,1,0}}")
    assert tracered.short_name(hlo) == W_K


def test_a_call_costs_one_layer_not_the_stack():
    stacked = reader("quant_matmul_stacked_roofline")
    ops, nbytes = stacked.cost_of(W_DOWN)
    assert ops == 2 * 16 * 14336 * 4096
    # x once, ONE layer's int8 weights, its scales, the result once
    assert nbytes == 2 * 16 * 14336 + 14336 * 4096 + 4 * 4096 + 2 * 16 * 4096
    assert nbytes < 1.02 * 14336 * 4096       # not 8 x: the stack is 470 MB
    assert stacked.cost_of(W_DOWN) == quant_matmul.cost(16, 14336, 4096)
    # shapes that do not agree with each other are not this kernel's
    assert stacked.cost_of(W_DOWN.replace("f32[8,1,4096]",
                                          "f32[8,1,1024]")) is None
    assert stacked.cost_of(LM_HEAD) is None


def test_share_is_least_time_over_device_time():
    stacked = reader("quant_matmul_stacked_roofline")
    least = sum(n * quant_matmul.least_seconds(*stacked.cost_of(sig), PEAKS)
                for sig, n in ((W_DOWN, 600), (W_K, 1200)))
    run = {"peaks": PEAKS, "trace": {"ops": [
        [W_DOWN, 600 * 75e-6, 600], [W_K, 1200 * 5.5e-6, 1200],
        [LM_HEAD, 0.1, 600], ["dynamic-slice_bitcast_fusion.6", 0.2, 4800]]}}
    assert stacked.read(run) == pytest.approx(
        100.0 * least / (600 * 75e-6 + 1200 * 5.5e-6))
    assert 90.0 < stacked.read(run) < 100.0   # bytes over 819 GB/s bind


def test_none_without_the_kernel_and_never_a_raise():
    stacked = reader("quant_matmul_stacked_roofline")
    assert stacked.read({"peaks": PEAKS, "trace": None}) is None
    assert stacked.read({"peaks": PEAKS}) is None
    assert stacked.read({"peaks": PEAKS, "trace": {"ops": [
        [LM_HEAD, 0.1, 600], ["fusion.3", 0.2, 10]]}}) is None


def test_the_two_dimensional_reader_skips_the_stacked_calls():
    assert quant_matmul.cost_of(W_DOWN) is None
    assert quant_matmul.cost_of(W_K) is None
    flat = reader("quant_matmul_roofline")
    both = {"peaks": PEAKS, "trace": {"ops": [
        [W_DOWN, 600 * 75e-6, 600], [LM_HEAD, 600 * 170e-6, 600]]}}
    head_only = {"peaks": PEAKS, "trace": {"ops": [
        [LM_HEAD, 600 * 170e-6, 600]]}}
    assert flat.read(both) == flat.read(head_only)
    assert 90.0 < flat.read(both) < 100.0

"""The Nemotron-3-Super served cell's files, as far as they compile nothing:
the configuration against the published config (the catalog row), the
traffic as the cell's design gives it, the operation counts against hand
arithmetic,
and each new reader on a record shaped as the driver's (a share under 100,
and None where there is nothing to read). `test_cpu_rehearsal` runs the
cell at the toy's size on the CPU (a minute or two a case: by hand, as the
other heavy tests): the sound program is `correct` and prints the metric a
CPU run can read; each fault planted under the program makes `correct`
false."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT
from lib import spec
from opcount import nemotron_step, ssd

CELL = "serve_nemotron3_super_chat_open"
CFG = json.load(open(os.path.join(
    BENCH, "configs", "nemotron-3-super-120b-a12b-serve-ep4.json")))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

#: nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 config.json, as the
#: catalog row holds it
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*"
                               "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*"
                               "EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
CUT = {"num_hidden_layers": 11, "hybrid_override_pattern": "EMEMEMEMEM*",
       "n_routed_experts": 128, "vocab_size": 32768}


def test_configuration_holds_every_published_key_but_the_cut():
    for key, value in PUBLISHED.items():
        assert CFG[key] == CUT.get(key, value), key
    assert CFG["published"] == {k: PUBLISHED[k] for k in CUT}
    assert set(CFG["reduced_why"]) == set(CUT)
    # the cut is one whole period of the published pattern
    assert PUBLISHED["hybrid_override_pattern"][26:37] == CUT[
        "hybrid_override_pattern"]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "nemotron-3-super-120b-a12b-serve-ep4")
    assert sorted(entry["reduced"]) == sorted(CUT)
    assert entry["source"] == CFG["source_url"]
    assert {"attention", "router", "latent", "shared_expert", "mamba",
            "weights"} <= set(CFG["assumed"])
    assert CFG["system"]["modelFormat"] == "nemotron_h" == CFG["family"]
    assert CFG["system"]["model_overrides"]["n_router_experts"] == 512
    # the family keeps the state in the model dtype: the one the precision
    # rule decided
    assert "dtype" not in CFG["system"]["model_overrides"]
    assert CFG["precision"]["ssm_state"] == CFG["precision"]["compute"] \
        == "bfloat16"
    assert CFG["precision"]["kv"] == "bfloat16"


def test_parameters_by_hand():
    """4.648 B parameters on this chip, 120.67 B in the published model."""
    d, h, p, g, n = 4096, 128, 64, 8, 128
    conv = h * p + 2 * g * n
    mamba = (d * (h * p + conv + h) + 4 * conv + conv + 3 * h + h * p
             + h * p * d + d)
    attn = d * 4096 + 2 * d * 256 + 4096 * d + d
    outside = d * 512 + 512 + 2 * d * 1024 + 2 * d * 5376 + d
    expert = 2 * 1024 * 2688
    here = 5 * mamba + attn + 5 * (outside + 128 * expert) + 2 * 32768 * d + d
    assert round(mamba / 1e6, 2) == 109.64
    assert round(outside / 1e6, 2) == 54.53
    assert round(here / 1e9, 3) == 4.648
    whole = 40 * mamba + 8 * attn + 40 * (outside + 512 * expert) \
        + 2 * 131072 * d
    assert round(whole / 1e9, 2) == 120.67


def test_cell_and_traffic_as_designed():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.driver_name == "http_open_loop_state"
    mix = cell.traffic
    assert mix["prompt_tokens"]["bins"] == [[32, 127, 0.35], [128, 511, 0.45],
                                            [512, 1024, 0.2]]
    assert mix["output_tokens"]["bounded_pareto"] == {"lo": 32, "hi": 256,
                                                      "shape": 1.5}
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["counters_zero"] == ["moe_rows_dropped"]
    assert mix["sharing"] is None
    # the longest sample fits the reference's pad: prompt + answer
    assert mix["verify_pad_tokens"] >= 1024 + 256
    assert [m["name"] for m in cell.end_to_end] == ["serve_out_tokens_per_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "ssd_prefill_roofline", "ssm_state_decode_roofline",
        "nemotron_ssm_device_share", "nemotron_serve_mfu"}


def test_token_and_prefill_operations_by_hand():
    d = 4096
    mats = 2.0 * (5 * (d * 18560 + 8192 * d) + (2 * d * 4096 + 2 * d * 256)
                  + 5 * (d * 512 + 2 * d * 1024 + 2 * d * 5376
                         + 2 * 1024 * 2688 * 22 * 128 / 512))
    assert nemotron_step.matmul_flops(CFG) == pytest.approx(mats)
    head = 2.0 * d * 32768
    step = 4.0 * 128 * 64 * 128 * 5
    assert nemotron_step.token_flops(CFG, 99) == pytest.approx(
        head + mats + step + 4.0 * 32 * 128 * 100)
    scan = 2.0 * (8 * 64.5 * 128 + 128 * 64.5 * 64 + 2 * 128 * 64 * 128)
    assert ssd.scan_token_cost(CFG) == (scan, 4.0 * 8192 + 4.0 * 1024
                                        + 512.0)
    assert nemotron_step.prefill_flops(CFG, 300) == pytest.approx(
        head + 300 * (mats + 5 * scan) + 4.0 * 32 * 128 * 300 * 301 / 2)


SCAN_NAME = ("ssd_chunk_scan.3(bf16[4,1024,8192],bf16[4,1024,1024],"
             "bf16[4,1024,1024],f32[4,8,1024,16],f32[4,8,1024,16],"
             "f32[4,8,16,1024],f32[4,8,16,1024],f32[4,128,64,128])"
             "->bf16[4,1024,8192],f32[4,128,64,128]")
STEP_NAME = ("ssm_state_update.7(bf16[96,8,64,16],bf16[96,8,1,128],"
             "bf16[96,8,1,128],f32[96,8,1,16],f32[96,8,1,16],"
             "f32[5,96,128,64,128])->f32[96,8,64,16],f32[5,96,128,64,128]")


def test_kernels_told_by_their_names():
    assert ssd.SCAN.match(SCAN_NAME) and not ssd.step_call(SCAN_NAME)
    assert ssd.step_call(STEP_NAME) == 4
    assert ssd.step_call(STEP_NAME.replace("f32[5,", "bf16[5,")) == 2
    assert not ssd.SCAN.match(STEP_NAME)
    assert not ssd.SCAN.match("fusion.3")
    ops, nbytes = ssd.step_row_cost(CFG, 4)
    assert nbytes == (2 * 4 * 128 * 64 * 128 + 2 * 8192 + 4 * 1024 + 512
                      + 4 * 8192)
    assert ops == 5.0 * 128 * 64 * 128


# -- the readers, on a record shaped as the driver's --------------------------

def record(trace=True, counters=True, scopes=True):
    ops = [[SCAN_NAME, 0.30, 20], [STEP_NAME, 3.0, 4000],
           ["fusion.3", 1.0, 5000]]
    run = {"config": CFG, "peaks": PEAKS,
           "window": {"t_open": 100.0, "seconds": 30.0},
           "requests": [{"prompt": [1] * 300, "done": 125.0,
                         "token_at": [100.5 + 0.02 * j for j in range(60)],
                         "token_ids": [2] * 60, "usage": None}],
           "counters": {"before": {}, "after": {}}, "trace": None}
    if counters:
        # the counts at the open, then at two finishes either side of the
        # traced 10 s: 1e5 scan tokens and 2e4 state rows a second there
        run["counters"]["before"].update(ssm_scan_tokens=1e4,
                                         ssm_state_rows=1e3)
        for done, tokens, rows in ((105.0, 5.1e5, 1.01e5),
                                   (112.0, 12.1e5, 2.41e5)):
            run["requests"].append({
                "prompt": [1] * 200, "done": done, "token_at": [],
                "token_ids": [], "usage": {"counters": {
                    "ssm_scan_tokens": tokens, "ssm_state_rows": rows}}})
    if trace:
        run["trace"] = {"ops": ops, "busy_s": 9.0, "window_s": 10.0}
        if scopes:
            run["trace"]["scopes"] = {
                "ssm_proj": 1.5, "ssm_conv": 0.1, "ssm_scan": 0.3,
                "ssm_state": 3.0, "moe_latent": 0.3, "moe_route": 0.2,
                "moe_experts": 2.0, "moe_shared": 0.6, "attn_full": 0.3,
                "lm_head": 0.4, "other": 0.3}
    return run


NEW = ("ssd_prefill_roofline", "ssm_state_decode_roofline",
       "nemotron_ssm_device_share", "nemotron_serve_mfu")


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_a_share_under_100(name):
    value = spec.metric_reader(name)(record())
    assert value is not None and 0 < value < 100


def test_readers_by_hand():
    run = record()
    assert spec.metric_reader("nemotron_ssm_device_share")(run) \
        == pytest.approx(100 * (1.5 + 0.1 + 0.3 + 3.0) / 9.0)
    # 1e6 scan tokens in the traced 10 s
    ops, nbytes = ssd.scan_token_cost(CFG)
    least = 1e6 * max(ops / 197e12, nbytes / 819e9)
    assert spec.metric_reader("ssd_prefill_roofline")(run) \
        == pytest.approx(100 * least / 0.30)
    # 2e5 state rows in the traced 10 s
    ops, nbytes = ssd.step_row_cost(CFG, 4)
    least = 2e5 * max(ops / 197e12, nbytes / 819e9)
    assert spec.metric_reader("ssm_state_decode_roofline")(run) \
        == pytest.approx(100 * least / 3.0)
    flops = nemotron_step.prefill_flops(CFG, 300) + sum(
        nemotron_step.token_flops(CFG, 300 + j - 1) for j in range(1, 60))
    assert spec.metric_reader("nemotron_serve_mfu")(run) \
        == pytest.approx(100 * flops / (30.0 * 197e12))


@pytest.mark.parametrize("name,run", [
    ("ssd_prefill_roofline", record(trace=False)),
    ("ssd_prefill_roofline", record(counters=False)),
    ("ssd_prefill_roofline", dict(record(), config={"family": "mistral"})),
    ("ssm_state_decode_roofline", record(trace=False)),
    ("ssm_state_decode_roofline", record(counters=False)),
    # no request finished after the traced part: its end is not bracketed
    ("ssm_state_decode_roofline", dict(record(), requests=[
        r for r in record()["requests"] if r["done"] < 110.0])),
    ("nemotron_ssm_device_share", record(scopes=False)),
    ("nemotron_ssm_device_share", record(trace=False)),
    ("nemotron_serve_mfu", dict(record(), config={"family": "laguna"})),
], ids=lambda v: v if isinstance(v, str) else "")
def test_reader_finds_nothing_and_does_not_raise(name, run):
    """The parent commit (no counters, no scopes), an untraced run, another
    family: nothing to read is None, never 0 and never a raise."""
    assert spec.metric_reader(name)(run) is None


class _Logits:
    """A reference whose hidden state IS the logits: hidden(...) gives
    fixed rows for the sound reference and `lower`'s own for a control."""
    rows = {None: [[1.0, 0.9, 0.0, 0.0], [2.0, 1.7, 0.0, 0.0],
                   [0.0, 0.5, 0.0, 0.0], [3.0, 2.85, 0.0, 0.0],
                   [0.0, 0.0, 0.0, 0.0]],
            "low": [[1.0, 0.0, 0.0, 0.0], [0.0, 2.0, 0.0, 0.0],
                    [0.0, 0.0, 0.0, 0.0], [0.0, 0.0, 0.0, 9.0],
                    [0.0, 0.0, 0.0, 0.0]]}

    def hidden(self, seed, toks, cfg, lower=None, fault=None):
        import jax.numpy as jnp
        return jnp.asarray([self.rows[lower]] * toks.shape[0])

    def ends(self, seed, cfg):
        return {}

    def head(self, ends, h, cfg, lower=None):
        return h


def test_off_best_counts_only_gaps_over_the_margin():
    """The state driver's share: a served token is off the best where its
    reference logit lies more than `margin` under the best (gaps 0.1, 0.3,
    0.5, 0.15 here: two of four over 0.2), the widest gap as the family
    driver reads it; a control is judged by its own first choices."""
    from drivers import http_open_loop_family as family
    from drivers import http_open_loop_state as state

    sample = {"prompt": [0], "tokens": [1, 1, 0, 1]}
    sound = family.reference_of
    family.reference_of = lambda cfg: _Logits()
    try:
        got = state.served_gaps({}, 1, [sample], pad_to=5, margin=0.2)
        low = state.served_gaps({}, 1, [sample], lower="low", pad_to=5,
                                margin=0.2)
    finally:
        family.reference_of = sound
    assert got["per_request_gaps"] == [[0.1, 0.3, 0.5, 0.15]]
    assert got["off_best_share"] == 50.0 and got["tokens_judged"] == 4
    assert got["widest_gap"] == pytest.approx(0.5)
    # the control's choices 0, 1, 0 (a tie: the first), 3: gaps 0, 0.3,
    # 0.5 and 3.0
    assert low["per_request_gaps"] == [[0.0, 0.3, 0.5, 3.0]]
    assert low["off_best_share"] == 75.0


@pytest.mark.parametrize("fault", [None, "pad_advances_state",
                                   "state_not_reset"])
def test_cpu_rehearsal(fault):
    """The cell's files at the toy's size on the CPU: the sound program is
    `correct` and reads `nemotron_serve_mfu` (the three device-trace
    metrics need a device plane, which the CPU has not); a fault planted
    under the program makes `correct` false."""
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    if fault:
        e["BENCH_FAMILY_FAULT"] = fault
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 41), "--seconds", "4", "--trace",
         "0" if fault else "1", "--no-chip", "--toy",
         os.path.join(HERE, "toy_nemotron.json")],
        capture_output=True, text=True, cwd=ROOT, env=e, timeout=1500)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    if fault:
        assert out["correct"] is False
        assert out["numbers"]["served_logit_gap_max"]["value"] > out[
            "numbers"]["served_logit_gap_max"]["limit"]
    else:
        assert out["correct"] is True
        assert isinstance(out["metrics"]["nemotron_serve_mfu"]["value"],
                          float)

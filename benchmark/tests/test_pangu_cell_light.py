"""The openPangu-Ultra-MoE served cell's files, as far as they compile
nothing: the configuration against the published config (the catalog row),
the traffic's laws, the operation counts against hand arithmetic (ISSUE
38's sums), and each new reader on a record shaped as the driver's (a share
under 100, and None where there is nothing to read)."""

import json
import os

import pytest

from conftest import BENCH, ROOT
from lib import spec, traffic
from opcount import mla_serve, pangu_step

CELL = "serve_pangu_ultra_moe_long_open"
CFG = json.load(open(os.path.join(
    BENCH, "configs", "openpangu-ultra-moe-718b-serve-ep32.json")))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

#: FreedomIntelligence/openPangu-Ultra-MoE-718B config.json, as the catalog
#: row holds it
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7680, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}
CUT = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
       "n_routed_experts": 8, "vocab_size": 19200}


def test_configuration_holds_every_published_key_but_the_cut():
    for key, value in PUBLISHED.items():
        assert CFG[key] == CUT.get(key, value), key
    assert CFG["published"] == {k: PUBLISHED[k] for k in CUT}
    assert set(CFG["reduced_why"]) == set(CUT)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "openpangu-ultra-moe-718b-serve-ep32")
    assert sorted(entry["reduced"]) == sorted(CUT)
    assert entry["source"] == CFG["source_url"]
    assert {"router", "post_norm_gain", "rope", "softmax_scale"} <= set(
        CFG["assumed"])
    assert CFG["system"]["modelFormat"] == "pangu_ultra_moe" == CFG["family"]
    assert CFG["system"]["model_overrides"] == {"n_router_experts": 256}
    assert CFG["precision"]["kv"] == "bfloat16"
    assert abs(CFG["post_norm_gain"] - 61 ** -0.5) < 1e-12


def test_cell_and_traffic_as_the_issue_gives_them():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.driver_name == "http_open_loop_latent"
    mix = cell.traffic
    assert mix["prompt_tokens"] == {"bins": [[1024, 4095, 0.5],
                                             [4096, 8192, 0.5]]}
    assert mix["output_tokens"] == {"bounded_pareto": {
        "lo": 256, "hi": 3072, "shape": 1.2}}
    assert mix["sharing"] is None and mix["counters_zero"] == [
        "moe_rows_dropped"]
    assert mix["verify_requests"] == 6 and mix["verify_pad_tokens"] == 11264
    sys_cfg = CFG["system"]["config"]
    assert (sys_cfg["n_slots"], sys_cfg["max_len"], sys_cfg["buckets"],
            sys_cfg["decode_chunk"]) == (64, 11264, [512, 1024], 8)
    assert sys_cfg["prefix_cache"] is False and sys_cfg["usage_timing"]
    # the chain compiles at load: compiled lazily, the warm-up prompts'
    # chains outlast the harness's 60 s drain (my chip run, PR 38)
    assert sys_cfg["warm_chain"] is True
    # the longest prompt and the longest answer fit a slot
    assert 8192 + 3072 == sys_cfg["max_len"]
    names = {m["name"] for m in cell.per_layer}
    assert {"mla_latent_decode_roofline", "mla_prefill_roofline",
            "pangu_mla_device_share", "pangu_serve_mfu"} <= names
    assert not names & {"token_gap_p95_ms",
                        "moe_serve_grouped_matmul_roofline",
                        "moe_decode_experts_touched_share",
                        "serve_model_mfu", "flash_decode_roofline"}
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_out_tokens_per_s", "setup_s"}


def test_warmup_meets_every_continuation_pair_the_mix_can():
    """The chain of an n-token prompt is 1024-row chunks, then a tail in
    the smallest bucket that holds it (serving/llm.py::_chunk_plan): the
    warm-up prompts meet every (prefix, tail bucket) pair of 1024-8192."""
    def pairs(n):
        out, done = set(), 1024
        while n - done > 1024:
            out.add((done, 1024))
            done += 1024
        if n > 1024:
            out.add((done, 512 if n - done <= 512 else 1024))
        return out

    mix = spec.Cell(CELL).traffic
    want = set().union(*(pairs(n) for n in range(1024, 8193)))
    got = set().union(*(pairs(n) for n in mix["warmup_prompt_tokens"]))
    assert want == got
    assert max(mix["warmup_prompt_tokens"]) <= 8192


def test_the_laws_means():
    """ISSUE 38: prompts of mean about 4,350, answers of mean about 634."""
    mix = spec.Cell(CELL).traffic
    n = 4000
    prompts = [traffic.quantile(mix["prompt_tokens"], (i + 0.5) / n)
               for i in range(n)]
    outs = [traffic.quantile(mix["output_tokens"], (i + 0.5) / n)
            for i in range(n)]
    assert 4300 < sum(prompts) / n < 4400
    assert 600 < sum(outs) / n < 670
    assert min(prompts) >= 1024 and max(prompts) <= 8192
    assert min(outs) >= 256 and max(outs) <= 3072


def test_the_schedule_opens_no_long_answer_early_at_the_close():
    """`order_seed` 139: of the one schedule every seed runs, the answers
    of 1,000 tokens or more are due in the window's first 9 s (they finish
    inside it) or its last 2 s (they hold little of it at the close)."""
    mix = spec.Cell(CELL).traffic
    reqs = traffic.make_requests(mix, 3000003841, 30.0, 19200)
    long_due = [r.due_s for r in reqs if r.max_tokens >= 1000]
    assert len(reqs) == 36 and len(long_due) == 5
    assert all(d < 9.0 or d > 28.0 for d in long_due)


def test_the_schedule_is_steadier_than_order_seed_0():
    """In the engine simulation fitted to the chip (schedule_sim.py), the
    tokens counted move with the run's speed under 139 at under half the
    rate they do under 0, the schedule the driver found too noisy."""
    import schedule_sim

    mix = spec.Cell(CELL).traffic
    el = {s: schedule_sim.elasticity(schedule_sim.schedule(mix, s, 30.0),
                                     30.0)
          for s in (0, mix["order_seed"])}
    assert mix["order_seed"] == 139 and el[139] < 0.5 * el[0]


def test_parameters_by_hand():
    """ISSUE 38: attention 196.6 M a layer, the dense layer 621.2 M (ISSUE
    38 adds the rounded parts: 621.3), an expert layer with 8 held
    623.2 M; with the vocabulary's eighth 3.41 B."""
    attn = pangu_step.attention_params(CFG)
    assert round(attn / 1e6, 1) == 196.6
    d = 7680
    dense = attn + 3 * d * 18432
    expert = 3 * d * 2048
    layer = attn + 8 * expert + expert + d * 256
    assert (round(dense / 1e6, 1), round(layer / 1e6, 1)) == (621.2, 623.2)
    assert round((dense + 4 * layer + 2 * 19200 * d) / 1e9, 2) == 3.41
    # a token's matmuls: the rank's quarter of an expert, not its eight
    assert pangu_step.layer_matmul_flops(CFG, dense=False) == 2.0 * (
        attn + d * 256 + expert + 8 * 8 / 256 * expert)
    assert pangu_step.layer_matmul_flops(CFG, dense=True) == 2.0 * dense


def test_token_and_prefill_operations_by_hand():
    mats = (pangu_step.layer_matmul_flops(CFG, True)
            + 4 * pangu_step.layer_matmul_flops(CFG, False))
    head = 2.0 * 7680 * 19200
    per_pair = 2.0 * 128 * (192 + 128) * 5
    assert pangu_step.token_flops(CFG, 4999) == head + mats + per_pair * 5000
    n = 4350
    got = pangu_step.prefill_flops(CFG, n)
    assert got == head + n * mats + per_pair * n * (n + 1) / 2
    # ISSUE 38's "prefill of about 18 TFLOP a request": 14.4 of matmuls
    # (the rank's quarter of an expert a token) and 3.9 of attention pairs
    assert 14e12 < n * mats < 15e12 and 17.5e12 < got < 19e12


def test_decode_kernel_call_by_hand():
    name = ("closed_call.31(s32[193],bf16[64,128,640],"
            "bf16[5,64,11264,640])->bf16[64,128,512]")
    assert mla_serve.decode_call(name) == (64, 128, 640, 512)
    # a GQA flash decode is not it, nor a query narrower than the rows
    assert mla_serve.decode_call(name.replace("bf16[64,128,640]",
                                              "bf16[64,128,512]")) is None
    assert mla_serve.decode_call("fusion.3") is None
    ops, nbytes = mla_serve.decode_cost(128, 576, 512, 5000, 64)
    assert ops == 2 * 128 * 1088 * 5000
    assert nbytes == 2 * (576 * 5000 + 64 * 128 * 1088)
    # ISSUE 38: 242 flop a byte of the rows, on the v5e's ridge (240)
    assert round(2 * 128 * 1088 / 1152) == 242


def test_prefill_kernel_call_by_hand():
    name = ("closed_call.5(s32[1],bf16[128,1024,256],bf16[128,8192,256],"
            "bf16[128,8192,128])->bf16[128,1024,128],f32[128,1,1,1024]")
    assert mla_serve.prefill_call(name) == (128, 1024, 8192)
    # heads of one size are another model's attention
    assert mla_serve.prefill_call(name.replace("bf16[128,8192,128]",
                                               "bf16[128,8192,256]")) is None
    ops, nbytes = mla_serve.prefill_cost(128, 1024, 8192)
    pairs = 1024 * 7168 + 1024 * 1025 / 2
    assert ops == 2 * 128 * pairs * 320
    assert nbytes == 2 * 128 * (1024 + 8192) * 320


# -- the readers, on a record shaped as the driver's --------------------------

def record(trace=True, counters=True, scopes=True):
    ops = [
        ["closed_call.31(s32[193],bf16[64,128,640],bf16[5,64,11264,640])"
         "->bf16[64,128,512]", 0.40, 1000],
        ["closed_call.5(s32[1],bf16[128,1024,256],bf16[128,8192,256],"
         "bf16[128,8192,128])->bf16[128,1024,128],f32[128,1,1,1024]",
         0.30, 10],
        ["closed_call.6(s32[1],bf16[128,1024,256],bf16[128,1024,256],"
         "bf16[128,1024,128])->bf16[128,1024,128],f32[128,1,1,1024]",
         0.05, 20],
        ["fusion.3", 1.0, 5000]]
    run = {"config": CFG, "peaks": PEAKS,
           "window": {"t_open": 100.0, "seconds": 30.0},
           "requests": [{"prompt": [1] * 4000, "done": 125.0,
                         "token_at": [100.5 + 0.02 * j for j in range(600)],
                         "token_ids": [2] * 600, "usage": None}],
           "counters": {"before": {}, "after": {}}, "trace": None}
    if counters:
        # the count at the open, then at two finishes either side of the
        # traced 10 s: 4e6 rows a second, 4e7 in the traced part
        run["counters"]["before"]["mla_context_tokens"] = 1e6
        for done, rows in ((105.0, 21e6), (112.0, 49e6)):
            run["requests"].append({
                "prompt": [1] * 2000, "done": done, "token_at": [],
                "token_ids": [], "usage": {"counters": {
                    "mla_context_tokens": rows}}})
    if trace:
        run["trace"] = {"ops": ops, "busy_s": 4.0, "window_s": 10.0}
        if scopes:
            run["trace"]["scopes"] = {
                "mla_project": 0.9, "mla_prefill": 0.35, "mla_decode": 0.4,
                "moe_experts": 0.8, "moe_route": 0.1, "moe_shared": 0.2,
                "dense_ffn": 0.3, "lm_head": 0.2, "other": 0.75}
    return run


NEW = ("mla_latent_decode_roofline", "mla_prefill_roofline",
       "pangu_mla_device_share", "pangu_serve_mfu")


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_a_share_under_100(name):
    value = spec.metric_reader(name)(record())
    assert value is not None and 0 < value < 100


def test_readers_by_hand():
    run = record()
    assert spec.metric_reader("pangu_mla_device_share")(run) \
        == pytest.approx(100 * (0.9 + 0.35 + 0.4) / 4.0)
    # 4e7 rows in the traced 10 s, over 1000 calls of 64 slots
    ops, nbytes = mla_serve.decode_cost(128, 576, 512, 4e7 / 1000, 64)
    least = 1000 * max(ops / 197e12, nbytes / 819e9)
    assert spec.metric_reader("mla_latent_decode_roofline")(run) \
        == pytest.approx(100 * least / 0.40)
    least = sum(n * max(o / 197e12, b / 819e9) for n, (o, b) in (
        (10, mla_serve.prefill_cost(128, 1024, 8192)),
        (20, mla_serve.prefill_cost(128, 1024, 1024))))
    assert spec.metric_reader("mla_prefill_roofline")(run) \
        == pytest.approx(100 * least / 0.35)
    flops = pangu_step.prefill_flops(CFG, 4000) + sum(
        pangu_step.token_flops(CFG, 4000 + j - 1) for j in range(1, 600))
    assert spec.metric_reader("pangu_serve_mfu")(run) \
        == pytest.approx(100 * flops / (30.0 * 197e12))


@pytest.mark.parametrize("name,run", [
    ("mla_latent_decode_roofline", record(trace=False)),
    ("mla_latent_decode_roofline", record(counters=False)),
    # no request finished after the traced part: its end is not bracketed
    ("mla_latent_decode_roofline", dict(record(), requests=[
        r for r in record()["requests"] if r["done"] < 110.0])),
    ("mla_prefill_roofline", record(trace=False)),
    ("mla_prefill_roofline", dict(record(), config={"family": "mistral"})),
    ("pangu_mla_device_share", record(scopes=False)),
    ("pangu_mla_device_share", record(trace=False)),
    ("pangu_serve_mfu", dict(record(), config={"family": "laguna"})),
], ids=lambda v: v if isinstance(v, str) else "")
def test_reader_finds_nothing_and_does_not_raise(name, run):
    """The parent commit (no counters, no scopes), an untraced run, another
    family: nothing to read is None, never 0 and never a raise."""
    assert spec.metric_reader(name)(run) is None

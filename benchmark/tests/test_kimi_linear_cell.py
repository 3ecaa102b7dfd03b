"""The Kimi-Linear cell's own pieces: the operation counts against hand
arithmetic at the published widths, the kernel readers on a hand-made trace
(the names as lib/tracered.short_name gives them for the step compiled for
the v5e), and the cell end to end on the CPU at toy sizes."""

import json
import os
import subprocess
import sys

import pytest

from lib import harness, spec, xscopes
from opcount import grouped_matmul, kda_chunk, kimi_linear_step, mla_attention

from conftest import BENCH, HERE, ROOT

CELL = "train_kimi_linear_ep32_s8k"
CFG = json.load(open(os.path.join(
    BENCH, "configs", "kimi-linear-48b-a3b-train-ep32.json")))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_cut_keeps_every_published_width():
    row = next(json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Kimi-Linear-48B-A3B-Instruct"' in l) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is None:
        pytest.skip("no catalog here")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kimi-linear-48b-a3b-train-ep32")
    differs = sorted(k for k, v in row["config"].items() if CFG.get(k) != v)
    assert differs == sorted(entry["reduced"])
    assert entry["source"] == row["source_url"] == CFG["source_url"]
    lin, pub = CFG["linear_attn_config"], CFG["published"]["linear_attn_config"]
    assert {k: lin[k] for k in ("head_dim", "num_heads",
                                "short_conv_kernel_size")} == {
        k: pub[k] for k in ("head_dim", "num_heads", "short_conv_kernel_size")}


def test_layer_parameters_by_hand():
    s = kimi_linear_step.sizes(CFG)
    # KDA: four 2304 x 4096 projections, two low-rank pairs through 128,
    # the beta projection, three 4-tap convolutions over 4096 channels
    kda = (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
           + 3 * 4 * 4096)
    assert kimi_linear_step.kda_layer_macs(s) == kda == 39_510_016
    # latent: q 2304 x 32 x 192, kv_a 2304 x 576, kv_b 512 x 32 x 256, o
    latent = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    assert kimi_linear_step.latent_layer_macs(s) == latent == 29_114_368


def test_a_step_is_the_issues_2_3_gflop_a_token():
    rows = 4 * 16384 * 8 * 8 / 256        # balanced routing, 4 expert layers
    flops = kimi_linear_step.train_flops_per_step(CFG, 2, 8192, rows)
    per_token = (4 * 39_510_016 + 29_114_368 + 3 * 2304 * 9216
                 + 4 * (3 * 2304 * 1024 + 2304 * 256))
    kda_ops, _ = kda_chunk.forward_cost(64, 8192, 128, 128)
    attn = 2 * 2 * 32 * 320 * 8192 * 8193 / 2
    by_hand = 3 * (2 * 16384 * per_token + 4 * kda_ops + attn
                   + 2 * rows * 3 * 2304 * 1024
                   + 2 * 2304 * 20480 * 2 * 8191)
    assert flops == pytest.approx(by_hand, rel=1e-12)
    assert 2.2e9 < flops / 16384 < 2.4e9


def test_kda_chunk_by_hand():
    tri = 64 * 65 / 2
    macs = (2 * tri * 128 + 64 ** 3 / 3 + tri * 256 + 2 * 64 * 128 * 128
            + tri * 128 + 64 * 128 * 128)
    assert kda_chunk.chunk_flops(128, 128) == 2 * macs
    ops, nbytes = kda_chunk.forward_cost(64, 8192, 128, 128)
    assert ops == 64 * 128 * 2 * macs
    assert nbytes == 64 * 8192 * (4 * 128 * 2 + 128 * 4)


def test_mla_and_grouped_matmul_by_hand():
    ops, nbytes = mla_attention.layer_cost(64, 8192, 192, 128)
    assert ops == 2 * 64 * 8192 * 8193 / 2 * (4 * 192 + 3 * 128)
    assert nbytes == 64 * 8192 * 4 * 320 * 2
    ops, nbytes = grouped_matmul.step_cost(16384, 4, 8, 2304, 1024)
    assert ops == 9 * 2 * 16384 * 2304 * 1024
    assert nbytes == 3 * 4 * 8 * 3 * 2304 * 1024 * 2 + 9 * 16384 * 3328 * 2


# one traced step of 1.0 s, as the names come out of the compiled step
ROWS = "bf16[64,8192,128]"
SQ = "f32[64,128,64,64]"
META = "s32[],s32[10],s32[72],s32[72],s32[1]"
TRACE = {"busy_s": 1.0, "window_s": 1.0, "ops": [
    [f"kda.8({ROWS},{ROWS},f32[64,8192,128])->{SQ},{SQ}", 0.016, 4],
    [f"jvp_kda_.8({ROWS},{ROWS},f32[64,8192,128])->{SQ},{SQ}", 0.016, 4],
    [f"kda.9({ROWS},{ROWS},{ROWS},f32[64,8192,128],{SQ},{SQ})->{ROWS},"
     "f32[64,128,128,128]", 0.040, 4],
    [f"jvp_kda_.9({ROWS},{ROWS},{ROWS},f32[64,8192,128],{SQ},{SQ})->{ROWS},"
     "f32[64,128,128,128]", 0.040, 4],
    ["mla.3(s32[1],bf16[64,8192,256],bf16[64,8192,256],bf16[64,8192,128])->"
     "bf16[64,8192,128],f32[64,16,1,512]", 0.020, 2],
    ["mla.5(bf16[64,8192,256],bf16[64,8192,256],bf16[64,8192,128],"
     "bf16[64,8192,128],f32[64,16,1,512],f32[64,16,1,512])->"
     "bf16[64,8192,256],bf16[64,8192,128]", 0.030, 1],
    ["mla.4(bf16[64,8192,256],bf16[64,8192,256],bf16[64,8192,128],"
     "bf16[64,8192,128],f32[64,16,1,512],f32[64,16,1,512])->"
     "bf16[64,8192,256]", 0.020, 1],
    [f"gmm.420({META},bf16[16384,2304],bf16[8,2304,1024])->bf16[16384,1024]",
     0.008, 24],
    [f"gmm.425({META},bf16[16384,1024],bf16[8,1024,2304])->bf16[16384,2304]",
     0.004, 12],
    [f"tgmm.150({META},bf16[16384,2304],bf16[16384,1024])->bf16[8,2304,1024]",
     0.006, 12],
    ["fusion.77", 0.5, 100],
    # another model's attention (q and v heads of one size) is not this one's
    ["shard_map.407(s32[1],bf16[32,2048,128],bf16[32,2048,128],"
     "bf16[32,2048,128])->bf16[32,2048,128],f32[32,8,1,256]", 0.1, 8]]}


SCOPES = {"kda": 5.4, "kda_backward": 3.0, "kda_solve": 0.6, "other": 1.0}


def run_of(trace):
    steps = [{"step_time_s": 1.0, "moe_rows_here": 16400.0,
              "moe_expert_load_max_over_mean": x} for x in (1.10, 1.14, 1.2)]
    return {"trace": trace, "steps": steps, "config": CFG, "peaks": PEAKS,
            "traffic": {"batch_size": 2, "seq_len": 8192}, "chips": 1,
            "window": {"span_s": 3.0}}


def test_the_readers_on_a_hand_made_trace():
    run = run_of(TRACE)
    read = lambda name: spec.metric_reader(name)(run)
    # KDA: 8 runs of the walking kernel = 4 layer-steps under remat
    ops, nbytes = kda_chunk.forward_cost(64, 8192, 128, 128)
    least = 4 * max(ops / 197e12, nbytes / 819e9)
    assert read("kda_chunk_roofline") == pytest.approx(
        100 * least / (0.016 * 2 + 0.040 * 2))
    ops, nbytes = mla_attention.layer_cost(64, 8192, 192, 128)
    assert read("mla_attention_roofline") == pytest.approx(
        100 * max(ops / 197e12, nbytes / 819e9) / 0.070)
    # 12 weight-gradient calls = 3 projections x 4 layers: one step
    ops, nbytes = grouped_matmul.step_cost(16400.0, 4, 8, 2304, 1024)
    assert read("grouped_matmul_roofline") == pytest.approx(
        100 * max(ops / 197e12, nbytes / 819e9) / 0.018)
    assert read("kimi_mechanism_device_share") == pytest.approx(
        100 * (0.112 + 0.070 + 0.018))
    assert read("moe_expert_load_max_over_mean") == 1.14
    flops = 3 * kimi_linear_step.train_flops_per_step(CFG, 2, 8192, 16400.0)
    assert read("kimi_linear_train_mfu") == pytest.approx(
        100 * flops / (3.0 * 197e12))
    for name in ("kda_chunk_roofline", "mla_attention_roofline",
                 "grouped_matmul_roofline", "kimi_mechanism_device_share"):
        assert 0 < read(name) <= 100
    # a scope's share: its seconds over the busy seconds
    run["trace"] = dict(TRACE, busy_s=10.0, scopes=SCOPES)
    assert read("kda_backward_device_share") == pytest.approx(30.0)
    assert read("kda_solve_device_share") == pytest.approx(6.0)


def test_a_reader_that_finds_nothing_returns_none_never_zero():
    """What the parent commit gives: a trace without these kernels, records
    without these counters."""
    run = run_of({"busy_s": 1.0, "window_s": 1.0,
                  "ops": [["fusion.77", 0.5, 100]]})
    run["steps"] = [{"step_time_s": 1.0}]
    for name in ("kda_chunk_roofline", "mla_attention_roofline",
                 "grouped_matmul_roofline", "kimi_mechanism_device_share",
                 "moe_expert_load_max_over_mean", "kimi_linear_train_mfu",
                 "kda_backward_device_share", "kda_solve_device_share"):
        assert spec.metric_reader(name)(run) is None
    # the CPU rehearsal's capture has no device plane: no scopes
    run["trace"]["scopes"] = None
    assert spec.metric_reader("kda_solve_device_share")(run) is None
    run["trace"] = None
    assert spec.metric_reader("kda_chunk_roofline")(run) is None


def _varint(n):
    out = b""
    while n >= 0x80:
        out, n = out + bytes([n & 0x7F | 0x80]), n >> 7
    return out + bytes([n])


def _msg(*fields):
    """protobuf wire format: (number, int) a varint, (number, bytes) a
    length-delimited field."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_device_time_by_scope_from_a_hand_made_capture(tmp_path):
    """xplane.proto by its field numbers: a plane's event metadata carry
    `tf_op` as a string or as a reference to a stat's name; a `while` holds
    its body's operations and keeps its own time alone."""
    entry = lambda k, v: _msg((1, k), (2, v))
    stat_meta = [(5, entry(1, _msg((1, 1), (2, b"tf_op")))),
                 (5, entry(2, _msg((1, 2), (2, b"flops")))),
                 (5, entry(3, _msg((1, 3), (2, b"jit(f)/kda/mul:"))))]
    op = lambda i, name, *stats: (4, entry(i, _msg(
        (1, i), (2, name), *[(5, s) for s in stats])))
    tf = lambda text: _msg((1, 1), (5, text))
    metas = [
        op(1, b"%while.1", tf(b"jit(f)/transpose(jvp(kda))/kda_backward/while:")),
        op(2, b"%fusion.2", tf(
            b"jit(f)/transpose(jvp(kda))/kda_backward/while/body/kda_solve/dot:")),
        op(3, b"%fusion.3", _msg((1, 2), (3, 7)), _msg((1, 1), (7, 3))),
        op(4, b"%copy.4", _msg((1, 2), (3, 7))),
        op(5, b"%fusion.5", tf(b"jit(f)/jvp(mla)/dot_general:"))]
    event = lambda meta, off, dur: (4, _msg((1, meta), (2, off), (3, dur)))
    ps = 10 ** 9                                       # a millisecond
    ops = _msg((2, b"XLA Ops"), (3, 5),
               event(1, 0, 10 * ps), event(2, 1 * ps, 4 * ps),
               event(3, 20 * ps, 3 * ps), event(4, 30 * ps, 2 * ps),
               event(5, 40 * ps, 1 * ps))
    other_line = _msg((2, b"XLA Modules"), (3, 5), event(1, 0, 99 * ps))
    device = _msg((2, b"/device:TPU:0"), (3, ops), (3, other_line),
                  *metas, *stat_meta)
    host = _msg((2, b"/host:CPU"), (3, ops), *metas, *stat_meta)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, device)))
    got = xscopes.scope_seconds(str(path), ("kda", "kda_solve",
                                            "kda_backward"))
    assert got == pytest.approx({"kda_backward": 6e-3, "kda_solve": 4e-3,
                                 "kda": 3e-3, "unnamed": 2e-3,
                                 "other": 1e-3})
    path.write_bytes(_msg((1, host)))
    assert xscopes.scope_seconds(str(path), ("kda",)) is None


def toy_run(script, *argv, fault=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_TEST_FAULT", None)
    if fault:
        env["BENCH_TEST_FAULT"] = fault
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), "--workload", CELL,
         *argv, "--no-chip", "--toy",
         os.path.join(HERE, "toy_kimi_linear.json")],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=1500)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout.strip().splitlines()


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end_on_the_cpu_at_toy_sizes(trace):
    out = json.loads(toy_run("run.py", "--seed", "3000000007", "--seconds",
                             "3", "--trace", str(trace))[-1])
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["numbers"]["moe_rows_dropped"] == {"value": 0.0, "limit": 0}
    if trace:
        assert {"step_p50_ms", "kimi_linear_train_mfu",
                "moe_expert_load_max_over_mean"} <= set(out["metrics"])
    else:
        assert set(out["metrics"]) == {"train_tokens_per_s_per_chip",
                                       "setup_s"}


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_a_step_broken_underneath_is_not_correct(fault):
    """The program's step wrapped by tests/faults.py, through run.py: the
    harness's own check of this driver's numbers refuses it."""
    out = json.loads(toy_run("run.py", "--seed", "3000000009", "--seconds",
                             "3", "--trace", "0", fault=fault)[-1])
    assert out["correct"] is False and out["failed"] == 0


def test_controls_and_planted_faults_fail_the_harness_check():
    """prove_family.py at toy sizes: the reference in a lower precision or
    with a fault planted, in the program's place, judged by the harness's
    `judge` against the mix's limits. `no_routed` is this family's own
    fault (the routed experts' sum left out). With the toy's float32
    compute the check refuses `bf16_state` too; beside the real cell's
    bfloat16 compute it cannot (PERF.md section 6), so the configuration
    states no precision for the KDA state."""
    lines = toy_run("prove_family.py", "--seeds", "3000000008",
                    "--controls", "fp8,bf16_state",
                    "--faults", "half_batch,no_routed", "--upper-seeds", "1")
    limits = json.load(open(os.path.join(
        HERE, "toy_kimi_linear.json")))["traffic"]["limits"]
    verdict = {}
    for line in lines:
        if not line.startswith("seed "):
            continue
        row = json.loads(line[5:])
        for kind, got in row.items():
            if kind == "program":
                got = dict(got, moe_rows_dropped=row["counters"][
                    "moe_rows_dropped"])
            if isinstance(got, dict) and "grad_norm_gap_max" in got:
                verdict[kind] = harness.judge({
                    k: {"value": v, "limit": limits[k]}
                    for k, v in got.items() if k in limits})
    assert verdict == {"program": True, "lower_fp8": False,
                       "lower_bf16_state": False, "fault_half_batch": False,
                       "fault_no_routed": False}

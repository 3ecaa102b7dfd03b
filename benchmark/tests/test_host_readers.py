"""The readers of the phase clock's host-time split (benchmark/metrics/
_host.py, PR 36) on a hand-made run: present -> the arithmetic their
docstrings state; absent (a program without the keys, as the parent commit
of the PR that added them) -> None, never a raise and never 0. Each is
declared in BENCHMARK.json for cells that exist. And, HEAVY (it builds and
runs models for minutes: run by hand like test_harness.py; the tier-1
re-export in tests/test_benchmark_yardstick.py leaves it out by name): the
CPU rehearsal of one served cell and of the Kimi-Linear cell prints them
under --trace 1."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

SERVED = ("engine_host_offcpu_share", "engine_device_empty_replay_share",
          "engine_device_empty_plan_share", "engine_device_empty_bank_share",
          "stream_write_lag_max_ms")
TRAINED = ("trainer_device_empty_share", "trainer_host_phase_max_ms")
NAMES = SERVED + TRAINED


def reader(name):
    return importlib.import_module(f"metrics.{name}").read


def request(decode_ms, phases, cpu, empty, lag):
    return {"token_at": [1.0, 1.1], "token_ids": [1, 2], "usage": {
        "queue_wait_ms": 1.0, "prefill_ms": 50.0, "decode_ms": decode_ms,
        "stream_write_lag_max_ms": lag,
        "engine": {"phases": phases, "cpu_ms": cpu,
                   "device_empty_ms": sum(empty.values()),
                   "device_empty_by_phase_ms": empty, "gc_ms": 0.0}}}


RUN = {"requests": [
    # host-only wall 2 + 3 + 5 + 10 = 20, CPU 1 + 1 + 2 + 4 = 8: 60 % off
    request(400.0,
            {"sched": [2.0, 5], "prefill_pack": [3.0, 1],
             "decode_plan": [5.0, 4], "replay": [10.0, 5],
             "decode_fetch": [300.0, 4], "prefix_bank": [12.0, 1],
             "idle": [68.0, 2]},
            {"sched": 1.0, "prefill_pack": 1.0, "decode_plan": 2.0,
             "replay": 4.0, "decode_fetch": 0.5, "prefix_bank": 11.0,
             "idle": 0.1},
            {"replay": 8.0, "sched": 2.0, "prefill_pack": 2.0,
             "decode_plan": 4.0, "prefix_bank": 12.0, "idle": 20.0},
            lag=3.0),
    # host-only wall 10 (replay alone), CPU 8: 20 % off; no bank at all
    request(200.0,
            {"replay": [10.0, 3], "decode_fetch": [190.0, 3]},
            {"replay": 8.0, "decode_fetch": 0.2},
            {"replay": 10.0},
            lag=2100.0),
    # host-only wall 4 + 4 = 8, CPU 8: 0 % off; device never empty
    request(100.0,
            {"decode_plan": [4.0, 1], "replay": [4.0, 1],
             "decode_fetch": [92.0, 1]},
            {"decode_plan": 4.0, "replay": 4.0, "decode_fetch": 0.1},
            {},
            lag=1.0),
    # failed before a usage object came back
    {"token_at": [], "token_ids": [], "usage": None},
], "steps": [
    {"step": 10, "step_time_s": 1.0, "device_empty_ms": 4.0,
     "host_phase_max_ms": 3.0},
    {"step": 11, "step_time_s": 2.0, "device_empty_ms": 1700.0,
     "host_phase_max_ms": 1690.0},
    {"step": 12, "step_time_s": 1.0, "device_empty_ms": 5.0,
     "host_phase_max_ms": 2.5},
]}

EXPECTED = {
    # median of 60 %, 20 %, 0 %
    "engine_host_offcpu_share": 20.0,
    # 8/400 = 2 %, 10/200 = 5 %, 0 %
    "engine_device_empty_replay_share": 2.0,
    # (2+2+4)/400 = 2 %, 0 %, 0 %
    "engine_device_empty_plan_share": 0.0,
    # 12/400 = 3 %, 0 %, 0 %
    "engine_device_empty_bank_share": 0.0,
    # the MAXIMUM: one stalled stream of three
    "stream_write_lag_max_ms": 2100.0,
    # median of 0.4 %, 85 %, 0.5 %
    "trainer_device_empty_share": 0.5,
    "trainer_host_phase_max_ms": 1690.0,
}


@pytest.mark.parametrize("name", NAMES)
def test_reader_arithmetic(name):
    assert reader(name)(RUN) == pytest.approx(EXPECTED[name])


def test_the_split_adds_up_to_the_whole():
    """The three device-empty shares, `idle` and the rest are one request's
    `device_empty_ms`: on a run of ONE request the medians add up."""
    one = {"requests": RUN["requests"][:1]}
    e = one["requests"][0]["usage"]["engine"]
    parts = sum(reader(f"engine_device_empty_{k}_share")(one)
                for k in ("replay", "plan", "bank"))
    idle = 100.0 * e["device_empty_by_phase_ms"]["idle"] / 400.0
    assert parts + idle == pytest.approx(
        reader("engine_device_empty_share")(one))


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_without_the_keys(name):
    """What the parent commit sends: `usage.engine` with phases and the
    whole overlay but no split and no CPU, records with `step_time_s`
    alone; and a run with no record at all."""
    old = {"requests": [
        {"token_at": [1.0], "token_ids": [1, 2],
         "usage": {"queue_wait_ms": 1.0, "prefill_ms": 50.0,
                   "decode_ms": 100.0, "first_write_lag_ms": 3.0,
                   "engine": {"phases": {"replay": [4.0, 1]},
                              "device_empty_ms": 2.0}}},
        {"token_at": [], "token_ids": [], "usage": None}],
        "steps": [{"step": 3, "step_time_s": 1.3, "data_wait_s": 0.01}]}
    assert reader(name)(old) is None
    assert reader(name)({"requests": []}) is None


def test_every_reader_is_declared_for_cells_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    declared = {m["name"]: m for m in bench["per_layer"]}
    served = {"serve_chat_open", "serve_laguna_xs2_mixed_open"}
    for name in NAMES:
        m = declared[name]
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["unit"] == ("ms" if name.endswith("_ms") else "%")
        assert set(m["workloads"]) <= cells
        # each listed cell reports the end-to-end metric this one moves
        assert set(m["workloads"]) <= set(end_to_end[m["moves"]]["workloads"])
        if name in TRAINED:
            assert m["workloads"] == ["train_kimi_linear_ep32_s8k"]
            assert m["layer"] == "trainer"
        elif name == "engine_device_empty_bank_share":
            assert m["workloads"] == ["serve_chat_open"]
        else:
            assert set(m["workloads"]) == served
    # appended, in the issue's order, after everything that was there
    assert [m["name"] for m in bench["per_layer"]][-len(NAMES):] == list(NAMES)


@pytest.mark.parametrize("cell, toy, names", [
    ("serve_chat_open", "toy_serve.json", SERVED),
    ("train_kimi_linear_ep32_s8k", "toy_kimi_linear.json", TRAINED)])
def test_cpu_rehearsal_prints_the_new_metrics(cell, toy, names):
    e = dict(os.environ, JAX_PLATFORMS="cpu",
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(2**31 + 36), "--seconds", "4", "--trace", "1",
         "--no-chip", "--toy", os.path.join(HERE, toy)],
        capture_output=True, text=True, cwd=ROOT, env=e, timeout=1500)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    for name in names:
        assert isinstance(out["metrics"][name]["value"], float), name
    assert out["correct"] is True

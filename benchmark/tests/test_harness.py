"""The harness end to end on the CPU at toy sizes (--no-chip, --toy): the
result line's keys, a CPU run without the switch fails and says why, a bare
directory fails, and with the timed path broken underneath `correct` comes
out false."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT


def run(*argv, env=None, cwd=ROOT, script=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu",
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    e.pop("BENCH_TEST_FAULT", None)
    e.update(env or {})
    return subprocess.run(
        [sys.executable, script or os.path.join(BENCH, "run.py"), *argv],
        capture_output=True, text=True, cwd=cwd, env=e, timeout=900)


def toy(cell, seed, fault=None, trace=0):
    kind = "serve" if cell.startswith("serve") else "train"
    p = run("--workload", cell, "--seed", str(seed), "--seconds", "3",
            "--trace", str(trace), "--no-chip", "--toy",
            os.path.join(HERE, f"toy_{kind}.json"),
            env={"BENCH_TEST_FAULT": fault} if fault else None)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def check_line(out, metric_names):
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "numbers"
    assert set(out["metrics"]) == set(metric_names)
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] is not None
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(out["device"])
    for n in out["numbers"].values():
        assert set(n) == {"value", "limit"}


def test_serve_result_line_and_numbers_on_stderr():
    out, err = toy("serve_chat_open", 2**31 + 5)
    check_line(out, ["token_gap_p95_ms", "serve_out_tokens_per_s",
                     "setup_s"])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    tail = err.strip().splitlines()[-2:]
    assert all("compared " in l and "limit" in l for l in tail)


def test_train_result_line_traced():
    out, _ = toy("train_fsdp2tp2", 7, trace=1)
    assert out["correct"] is True
    assert {"step_p50_ms", "train_mfu"} <= set(out["metrics"])
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_train_window_ends_on_a_step_and_counts_all_its_time():
    out, err = toy("train_fsdp2tp2", 9)
    (line,) = [l for l in err.splitlines() if " steps in " in l]
    m = re.search(r"(\d+) steps in .*'span_s': ([\d.]+), 'seconds': ([\d.]+)",
                  line)
    steps, span, seconds = int(m[1]), float(m[2]), float(m[3])
    assert steps == out["attempted"] and seconds == 3.0
    assert span >= seconds          # closes with the first step at or after
    rate = out["metrics"]["train_tokens_per_s_per_chip"]["value"]
    assert rate == pytest.approx(steps * 8 * 64 / span / 4, rel=1e-6)


@pytest.mark.parametrize("cell,fault", [
    ("serve_chat_open", "altered_token"),
    ("train_fsdp2tp2", "state_unchanged"),
    ("train_fsdp2tp2", "half_batch"),
    ("train_fsdp2tp2", "no_exchange")])
def test_broken_timed_path_is_not_correct(cell, fault):
    out, _ = toy(cell, 11, fault=fault)
    assert out["correct"] is False


def test_cpu_run_fails_and_says_why():
    p = run("--workload", "serve_chat_open", "--seed", "1", "--seconds",
            "1", "--trace", "0")
    assert p.returncode != 0 and not p.stdout.strip()
    assert "no accelerator" in p.stderr


def test_bare_directory_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run("--workload", "serve_chat_open", "--seed", "1", "--seconds", "1",
            "--trace", "0", "--no-chip", cwd=str(tmp_path),
            script=str(tmp_path / "benchmark" / "run.py"),
            env={"PYTHONPATH": ""})
    assert p.returncode != 0 and not p.stdout.strip()

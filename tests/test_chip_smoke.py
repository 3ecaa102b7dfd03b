"""chip_smoke.py must fail where there is no chip: a smoke that passes on a
CPU proves nothing about the system on the accelerator. (What it does on
the chip is the chip's to show — see the r21 entry of CHANGES.md.)"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_smoke(cwd):
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, timeout=240,
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


def has_result_line(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    try:
        return bool(lines) and "ok" in json.loads(lines[-1])
    except ValueError:
        return False


def test_result_line_is_exactly_what_the_driver_reads(monkeypatch, capsys):
    """On success the LAST stdout line is {"ok", "device": {"platform",
    "kind", "count"}} and nothing else; what the phases found goes on the
    line before it."""
    sys.path.insert(0, REPO)
    import chip_smoke

    cache = {"dir": "d", "entries_start": 1, "entries_end": 2, "hits": 3}
    common = {"ok": True, "compile_s": 1.0, "cache": cache,
              "peak_bytes_in_use": [1]}
    reports = {
        "facts": dict(common, device={"platform": "tpu",
                                      "kind": "TPU v5 lite", "count": 1},
                      versions={}, round_trip_ms_median=1.0,
                      kernel_parity={}),
        "serve": dict(common, attention={}, mosaic_calls={}, requests=[]),
        "train": dict(common, batch=6, losses=[2.0, 1.0],
                      attention_bodies=["pallas"]),
    }
    monkeypatch.setattr(chip_smoke, "run_phase",
                        lambda phase, chips, deadline: reports[phase])
    assert chip_smoke.parent(1) == 0
    *_, summary, last = capsys.readouterr().out.strip().splitlines()
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert summary.startswith("summary: ")
    assert summary.endswith('"claim": null}')


def test_fails_on_cpu_and_names_the_reason(procgroup_guard):
    out = run_smoke(REPO)
    assert out.returncode != 0
    assert "no accelerator" in out.stdout and "'cpu'" in out.stdout
    assert "FAILED in facts" in out.stderr
    assert not has_result_line(out.stdout)


def test_fails_alone_in_a_directory(tmp_path, procgroup_guard):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = run_smoke(tmp_path)
    assert out.returncode != 0
    assert "No module named 'kubeflow_tpu'" in out.stdout
    assert not has_result_line(out.stdout)

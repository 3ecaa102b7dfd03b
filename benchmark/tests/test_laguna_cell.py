"""The Laguna-XS.2 served cell at the toy size, the parts that build and run
the model (by hand: `JAX_PLATFORMS=cpu python -m pytest
benchmark/tests/test_laguna_cell.py`; the parts that compile nothing are in
`test_laguna_cell_light.py`, which tier-1 runs): the program's served tokens
against the reference through the new driver's `served_gaps`; the control
and every planted fault fail the toy's limit, the sound program passes; the
fault that exists only in the program (a ring one block short) planted under
it; the CPU rehearsal of the whole cell."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT
from drivers import http_open_loop_family as drv
from lib import harness
from reference import laguna as ref

TOY = os.path.join(BENCH, "tests", "toy_laguna.json")
SEED = 23


def toy_cell():
    import argparse

    return harness.load_cell(argparse.Namespace(
        workload="serve_laguna_xs2_mixed_open", toy=TOY))


def serve(cell, prompts, max_new=16):
    """The toy engine on the prompts, as the InferenceService would build
    it: -> samples for `served_gaps`."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models import laguna
    from kubeflow_tpu.serving.llm import LLMEngine

    c = cell.config
    model = {k: c[k] for k in c["system"]["model_keys"]}
    cfg = laguna.LagunaConfig(**model, dtype=jnp.float32)
    eng = LLMEngine(laguna.init(jax.random.key(SEED), cfg), cfg,
                    n_slots=4, max_len=96, buckets=(16, 32), decode_chunk=4,
                    kv_quantize=c["system"]["config"]["kv_quantize"],
                    family=laguna)
    rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_until_idle()
    out = [{"index": i, "prompt": p, "tokens": eng.result(r)}
           for i, (p, r) in enumerate(zip(prompts, rids))]
    ring = eng.metrics()["kv_window_ring_tokens"]
    eng.close()
    return out, ring


def prompts():
    rng = np.random.default_rng(SEED)
    return [list(map(int, rng.integers(0, 128, n))) for n in (9, 30, 47, 70)]


@pytest.fixture(scope="module")
def cell():
    return toy_cell()


@pytest.fixture(scope="module")
def samples(cell):
    return serve(cell, prompts())[0]


def test_the_sound_program_passes(cell, samples):
    got = drv.served_gaps(cell.config, SEED, samples, pad_to=96)
    limit = cell.traffic["limits"]["served_logit_gap_max"]
    assert got["tokens_judged"] == 64
    assert got["widest_gap"] <= limit, got


@pytest.mark.parametrize("kind,name", [("lower", "int8")] + [
    ("fault", f) for f in sorted(ref.FAULTS)])
def test_the_control_and_every_planted_fault_fail(cell, samples, kind, name):
    got = drv.served_gaps(cell.config, SEED, samples, pad_to=96,
                          **{kind: name})
    assert got["widest_gap"] > cell.traffic["limits"][
        "served_logit_gap_max"], (name, got)


def test_a_ring_with_no_room_for_a_chunk_fails_under_the_program(
        cell, monkeypatch):
    from kubeflow_tpu.models import laguna
    from kubeflow_tpu.ops import flash_decode

    monkeypatch.setattr(flash_decode, "DEFAULT_BLOCK_KV", 8)
    sound, ring = serve(cell, prompts()[2:])
    assert ring == 40                               # window 8 + bucket 32
    ok = drv.served_gaps(cell.config, SEED, sound, pad_to=96)
    monkeypatch.setattr(laguna, "ring_rows", laguna.ring_rows)
    monkeypatch.setenv("BENCH_FAMILY_FAULT", "ring_window_only")
    assert drv.plant_program_fault(cell.config) == "ring_window_only"
    short, ring = serve(cell, prompts()[2:])
    assert ring == 8
    bad = drv.served_gaps(cell.config, SEED, short, pad_to=96)
    limit = cell.traffic["limits"]["served_logit_gap_max"]
    assert ok["widest_gap"] <= limit < bad["widest_gap"], (ok, bad)


def test_cpu_rehearsal_of_the_cell():
    """The whole cell through run.py at the toy size: Platform, router,
    HTTP, SSE, the counters and the readers that need no device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "serve_laguna_xs2_mixed_open", "--seed", "3000000007", "--seconds",
         "5", "--trace", "1", "--no-chip", "--toy", TOY],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["numbers"]) == {"served_logit_gap_max",
                                    "moe_rows_dropped", "failed_requests"}
    assert line["metrics"]["compiles_in_window.serve"]["value"] == 0
    assert 0 < line["metrics"]["moe_decode_experts_touched_share"][
        "value"] <= 100
    assert "moe_serve_grouped_matmul_roofline" not in line["metrics"]

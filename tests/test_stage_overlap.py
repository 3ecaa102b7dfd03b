"""Wavefront-overlap schedule seam (ISSUE 20, parallel/pipeline.py +
serving/multichip.py):

- collective_matmul: the all-gather-form chunked decomposition is
  BIT-exact against the monolithic matmul (row/column slicing only, no
  float-sum reassociation) for every rank, via the injectable shift —
  no shard_map needed in a single process;
- resolve_schedule: the configured value, else the sync default; an
  invalid value raises;
- StagePerf carries the schedule kind into snapshot()/pipeline_perf();
- engine level: the overlapped wavefront dispatch is byte-identical to
  the sync schedule on a virtual pp2 staging (the schedule changes WHEN
  stages block, never what they compute), and its measured bubble is
  reported under the overlapped accounting;
- a shard_map-engaging smoke runs the ppermute ring for real.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeflow_tpu.parallel import pipeline


# -- collective_matmul --------------------------------------------------------

@pytest.mark.parametrize("size,rows,k,n", [(2, 4, 8, 8), (4, 4, 8, 12),
                                           (8, 2, 16, 8)])
def test_collective_matmul_exact(size, rows, k, n):
    """Every device's chunk schedule reconstructs allgather(x) @ w
    bit-for-bit: chunk j lands at row block (idx + j) % size untouched."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((rows * size, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    ref = np.asarray(x @ w)
    for idx in range(size):
        chunks = [x[((idx + j) % size) * rows:
                    ((idx + j) % size + 1) * rows]
                  for j in range(size)]
        it = iter(chunks[1:])
        out = pipeline.collective_matmul(
            chunks[0], w, shift=lambda cur: next(it),
            axis_size=size, axis_index=idx)
        assert np.array_equal(np.asarray(out), ref), idx


def test_collective_matmul_single_device_degenerate():
    """size=1: no shift ever fires — the loop is one plain matmul."""
    x = jnp.arange(8.0).reshape(2, 4)
    w = jnp.arange(12.0).reshape(4, 3)

    def boom(cur):
        raise AssertionError("shift must not be called at size=1")

    out = pipeline.collective_matmul(x, w, shift=boom, axis_size=1,
                                     axis_index=0)
    assert np.array_equal(np.asarray(out), np.asarray(x @ w))


def test_collective_matmul_under_shard_map():
    """The production path: ppermute ring inside shard_map across the
    stage axis."""
    from jax.sharding import Mesh, PartitionSpec as P

    size = 2
    mesh = Mesh(np.array(jax.devices()[:size]), ("tp",))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)

    def body(xs, wf):
        return pipeline.collective_matmul(xs, wf, axis_name="tp")

    # every device assembles the whole gathered product; that replication
    # is by construction, not something the vma check can infer
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("tp"), P()),
                       out_specs=P(), check_vma=False)
    out = jax.jit(fn)(x, w)
    assert np.array_equal(np.asarray(out), np.asarray(x @ w))


# -- schedule seam ------------------------------------------------------------

@pytest.mark.parametrize("configured,want", [
    (None, "sync"),                  # unset: overlap stays opt-in
    ("sync", "sync"),
    ("overlapped", "overlapped"),
    ("bogus", ValueError),
    ("1", ValueError),               # the two names are the only values
])
def test_resolve_schedule_policy(configured, want):
    if want is ValueError:
        with pytest.raises(ValueError):
            pipeline.resolve_schedule(configured)
    else:
        assert pipeline.resolve_schedule(configured) == want


def test_stageperf_snapshot_reports_schedule():
    perf = pipeline.StagePerf(2)
    assert perf.snapshot()["schedule"] == "sync"
    perf.schedule = "overlapped"
    snap = perf.snapshot()
    assert snap["schedule"] == "overlapped"
    perf.reset()
    # reset clears counters, not the engine-pinned schedule kind
    assert perf.snapshot()["schedule"] == "overlapped"


# -- engine level -------------------------------------------------------------

from kubeflow_tpu.models import llama  # noqa: E402
from kubeflow_tpu.serving.llm import LLMEngine  # noqa: E402
from kubeflow_tpu.serving.multichip import StageShardedEngine  # noqa: E402

CFG = llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=4, n_heads=8,
                        n_kv_heads=4, d_ff=128, max_seq_len=64,
                        attention_impl="xla", remat=False,
                        dtype=jnp.float32)
KW = dict(n_slots=2, max_len=48, buckets=(8,), decode_chunk=4)
PROMPT = [5, 9, 2, 44, 17]


def test_overlapped_schedule_byte_parity():
    params = llama.init(jax.random.key(7), CFG)
    ref = LLMEngine(params, CFG, **KW)
    want = ref.generate(list(PROMPT), 12)
    rid = ref.submit(list(PROMPT), 8, temperature=0.9, top_k=8, seed=3)
    ref.run_until_idle()
    want_seeded = ref.result(rid)
    ref.close()
    bubbles = {}
    for sched in ("sync", "overlapped"):
        eng = StageShardedEngine(params, CFG, stage=2,
                                 stage_schedule=sched,
                                 stage_timing=True, **KW)
        try:
            assert eng.generate(list(PROMPT), 12) == want
            rid = eng.submit(list(PROMPT), 8, temperature=0.9, top_k=8,
                             seed=3)
            eng.run_until_idle()
            assert eng.result(rid) == want_seeded
            eng.release(rid)
            perf = eng.pipeline_perf()
            assert perf["schedule"] == sched
            assert perf["steps"] > 0
            bubbles[sched] = perf["bubble_frac"]
        finally:
            eng.close()
    # both accountings produce a real fraction; the overlapped one
    # measures dispatch→drain occupancy windows, which overlap
    for v in bubbles.values():
        assert 0.0 <= v <= 1.0


@pytest.mark.parametrize("configured,want", [
    (None, "sync"), ("overlapped", "overlapped")])
def test_schedule_seam_on_engine(configured, want):
    """The engine's schedule is its constructor's value, else sync, and
    the stage clock reports the one that runs."""
    params = llama.init(jax.random.key(7), CFG)
    eng = StageShardedEngine(params, CFG, stage=2,
                             stage_schedule=configured, **KW)
    try:
        assert eng.stage_schedule == want
        assert eng.pipeline_perf()["schedule"] == want
    finally:
        eng.close()

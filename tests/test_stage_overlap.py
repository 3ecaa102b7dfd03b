"""Wavefront-overlap schedule seam (ISSUE 20, parallel/pipeline.py +
serving/multichip.py) and the two overlapped tensor-parallel matmul
forms (parallel/overlap.py):

- gather_matmul: the all-gather-form chunked decomposition is
  BIT-exact against the monolithic matmul (row/column slicing only, no
  float-sum reassociation) for every rank, via the injectable shift —
  no shard_map needed in a single process;
- matmul_scatter: its twin, equal to psum_scatter(x @ w) for every
  rank (the ring's order of additions is its own, so the cases use
  values whose sums are exact in any order);
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

from kubeflow_tpu.parallel import overlap, pipeline


# -- gather_matmul ------------------------------------------------------------

@pytest.mark.parametrize("size,rows,k,n", [(2, 4, 8, 8), (4, 4, 8, 12),
                                           (8, 2, 16, 8)])
def test_gather_matmul_exact(size, rows, k, n):
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
        out = overlap.gather_matmul(
            chunks[0], w, shift=lambda cur: next(it),
            axis_size=size, axis_index=idx)
        assert np.array_equal(np.asarray(out), ref), idx


def test_gather_matmul_single_device_degenerate():
    """size=1: no shift ever fires — the loop is one plain matmul."""
    x = jnp.arange(8.0).reshape(2, 4)
    w = jnp.arange(12.0).reshape(4, 3)

    def boom(cur):
        raise AssertionError("shift must not be called at size=1")

    out = overlap.gather_matmul(x, w, shift=boom, axis_size=1,
                                     axis_index=0)
    assert np.array_equal(np.asarray(out), np.asarray(x @ w))


def test_gather_matmul_under_shard_map():
    """The production path: ppermute ring inside shard_map across the
    stage axis."""
    from jax.sharding import Mesh, PartitionSpec as P

    size = 2
    mesh = Mesh(np.array(jax.devices()[:size]), ("tp",))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)

    def body(xs, wf):
        return overlap.gather_matmul(xs, wf, axis_name="tp")

    # every device assembles the whole gathered product; that replication
    # is by construction, not something the vma check can infer
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("tp"), P()),
                       out_specs=P(), check_vma=False)
    out = jax.jit(fn)(x, w)
    assert np.array_equal(np.asarray(out), np.asarray(x @ w))


def test_gather_matmul_one_rotation_feeds_every_weight():
    """A tuple of weights rides ONE rotation of the activation (the shift
    fires size - 1 times, whatever the number of weights), along any
    axis; `blocks=True` hands the per-block products over in ring order."""
    size, rows = 4, 3
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, rows * size, 8)), jnp.float32)
    ws = tuple(jnp.asarray(rng.standard_normal((8, n)), jnp.float32)
               for n in (8, 4, 4))
    for idx in range(size):
        order = [(idx + j) % size for j in range(size)]
        chunks = [x[:, b * rows:(b + 1) * rows] for b in order]
        for blocks in (False, True):
            it, fired = iter(chunks[1:]), []
            out = overlap.gather_matmul(
                chunks[0], ws, axis=1, blocks=blocks, axis_size=size,
                axis_index=idx,
                shift=lambda cur: (fired.append(1), next(it))[1])
            assert len(fired) == size - 1
            for i, w in enumerate(ws):
                if blocks:
                    for j, c in enumerate(chunks):
                        assert np.array_equal(np.asarray(out[j][i]),
                                              np.asarray(c @ w))
                else:
                    assert np.array_equal(np.asarray(out[i]),
                                          np.asarray(x @ w))


# -- matmul_scatter -----------------------------------------------------------

def _scatter_case(size, rows, k, n, seed=0):
    """Per-rank operands of a row-parallel matmul with small whole
    numbers for values: every partial sum is exact in float32, in any
    order of additions."""
    rng = np.random.default_rng(seed)
    xs = [jnp.asarray(rng.integers(-4, 5, (rows * size, k)), jnp.float32)
          for _ in range(size)]
    ws = [jnp.asarray(rng.integers(-4, 5, (k, n)), jnp.float32)
          for _ in range(size)]
    return xs, ws


@pytest.mark.parametrize("size,rows,k,n", [(2, 4, 8, 8), (4, 4, 8, 12),
                                           (8, 2, 16, 8)])
@pytest.mark.parametrize("as_blocks", [False, True])
def test_matmul_scatter_exact(size, rows, k, n, as_blocks):
    """Every rank's schedule gives its block of psum_scatter(x @ w): at
    step j it multiplies block (idx - 1 - j) % size and adds what the
    rank before it sent, the sum of that block over the ranks the ring
    has visited. The injected shift plays the rank before: it checks
    what this rank sends on and hands over what would arrive."""
    xs, ws = _scatter_case(size, rows, k, n)
    partial = [np.asarray(x @ w) for x, w in zip(xs, ws)]   # [rank][all rows]
    ref = sum(partial)

    def block_of(a, b):
        return a[b * rows:(b + 1) * rows]

    for idx in range(size):
        step = [0]

        def shift(acc, idx=idx):
            j = step[0]
            step[0] += 1
            # this rank has just closed step j: block idx - 1 - j, summed
            # over the ranks from that block's first (b + 1) to this one
            b = (idx - 1 - j) % size
            sent = sum(block_of(partial[(b + 1 + t) % size], b)
                       for t in range(j + 1))
            assert np.array_equal(np.asarray(acc), sent), (idx, j)
            # what arrives is the same of the rank before, a block on
            b_in = (idx - 2 - j) % size
            return jnp.asarray(sum(
                block_of(partial[(b_in + 1 + t) % size], b_in)
                for t in range(j + 1)))

        x = xs[idx]
        if as_blocks:   # gather_matmul's ring order: position p = block idx + p
            x = [block_of(x, (idx + p) % size) for p in range(size)]
        out = overlap.matmul_scatter(x, ws[idx], shift=shift,
                                     axis_size=size, axis_index=idx)
        assert step[0] == size - 1
        assert np.array_equal(np.asarray(out), block_of(ref, idx)), idx


def test_matmul_scatter_single_device_degenerate():
    """size=1: no shift ever fires — the loop is one plain matmul."""
    x = jnp.arange(8.0).reshape(2, 4)
    w = jnp.arange(12.0).reshape(4, 3)

    def boom(cur):
        raise AssertionError("shift must not be called at size=1")

    out = overlap.matmul_scatter(x, w, shift=boom, axis_size=1,
                                 axis_index=0)
    assert np.array_equal(np.asarray(out), np.asarray(x @ w))


@pytest.mark.parametrize("size", [2, 4])
def test_matmul_scatter_under_shard_map(size):
    """The production path against psum_scatter itself, both inside one
    shard_map: the ppermute ring over the contracted features' axis."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:size]), ("tp",))
    xs, ws = _scatter_case(size, 2, 4, 8, seed=1)
    x = jnp.concatenate(xs, axis=1)      # [rows, size * k]: features split
    w = jnp.concatenate(ws, axis=0)

    def body(xl, wl):
        return (overlap.matmul_scatter(xl, wl, axis_name="tp"),
                jax.lax.psum_scatter(xl @ wl, "tp", scatter_dimension=0,
                                     tiled=True))

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(None, "tp"), P("tp")),
                       out_specs=(P("tp"), P("tp")))
    ours, theirs = jax.jit(fn)(x, w)
    assert np.array_equal(np.asarray(ours), np.asarray(theirs))
    assert np.array_equal(np.asarray(ours), np.asarray(x @ w))


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

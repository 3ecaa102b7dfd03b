import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.parallel import MeshConfig
from kubeflow_tpu.training import Trainer, TrainerConfig, OptimizerConfig, restore_or_init
from kubeflow_tpu.training import data as data_lib
from kubeflow_tpu.training.checkpoint import CheckpointManager


def make_trainer(tmp_path=None, model="mnist_cnn", mesh=MeshConfig(), devices=None, **over):
    cfg = TrainerConfig(
        model=model,
        model_overrides=over.pop("model_overrides", {}),
        batch_size=8,
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=2, total_steps=50),
        mesh=mesh,
        log_every=5,
    )
    return Trainer(cfg, devices=devices)


def test_mnist_loss_decreases():
    tr = make_trainer()
    data = data_lib.for_model("mnist_cnn", tr.model_cfg, 8)
    losses = []
    tr.metrics.echo = False
    state = tr.train(data, 30, step_callback=lambda s, m: losses.append(m["loss"]))
    assert losses[-1] < losses[0]
    assert int(state["step"]) == 30


def test_logged_record_splits_out_the_data_wait():
    """Each logged step's record carries `data_wait_s` beside
    `step_time_s`: the host's wall in next(data) + shard_batch, per step
    of the interval, so a slow input pipeline shows as itself and not as
    a slow step."""
    import time

    tr = make_trainer()
    tr.metrics.echo = False
    inner = data_lib.for_model("mnist_cnn", tr.model_cfg, 8)

    def slow():
        for batch in inner:
            time.sleep(0.01)
            yield batch

    records = []
    tr.train(slow(), 10, step_callback=lambda s, m: records.append(m))
    assert len(records) == 2              # log_every=5
    for rec in records:
        # (the last step prefetches nothing: 4 waits over 5 steps)
        assert 0.005 <= rec["data_wait_s"] <= rec["step_time_s"]


def _train_records(n_steps, monkeypatch=None, stall_ns=None,
                   callback_sleep_s=0.0, **over):
    import time

    if stall_ns is not None:
        monkeypatch.setattr("kubeflow_tpu.obs.trace.STALL_NS", stall_ns)
    cfg = TrainerConfig(
        model="mnist_cnn", batch_size=8,
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=2,
                                  total_steps=50), log_every=1, **over)
    tr = Trainer(cfg)
    tr.metrics.echo = False
    records = []

    def callback(step, scalars):
        records.append(dict(scalars))
        if callback_sleep_s and step == 4:
            time.sleep(callback_sleep_s)

    tr.train(data_lib.for_model("mnist_cnn", tr.model_cfg, 8), n_steps,
             step_callback=callback)
    return records


HOST_PHASES = ("data_wait", "dispatch", "fetch", "log", "checkpoint",
               "profile")


@pytest.mark.parametrize("check", [
    "partition", "data_wait_derived", "device_empty", "scalars"])
def test_trainer_loop_runs_on_the_phase_clock(check):
    """The Trainer's loop is on the engine thread's clock class: every
    logged record carries, per step since the last record, the wall of
    each of TRAINER_PHASES (they partition the interval between two
    fetches' ends, so they sum to `step_time_s`), the loop thread's CPU,
    the device-empty overlay (fetch -> next step_fn call), the
    collector's pauses and the longest single occurrence."""
    from kubeflow_tpu.obs.trace import TRAINER_PHASES

    assert TRAINER_PHASES == HOST_PHASES
    records = _train_records(20)
    assert len(records) == 20
    for n, rec in enumerate(records):
        if check == "partition":
            total = sum(rec[f"host_{p}_ms"] for p in HOST_PHASES)
            assert total == pytest.approx(rec["step_time_s"] * 1e3,
                                          rel=0.01)
            # no checkpoint dir, no profile dir: their phases never open
            assert rec["host_checkpoint_ms"] == rec["host_profile_ms"] == 0
            assert rec["host_dispatch_ms"] > 0 and rec["host_fetch_ms"] > 0
        elif check == "data_wait_derived":
            assert rec["data_wait_s"] == rec["host_data_wait_ms"] / 1e3
            # the last step prefetches nothing, the first fetched before
            # the first record's interval began
            assert (rec["data_wait_s"] > 0) is (n < 19)
        elif check == "device_empty":
            # with log_every 1 the device is empty from each fetch to the
            # next step_fn call: the record's log phase and what follows,
            # never the dispatch or the fetch it waits in
            assert 0 <= rec["device_empty_ms"] <= (
                rec["step_time_s"] * 1e3 - rec["host_dispatch_ms"])
            if n > 0:
                assert rec["device_empty_ms"] > 0
        else:
            assert 0 <= rec["host_cpu_ms"] <= rec["step_time_s"] * 1e3
            assert rec["gc_pause_ms"] >= 0
            # the longest occurrence that is not a fetch, NOT per step
            assert 0 < rec["host_phase_max_ms"] <= \
                rec["step_time_s"] * 1e3 + 1e-6
            assert all(isinstance(v, float) for v in rec.values())


@pytest.mark.parametrize("slow", ["step_callback", "nothing"])
def test_trainer_stall_names_the_slow_phase(slow, monkeypatch, caplog):
    """The stall rule on the Trainer's loop: a slow `step_callback`
    shows as the NEXT record's `host_log_ms` (the callback runs after
    its own record is cut) and as one `trainer stall:` line naming `log`
    and the step; `fetch` lasts a step by design and never stalls."""
    import logging

    from kubeflow_tpu.obs import metrics as obs_metrics

    before = obs_metrics.ENGINE_STALLS.value(engine="trainer", phase="log")
    with caplog.at_level(logging.WARNING, logger="kubeflow_tpu.obs.trace"):
        records = _train_records(
            8, monkeypatch, stall_ns=100_000_000,
            callback_sleep_s=0.15 if slow == "step_callback" else 0.0)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("trainer stall:")]
    assert not [ln for ln in lines if "phase fetch" in ln]
    logged = [ln for ln in lines if "phase log" in ln]
    stalls = obs_metrics.ENGINE_STALLS.value(engine="trainer", phase="log")
    by_step = [r["host_log_ms"] for r in records]
    if slow == "nothing":
        assert not logged and stalls == before and max(by_step) < 100
        return
    assert len(logged) == 1 and stalls == before + 1
    assert "step=4" in logged[0] and "cpu_ms=" in logged[0]
    # record 5 (index 4) is the interval the sleep fell in
    assert by_step[4] >= 150 and max(by_step[:4] + by_step[5:]) < 100
    assert records[4]["host_phase_max_ms"] >= 150
    assert records[4]["device_empty_ms"] >= 150
    assert records[4]["host_cpu_ms"] < records[4]["step_time_s"] * 1e3 - 100


def test_bf16_first_moment_halves_mu_state():
    """OptimizerConfig.mu_dtype='bfloat16': adam's first moment carries
    bf16 (half the HBM residency + step traffic) while params and the
    second moment stay f32, and training still converges."""
    cfg = TrainerConfig(
        model="mnist_cnn", batch_size=8,
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=2,
                                  total_steps=50, mu_dtype="bfloat16"),
        log_every=1)
    tr = Trainer(cfg)
    abstract = tr.abstract_state()
    dtypes = {str(l.dtype) for l in jax.tree.leaves(abstract["opt_state"])}
    assert "bfloat16" in dtypes and "float32" in dtypes
    assert all(l.dtype == jnp.float32
               for l in jax.tree.leaves(abstract["params"]))
    tr.metrics.echo = False
    losses = []
    data = data_lib.for_model("mnist_cnn", tr.model_cfg, 8)
    tr.train(data, 20, step_callback=lambda s, m: losses.append(m["loss"]))
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_llama_tiny_train_dp_tp(devices8):
    tr = make_trainer(
        model="llama", mesh=MeshConfig(data=2, fsdp=2, tensor=2),
        devices=devices8,
        model_overrides={"vocab_size": 256, "d_model": 32, "n_layers": 2,
                         "n_heads": 4, "n_kv_heads": 2, "d_ff": 64,
                         "max_seq_len": 64},
    )
    tr.metrics.echo = False
    data = data_lib.for_model("llama", tr.model_cfg, 8, seq_len=32)
    losses = []
    tr.train(data, 20, step_callback=lambda s, m: losses.append(m["loss"]))
    assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0]


def test_checkpoint_resume(tmp_path):
    tr = make_trainer()
    tr.metrics.echo = False
    data = data_lib.for_model("mnist_cnn", tr.model_cfg, 8)
    state = tr.train(data, 5)
    mngr = CheckpointManager(str(tmp_path / "ckpt"))
    mngr.save(5, jax.device_get(state) and state)
    mngr.close()

    tr2 = make_trainer()
    state2, resumed = restore_or_init(tr2, str(tmp_path / "ckpt"))
    assert resumed
    assert int(state2["step"]) == 5
    w1 = np.asarray(jax.device_get(state["params"]["fc2"]["w"]))
    w2 = np.asarray(jax.device_get(state2["params"]["fc2"]["w"]))
    np.testing.assert_allclose(w1, w2)


def test_restore_or_init_fresh(tmp_path):
    tr = make_trainer()
    state, resumed = restore_or_init(tr, str(tmp_path / "none"))
    assert not resumed
    assert int(state["step"]) == 0


def test_llama_scan_vs_unrolled_layers_identical():
    """cfg.scan_layers only changes scheduling (scan vs python loop):
    numerically equivalent within fusion-reassociation tolerance."""
    import dataclasses

    from kubeflow_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=128, d_model=32, n_layers=3,
                            n_heads=4, n_kv_heads=2, d_ff=64, max_seq_len=32,
                            attention_impl="xla", remat=True,
                            dtype=jnp.float32, scan_layers=True)
    params = llama.init(jax.random.key(0), cfg)
    tokens = np.array([[3, 17, 42, 9, 55, 2, 8, 11]], np.int32)
    a = jax.jit(lambda p, t: llama.apply(p, t, cfg))(params, tokens)
    cfg2 = dataclasses.replace(cfg, scan_layers=False)
    b = jax.jit(lambda p, t: llama.apply(p, t, cfg2))(params, tokens)
    # fp32: identical math; fusion reassociation may flip last ulps only
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)

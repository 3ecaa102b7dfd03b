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

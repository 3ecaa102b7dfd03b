"""models/kimi_linear.py on the normal path: bfloat16 compute beside its
float32 self, and two Trainer steps that report the routed experts'
counters (the reference comparisons are in test_kimi_linear.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.models import kimi_linear as kl

CFG = dataclasses.replace(kl.KimiLinearConfig.tiny(), dtype=jnp.float32,
                          attention_impl="xla")


@pytest.fixture(scope="module")
def params():
    return kl.init(jax.random.key(3), CFG)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, 96), 0, 512)


@pytest.mark.slow      # a second compile of the five-layer model: 35 s
def test_bfloat16_compute_stays_near_the_float32_model(params, tokens):
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    loss, _ = kl.loss_fn(params, {"tokens": tokens}, cfg)
    want, _ = kl.loss_fn(params, {"tokens": tokens}, CFG)
    assert abs(float(loss) - float(want)) < 2e-2 * float(want)


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_a_trainer_step_trains_the_family_and_reports_the_counters(
        monkeypatch, path):
    """Two steps on the CPU's jax.numpy KDA or the kernels interpreted; the
    first record says which backward and forward solve the step's trace
    took, and how many chunks and heads the forward kernels took a grid
    step (one chunk of 64 positions, 2 rows x 2 heads; 0 off the
    kernels)."""
    from kubeflow_tpu.ops import kda
    from kubeflow_tpu.parallel import MeshConfig
    from kubeflow_tpu.training.data import synthetic_tokens
    from kubeflow_tpu.training.trainer import Trainer, TrainerConfig

    overrides = {f.name: getattr(CFG, f.name)
                 for f in dataclasses.fields(CFG)
                 if f.name not in ("dtype", "param_dtype")}
    monkeypatch.setattr(kda, "FORCE_INTERPRET", path == "pallas")
    seen = []
    trainer = Trainer(TrainerConfig(
        model="kimi_linear", model_overrides=overrides, batch_size=2,
        mesh=MeshConfig(data=1), log_every=1), devices=jax.devices()[:1])
    trainer.train(synthetic_tokens(2, 64, 512), 2,
                  step_callback=lambda step, scalars: seen.append(scalars))
    assert len(seen) == 2
    for name in ("loss", "moe_rows_here", "moe_rows_dropped",
                 "moe_expert_load_max_over_mean", "router_top1_share_max"):
        assert name in seen[-1]
    assert seen[-1]["moe_rows_dropped"] == 0
    for part in ("backward", "solve"):
        assert seen[0][f"kda_{part}_kernel_share"] == (
            1.0 if path == "pallas" else 0.0)
        assert f"kda_{part}_kernel_share" not in seen[1]
    blocking = (1.0, 4.0) if path == "pallas" else (0.0, 0.0)
    names = ("kda_intra_chunks_per_step", "kda_walk_heads_per_step")
    assert tuple(seen[0][name] for name in names) == blocking
    assert not set(names) & set(seen[1])

"""Built-in `trainer` pod target — the reference's training container image.

The reference ships example trainer images (⊘ kubeflow/examples mnist,
training-operator `examples/`) that jobs point at; users only write YAML.
Here the same role is a registered worker target: a JAXJob template says

    template:
      backend: thread
      target: trainer
      env:
        KTPU_TRAINER_CONFIG: >
          {"model": "mnist_cnn", "batch_size": 32, "num_steps": 100,
           "optimizer": {"learning_rate": 0.01},
           "mesh": {"data": -1}, "checkpoint_dir": "/tmp/ckpt/mnist"}

and the target builds Trainer + synthetic/array data, trains `num_steps`,
resuming from `checkpoint_dir` if a checkpoint exists (the restart/resume
contract, SURVEY.md §5.4). Metrics go to KTPU_METRICS_FILE (HPO collector)
and, when KTPU_TRIAL_NAME is set, straight to the observation DB.

Cancellation (pod deletion, elastic scale-down) is honored between steps:
the cancel event maps to SystemExit(143) — SIGTERM semantics, retryable
under the ExitCode restart policy.
"""

from __future__ import annotations

import dataclasses
import json
import threading
from typing import Any

from kubeflow_tpu.control.executor import worker_target
from kubeflow_tpu.parallel import MeshConfig
from kubeflow_tpu.training.checkpoint import restore_or_init
from kubeflow_tpu.training.data import DatasetConfig
from kubeflow_tpu.training.metrics_writer import MetricsWriter
from kubeflow_tpu.training.trainer import (OptimizerConfig, Trainer,
                                           TrainerConfig)


def config_from_env(env: dict[str, str]) -> tuple[TrainerConfig, int]:
    """Parse KTPU_TRAINER_CONFIG into (TrainerConfig, num_steps)."""
    raw = json.loads(env.get("KTPU_TRAINER_CONFIG", "{}"))
    num_steps = int(raw.pop("num_steps", 100))
    opt = raw.pop("optimizer", {})
    mesh = raw.pop("mesh", {})
    dataset = raw.pop("dataset", {})
    known = {f.name for f in dataclasses.fields(TrainerConfig)}
    unknown = set(raw) - known
    if unknown:
        raise ValueError(f"unknown trainer config keys: {sorted(unknown)}")
    cfg = TrainerConfig(**raw)
    cfg.optimizer = OptimizerConfig(**opt)
    cfg.mesh = MeshConfig(**mesh)
    cfg.dataset = DatasetConfig(**dataset)
    # LR schedule spans the run unless the spec pinned total_steps itself
    # (e.g. chunked training resuming against a longer schedule)
    if "total_steps" not in opt:
        cfg.optimizer.total_steps = num_steps
    return cfg, num_steps


@worker_target("trainer")
def train_target(env: dict[str, str], cancel: threading.Event) -> None:
    """Train a registered model from env-provided config (see module doc)."""
    from kubeflow_tpu.hpo.observations import report_metric
    from kubeflow_tpu.runtime.compile_cache import ensure_compile_cache
    from kubeflow_tpu.training import data as data_lib

    ensure_compile_cache()
    cfg, num_steps = config_from_env(env)
    metrics = MetricsWriter(env.get("KTPU_METRICS_FILE"))
    trial = env.get("KTPU_TRIAL_NAME")

    trainer = Trainer(cfg, metrics=metrics)
    state, resumed = restore_or_init(trainer, cfg.checkpoint_dir)
    start = int(state["step"])
    if resumed:
        print(f"resumed from checkpoint at step {start}", flush=True)
    remaining = max(0, num_steps - start)

    def on_step(step: int, scalars: dict[str, Any]) -> None:
        if trial:
            for k, v in scalars.items():
                if k not in ("step_time_s", "includes_compile"):
                    report_metric(trial, k, float(v), step)
        if cancel.is_set():
            raise SystemExit(143)

    data = data_lib.make_dataset(cfg.dataset, cfg.model, trainer.model_cfg,
                                 cfg.batch_size, fallback_seed=cfg.seed)
    try:
        trainer.train(data, remaining, state=state, step_callback=on_step)
    finally:
        if hasattr(data, "close"):
            data.close()
    metrics.close()
    print(f"training done: {num_steps} steps", flush=True)

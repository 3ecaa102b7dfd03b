"""Data pipelines: synthetic generators per model family + sharded host→device
staging. The reference delegates data loading entirely to user containers;
here the built-in models get deterministic synthetic datasets (benchmarking,
HPO sweeps, tests) plus an array-backed dataset for real data.

Multi-host note: each process generates/loads only its local shard (determined
by jax.process_index()), and `Trainer.shard_batch` stages it onto the mesh —
the jax.make_array_from_process_local_data path when running multi-process.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterator

import numpy as np


@dataclasses.dataclass
class DatasetConfig:
    """What a job trains on (the `dataset` key of KTPU_TRAINER_CONFIG).

    The reference mounts real data into trainer pods (⊘ kubeflow/examples
    mnist PVC/GCS volumes); here the same contract is a typed source spec:

      synthetic   — per-model generator (default; benches/HPO/tests)
      token_file  — flat uint32 token corpus via the C++ prefetching loader
                    (native/src/data_loader.cpp) with a Python twin fallback
      array_file  — .npz of named arrays, epoch-cycled minibatches

    Multi-host: every process sees the same config; `make_dataset` gives each
    process a batch_size/process_count slice (stride-sliced rows for
    array_file, a process-decorrelated crop seed for token_file/synthetic)
    and `Trainer.shard_batch` assembles the global array.
    """

    type: str = "synthetic"
    path: str | None = None
    seq_len: int = 128
    seed: int | None = None  # falls back to TrainerConfig.seed
    prefer_native: bool = True  # token_file: C++ prefetch ring when built
    shuffle: bool = True  # array_file


def synthetic_tokens(batch_size: int, seq_len: int, vocab_size: int,
                     seed: int = 0) -> Iterator[dict[str, Any]]:
    """Infinite LM batches with a learnable structure (repeating n-grams) so
    loss actually decreases — pure-random tokens can't show learning."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, vocab_size, size=(64,))
    while True:
        starts = rng.integers(0, 64, size=(batch_size,))
        # exactly seq_len tokens: the model forwards the full sequence and
        # shifts logits internally (loss over seq_len-1 targets), keeping S a
        # clean power of two for attention blocks and the sequence mesh axis
        tokens = np.stack([
            np.resize(np.roll(base, -s), seq_len) for s in starts
        ])
        noise = rng.random(tokens.shape) < 0.02
        tokens = np.where(noise, rng.integers(0, vocab_size, tokens.shape), tokens)
        yield {"tokens": tokens.astype(np.int32)}


def synthetic_images(batch_size: int, image_size: int, channels: int,
                     n_classes: int, seed: int = 0) -> Iterator[dict[str, Any]]:
    """Class-conditional gaussian blobs: learnable image classification."""
    rng = np.random.default_rng(seed)
    protos = rng.normal(size=(n_classes, image_size, image_size, channels))
    while True:
        labels = rng.integers(0, n_classes, size=(batch_size,))
        images = protos[labels] + 0.5 * rng.normal(
            size=(batch_size, image_size, image_size, channels))
        yield {"image": images.astype(np.float32),
               "label": labels.astype(np.int32)}


def synthetic_classification_text(batch_size: int, seq_len: int,
                                  vocab_size: int, n_classes: int = 2,
                                  seed: int = 0) -> Iterator[dict[str, Any]]:
    """BERT-style: label determined by presence of class-marker tokens."""
    rng = np.random.default_rng(seed)
    while True:
        labels = rng.integers(0, n_classes, size=(batch_size,))
        tokens = rng.integers(n_classes + 1, vocab_size,
                              size=(batch_size, seq_len))
        tokens[:, 1] = labels + 1  # marker token after [CLS]
        tokens[:, 0] = 0  # [CLS]
        yield {"tokens": tokens.astype(np.int32),
               "label": labels.astype(np.int32)}


def array_dataset(arrays: dict[str, np.ndarray], batch_size: int,
                  shuffle: bool = True, seed: int = 0,
                  drop_remainder: bool = True) -> Iterator[dict[str, Any]]:
    """Epoch-cycling minibatcher over in-memory arrays (the MNIST/e2e path)."""
    n = len(next(iter(arrays.values())))
    rng = np.random.default_rng(seed)
    while True:
        idx = rng.permutation(n) if shuffle else np.arange(n)
        stop = n - batch_size + 1 if drop_remainder else n
        for i in range(0, stop, batch_size):
            sel = idx[i:i + batch_size]
            yield {k: v[sel] for k, v in arrays.items()}


def for_model(model: str, model_cfg, batch_size: int, seq_len: int = 128,
              seed: int = 0) -> Iterator[dict[str, Any]]:
    """Default synthetic stream for a registered model (bench/HPO/test path)."""
    if model in ("llama", "llama_lora", "mixtral", "kimi_linear"):
        return synthetic_tokens(batch_size, seq_len, model_cfg.vocab_size, seed)
    if model == "bert":
        return synthetic_classification_text(
            batch_size, min(seq_len, model_cfg.max_seq_len),
            model_cfg.vocab_size, model_cfg.n_classes, seed)
    if model == "mnist_cnn":
        return synthetic_images(batch_size, 28, 1, model_cfg.n_classes, seed)
    if model == "resnet":
        return synthetic_images(batch_size, model_cfg.image_size, 3,
                                model_cfg.n_classes, seed)
    if model in ("nas_cnn", "darts_supernet", "vit"):
        return synthetic_images(batch_size, model_cfg.image_size,
                                model_cfg.in_channels, model_cfg.n_classes,
                                seed)
    raise KeyError(f"no synthetic data recipe for model {model!r}")


def make_dataset(ds: DatasetConfig, model: str, model_cfg, batch_size: int,
                 fallback_seed: int = 0) -> Iterator[dict[str, Any]]:
    """Resolve a DatasetConfig to this process's batch iterator.

    batch_size is the GLOBAL batch (the Trainer.shard_batch contract); each
    process yields its batch_size/process_count share, decorrelated across
    hosts by a process-offset seed (token_file/synthetic) or a stride slice
    of the rows (array_file)."""
    import jax

    pc, pi = jax.process_count(), jax.process_index()
    if batch_size % pc:
        raise ValueError(
            f"batch_size {batch_size} not divisible by {pc} processes")
    local = batch_size // pc
    seed = ds.seed if ds.seed is not None else fallback_seed

    if ds.type == "synthetic":
        return for_model(model, model_cfg, local, seq_len=ds.seq_len,
                         seed=seed + pi)
    if ds.type == "token_file":
        if not ds.path:
            raise ValueError("dataset.type=token_file requires dataset.path")
        from kubeflow_tpu.training.loader import token_file_dataset

        return token_file_dataset(ds.path, local, ds.seq_len,
                                  seed=seed + pi,
                                  prefer_native=ds.prefer_native)
    if ds.type == "array_file":
        if not ds.path:
            raise ValueError("dataset.type=array_file requires dataset.path")
        with np.load(ds.path) as z:
            arrays = {k: z[k][pi::pc] for k in z.files}
        return array_dataset(arrays, local, shuffle=ds.shuffle, seed=seed)
    raise ValueError(f"unknown dataset type {ds.type!r} "
                     "(expected synthetic | token_file | array_file)")

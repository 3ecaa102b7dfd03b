"""Profiling/tracing hooks — the platform's TensorBoard-profiler analog
(SURVEY.md §5.1: the reference delegates workload profiling to TF/torch
profilers surfaced through the tensorboard-controller; here `jax.profiler`
is first-class and the trace windows are part of the trainer config).

Two surfaces:

- `trace(logdir)`: context manager around arbitrary device work.
- `StepProfiler`: step-windowed capture for the training loop — starts at
  `start_step`, captures `num_steps` steps, then stops and writes a
  `PROFILE_DONE` marker; the Tensorboard CR can point at the same logdir
  (tensorboard-plugin-profile reads the plugins/profile subdir).

The captured dir is the artifact; callers register it in the metadata
store for lineage like any pipeline output (SURVEY.md §5.1 "artifact =
trace dir registered in the metadata store").
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Callable


@contextlib.contextmanager
def trace(logdir: str):
    """jax.profiler.trace with the dir created up front; yields the dir."""
    import jax

    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


class StepProfiler:
    """Capture a [start_step, start_step + num_steps) window of the train
    loop. `maybe_stop` takes a sync thunk because dispatch returns before
    the device finishes — the caller fences the trace (a scalar fetch or
    block_until_ready on the step's outputs)."""

    def __init__(self, logdir: str, start_step: int = 2, num_steps: int = 3):
        if num_steps < 1:
            raise ValueError("profile_num_steps must be >= 1")
        self.logdir = logdir
        self.start_step = start_step
        self.end_step = start_step + num_steps
        self.active = False
        self.done = False

    def maybe_start(self, step: int) -> None:
        if self.done or self.active or step < self.start_step:
            return
        import jax

        os.makedirs(self.logdir, exist_ok=True)
        jax.profiler.start_trace(self.logdir)
        self.active = True

    def maybe_stop(self, step: int,
                   sync: Callable[[], Any] | None = None) -> None:
        if not self.active or step + 1 < self.end_step:
            return
        import jax

        if sync is not None:
            sync()  # fence: device work for the window must have retired
        jax.profiler.stop_trace()
        self.active = False
        self.done = True
        with open(os.path.join(self.logdir, "PROFILE_DONE"), "w") as f:
            f.write(f"steps {self.start_step}..{self.end_step - 1}\n")

    def close(self) -> None:
        """Stop a still-open window (loop ended early)."""
        if self.active:
            import jax

            jax.profiler.stop_trace()
            self.active = False


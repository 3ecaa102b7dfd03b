"""The JAXJob trainer — the in-framework replacement for the reference's L7
user containers (torch DDP loops launched by PyTorchJob, SURVEY.md §3.1).

Where the reference injects MASTER_ADDR/WORLD_SIZE env vars and lets torch
build NCCL rings, this trainer receives a Mesh and expresses all parallelism
as shardings on one jitted train step; XLA inserts the collectives. One code
path covers 1 chip -> v5e-16 -> multi-slice: only the MeshConfig changes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from kubeflow_tpu.models import registry
from kubeflow_tpu.obs.trace import TRAINER_PHASES, PhaseClock, PhaseMark
from kubeflow_tpu.ops import flash_attention, kda
from kubeflow_tpu.parallel import (
    MeshConfig,
    active_mesh,
    make_mesh,
    logical_to_spec,
    overlap,
    tree_logical_to_sharding,
)
from kubeflow_tpu.training.data import DatasetConfig
from kubeflow_tpu.training.metrics_writer import MetricsWriter


@dataclasses.dataclass
class OptimizerConfig:
    name: str = "adamw"
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    schedule: str = "cosine"  # cosine | linear | constant
    # first-moment dtype: "bfloat16" halves mu's HBM residency AND its
    # read+write traffic each step (+1 MFU pt at the bench shape); the
    # second moment stays f32 (its dynamic range matters for the rsqrt)
    mu_dtype: str | None = None
    # parameter-efficient fine-tuning: only params whose tree path starts
    # with this "/"-joined prefix train (e.g. "lora" for llama_lora);
    # everything else is frozen with optax.set_to_zero, so optimizer
    # moments exist ONLY for the trainable leaves — the memory contract
    # that lets an 8B LoRA fine-tune fit where full Adam state would not
    trainable_prefix: str | None = None


@dataclasses.dataclass
class TrainerConfig:
    model: str = "mnist_cnn"
    model_overrides: dict[str, Any] = dataclasses.field(default_factory=dict)
    batch_size: int = 8
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    sharding_rules: dict[str, Any] = dataclasses.field(default_factory=dict)
    dataset: DatasetConfig = dataclasses.field(default_factory=DatasetConfig)
    seed: int = 0
    log_every: int = 10
    checkpoint_dir: str | None = None
    checkpoint_every: int = 200
    keep_checkpoints: int = 3
    # step-windowed jax.profiler capture (SURVEY.md §5.1); None disables
    profile_dir: str | None = None
    profile_start_step: int = 2
    profile_num_steps: int = 3


def _host_scalars(clock: PhaseClock, last: PhaseMark, now: PhaseMark,
                  steps: int) -> dict[str, float]:
    """What the loop's phase clock read between two marks (the ends of
    two logged steps' fetches), per step like `step_time_s`: the wall of
    each phase (`host_<phase>_ms`; they partition the interval, so they
    sum to `step_time_s`), the thread's CPU over all of them, the
    device-empty overlay, the collector's pauses, and the longest single
    occurrence that was not a fetch (NOT per step). `data_wait_s` is
    `host_data_wait_ms` in seconds: the host wall in next(data) +
    shard_batch."""
    def per_step_ms(a: int, b: int) -> float:
        return (b - a) / 1e6 / steps

    out = {f"host_{p}_ms": per_step_ms(a, b)
           for p, a, b in zip(clock.phases, last.ns, now.ns)}
    longest = clock.longest_since(last.at_ns / 1e9, now.at_ns)
    out.update(
        step_time_s=(now.at_ns - last.at_ns) / 1e9 / steps,
        data_wait_s=out["host_data_wait_ms"] / 1e3,
        host_cpu_ms=per_step_ms(sum(last.cpu_ns), sum(now.cpu_ns)),
        device_empty_ms=per_step_ms(last.device_empty_ns,
                                    now.device_empty_ns),
        gc_pause_ms=per_step_ms(last.gc_ns, now.gc_ns),
        host_phase_max_ms=longest[0] / 1e6 if longest else 0.0)
    return out


def _path_keys(path) -> tuple[str, ...]:
    """Normalize a jax tree path (DictKey/GetAttrKey/SequenceKey entries) to
    plain strings — trainable_prefix matching and the optimizer-state
    suffix-sharding fallback MUST normalize identically, so there is
    exactly one implementation."""
    return tuple(str(getattr(p, "key", getattr(p, "name",
                             getattr(p, "idx", p)))) for p in path)


def make_optimizer(cfg: OptimizerConfig) -> optax.GradientTransformation:
    if cfg.schedule == "cosine":
        sched = optax.warmup_cosine_decay_schedule(
            0.0, cfg.learning_rate, cfg.warmup_steps,
            max(cfg.total_steps, cfg.warmup_steps + 1))
    elif cfg.schedule == "linear":
        sched = optax.linear_schedule(cfg.learning_rate, 0.0, cfg.total_steps)
    else:
        sched = cfg.learning_rate
    mu_dtype = jnp.dtype(cfg.mu_dtype) if cfg.mu_dtype else None
    opt = {
        "adamw": lambda: optax.adamw(sched, b1=cfg.b1, b2=cfg.b2,
                                     weight_decay=cfg.weight_decay,
                                     mu_dtype=mu_dtype),
        "adam": lambda: optax.adam(sched, b1=cfg.b1, b2=cfg.b2,
                                   mu_dtype=mu_dtype),
        # sgd's momentum trace is its mu analog (accumulator_dtype)
        "sgd": lambda: optax.sgd(sched, momentum=0.9,
                                 accumulator_dtype=mu_dtype),
    }[cfg.name]()
    if cfg.trainable_prefix:
        prefix = tuple(cfg.trainable_prefix.split("/"))

        def labels(params):
            def lab(path, _):
                keys = _path_keys(path)
                return ("train" if keys[:len(prefix)] == prefix
                        else "freeze")
            return jax.tree_util.tree_map_with_path(lab, params)

        opt = optax.multi_transform(
            {"train": opt, "freeze": optax.set_to_zero()}, labels)
    if cfg.grad_clip:
        opt = optax.chain(optax.clip_by_global_norm(cfg.grad_clip), opt)
    return opt


class Trainer:
    """Builds the sharded train step for a registered model on a mesh."""

    def __init__(self, config: TrainerConfig, *, mesh: Mesh | None = None,
                 devices=None, metrics: MetricsWriter | None = None):
        self.config = config
        self.mesh = mesh if mesh is not None else make_mesh(config.mesh,
                                                            devices=devices)
        self.model = registry.get(config.model)
        self.model_cfg = self.model.config_cls(**config.model_overrides)
        self.optimizer = make_optimizer(config.optimizer)
        self.metrics = metrics or MetricsWriter()
        self.rules = config.sharding_rules

        logical = self.model.logical_axes(self.model_cfg)
        self.param_sharding = tree_logical_to_sharding(logical, self.mesh,
                                                       self.rules)
        self.batch_spec = logical_to_spec(("batch",), self.rules)
        self.batch_sharding = NamedSharding(self.mesh, self.batch_spec)
        # rank>=2 batch leaves ([B, S, ...] tokens/masks) additionally shard
        # dim 1 over the sequence axis (dropped at size 1 — a no-op off the
        # long-context path)
        self.batch_seq_spec = logical_to_spec(("batch", "seq"), self.rules)
        self.batch_seq_sharding = NamedSharding(self.mesh, self.batch_seq_spec)
        self.repl = NamedSharding(self.mesh, PartitionSpec())

        self._jit_init = None
        self._jit_step = None
        # which of a layer's tensor-parallel projections traced in the
        # form that carries its own exchange (parallel/overlap.py): filled
        # when the step is traced, empty on a mesh without `tensor`
        self.overlapped_sites: set[str] = set()
        # beside it, what the step's calls of the Pallas attention kernels
        # found: ops/flash_pallas.block_census's (interior, diagonal,
        # future) tiles a head, one entry a traced call
        self.attention_census: list[tuple[int, int, int]] = []
        # and the path each traced KDA backward and forward solve took
        # ("kernel" or "xla")
        self.kda_backward_census: list[str] = []
        self.kda_solve_census: list[str] = []

    # -- state ---------------------------------------------------------------

    def init_state(self) -> dict[str, Any]:
        """Initialize params+opt_state directly sharded on the mesh (no full
        replica ever materializes on one host — essential at 8B scale)."""
        if self._jit_init is None:
            def _init(rng):
                params = self.model.init(rng, self.model_cfg)
                opt_state = self.optimizer.init(params)
                return {"params": params, "opt_state": opt_state,
                        "step": jnp.zeros((), jnp.int32)}

            abstract = jax.eval_shape(_init, jax.random.key(self.config.seed))
            out_sh = self._state_sharding(abstract)
            self._jit_init = jax.jit(_init, out_shardings=out_sh)
        return self._jit_init(jax.random.key(self.config.seed))

    def _state_sharding(self, abstract_state):
        """Param shardings for params; optimizer momenta follow their params
        *structurally* (optax.tree_map_params — shape matching would confuse
        transposed same-shape weights like wq/wo); non-param leaves replicate.
        Wrapped optimizers optax can't traverse (multi_transform for
        trainable_prefix freezing) fall back to exact path-SUFFIX matching:
        a momentum leaf's trailing dict path IS its param's path (mu/nu
        mirror the params tree), so the match is as exact as the structural
        one — a same-shape transposed weight still can't confuse it."""
        try:
            opt_sh = optax.tree_map_params(
                self.optimizer,
                lambda _, sh: sh,
                abstract_state["opt_state"],
                self.param_sharding,
                transform_non_params=lambda _: self.repl,
            )
        except (ValueError, TypeError):
            # the ONLY known-untraversable optimizer is the multi_transform
            # wrapper trainable_prefix builds; any other failure here is a
            # real sharding-spec bug that must not hide behind the fallback
            if not self.config.optimizer.trainable_prefix:
                raise
            opt_sh = self._suffix_path_sharding(abstract_state)
        return {"params": self.param_sharding, "opt_state": opt_sh,
                "step": self.repl}

    def _suffix_path_sharding(self, abstract_state):
        norm = _path_keys
        flat_sh = {norm(p): sh for p, sh in
                   jax.tree_util.tree_flatten_with_path(
                       self.param_sharding,
                       is_leaf=lambda x: isinstance(x, NamedSharding))[0]}
        flat_shape = {norm(p): leaf.shape for p, leaf in
                      jax.tree_util.tree_flatten_with_path(
                          abstract_state["params"])[0]}

        def assign(path, leaf):
            keys = norm(path)
            for i in range(len(keys)):  # longest suffix first
                suf = keys[i:]
                if suf in flat_sh and flat_shape[suf] == leaf.shape:
                    return flat_sh[suf]
            return self.repl

        return jax.tree_util.tree_map_with_path(
            assign, abstract_state["opt_state"])

    def abstract_state(self) -> dict[str, Any]:
        """Sharding-annotated ShapeDtypeStructs of the train state — the
        checkpoint-restore target (no device memory touched)."""
        def _init(rng):
            params = self.model.init(rng, self.model_cfg)
            opt_state = self.optimizer.init(params)
            return {"params": params, "opt_state": opt_state,
                    "step": jnp.zeros((), jnp.int32)}

        abstract = jax.eval_shape(_init, jax.random.key(self.config.seed))
        shardings = self._state_sharding(abstract)
        return jax.tree.map(
            lambda l, sh: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sh),
            abstract, shardings)

    # -- step ----------------------------------------------------------------

    def _build_step(self, example_batch, state):
        loss_fn = self.model.loss_fn
        model_cfg = self.model_cfg
        optimizer = self.optimizer

        def train_step(state, batch):
            def compute(params):
                return loss_fn(params, batch, model_cfg)

            (loss, metrics), grads = jax.value_and_grad(compute, has_aux=True)(
                state["params"])
            updates, new_opt = optimizer.update(grads, state["opt_state"],
                                                state["params"])
            new_params = optax.apply_updates(state["params"], updates)
            metrics = dict(metrics)
            metrics["grad_norm"] = optax.global_norm(grads)
            new_state = {"params": new_params, "opt_state": new_opt,
                         "step": state["step"] + 1}
            return new_state, metrics

        # state keeps the sharding it was initialized with (in_shardings=None
        # = "as given") and LEAVES with it: the partitioner's own choice for
        # the new state differs (norm leaves over fsdp, size-1 axes dropped
        # from every spec), and a state that comes back sharded otherwise
        # misses the jit cache on the next call: the step lowered and
        # compiled twice. batch is forced onto the data (+sequence) axes.
        batch_sh = jax.tree.map(self._leaf_sharding, example_batch)
        state_sh = jax.tree.map(lambda leaf: leaf.sharding, state)
        jitted = self._jitted = jax.jit(
            train_step,
            in_shardings=(None, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )

        def step(state, batch):
            # ambient mesh for shard_map islands (ring/Ulysses attention,
            # MoE all-to-all) traced inside the jitted step
            seen = len(flash_attention.TRACED_CENSUS)
            seen_kda = len(kda.TRACED_BACKWARD), len(kda.TRACED_SOLVE)
            with active_mesh(self.mesh), overlap.count_sites() as traced:
                out = jitted(state, batch)
            self.overlapped_sites |= traced
            self.attention_census += flash_attention.TRACED_CENSUS[seen:]
            self.kda_backward_census += kda.TRACED_BACKWARD[seen_kda[0]:]
            self.kda_solve_census += kda.TRACED_SOLVE[seen_kda[1]:]
            return out

        return step

    def aot_lower(self, abstract_batch):
        """AOT-lower the sharded train step from ShapeDtypeStructs alone —
        no device memory is touched, so an 8B-scale layout can be proven on
        hosts that could never hold the weights (training/contract.py)."""
        abstract_state = self.abstract_state()
        self._build_step(abstract_batch, abstract_state)
        with active_mesh(self.mesh):
            return self._jitted.lower(abstract_state, abstract_batch)

    def compiled_step(self, state, example_batch):
        if self._jit_step is None:
            self._jit_step = self._build_step(example_batch, state)
        return self._jit_step

    def _leaf_sharding(self, x) -> NamedSharding:
        return (self.batch_seq_sharding if getattr(x, "ndim", 0) >= 2
                else self.batch_sharding)

    def shard_batch(self, batch: dict[str, Any]) -> dict[str, Any]:
        """Host batch -> global device arrays.

        Single-process: a committing device_put. Multi-host (a JAXJob
        spanning processes via jax.distributed): each host feeds its OWN
        rows — config.batch_size stays the GLOBAL batch, the data iterator
        on every host yields batch_size / process_count examples, and the
        per-host blocks are assembled into one global array without any
        cross-host transfer (the v5e-16 multi-host feeding path, SURVEY.md
        §5.8)."""
        if jax.process_count() == 1:
            return jax.tree.map(
                lambda x: jax.device_put(x, self._leaf_sharding(x)), batch)
        import numpy as np

        # np (not jnp): committing the local batch to a device first would
        # add a redundant whole-batch transfer before the per-device slicing
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(
                self._leaf_sharding(x), np.asarray(x)), batch)

    # -- loop ----------------------------------------------------------------

    def train(self, data: Iterator[dict[str, Any]], num_steps: int,
              state: dict[str, Any] | None = None,
              step_callback: Callable[[int, dict], None] | None = None):
        state = state if state is not None else self.init_state()
        ckpt = None
        if self.config.checkpoint_dir:
            from kubeflow_tpu.training.checkpoint import CheckpointManager

            ckpt = CheckpointManager(
                self.config.checkpoint_dir,
                max_to_keep=self.config.keep_checkpoints,
                save_interval_steps=self.config.checkpoint_every)
        step_fn = None
        steps_since_log = 0
        first_interval = True  # includes jit compile; flagged, not averaged in
        start_step = int(state["step"])
        prof = None
        if self.config.profile_dir:
            from kubeflow_tpu.training.profiling import StepProfiler

            # window is relative to THIS run's first step: on resume the
            # compile happens again, and profile_start_step exists to skip it
            prof = StepProfiler(self.config.profile_dir,
                                start_step + self.config.profile_start_step,
                                self.config.profile_num_steps)
        pending = None
        step = start_step
        # every instant of the loop lies in one of TRAINER_PHASES, as the
        # engine thread's does in its own (obs.trace.PhaseClock): wall,
        # the thread's CPU, the collector's pauses, and the device-empty
        # overlay from a fetch to the next step_fn call; under a profiler
        # capture the phases are `trainer.<phase>` on this thread's host
        # line, beside the steps' `train_step` annotations. `fetch` lasts
        # the step by design, so it is the phase that may wait.
        clock = PhaseClock(
            "trainer",
            lambda: {"step": step, "includes_compile": first_interval},
            phases=TRAINER_PHASES, dispatch=frozenset(("dispatch",)),
            waits=frozenset(("fetch",)))
        clock.hold_open = True

        def next_batch():
            clock.enter("data_wait")
            return self.shard_batch(next(data))

        clock.enter("data_wait")
        last = clock.mark()
        for i in range(num_steps):
            batch = pending if pending is not None else next_batch()
            pending = None
            if step_fn is None:
                step_fn = self.compiled_step(state, batch)
            if prof is not None:
                clock.enter("profile")
                prof.maybe_start(start_step + i + 1)
            clock.enter("dispatch")
            # after the transition: a stall of the phase just closed (the
            # log, the save) is reported with the step it belonged to
            step = start_step + i + 1
            with jax.profiler.StepTraceAnnotation("train_step",
                                                  step_num=step):
                state, metrics = step_fn(state, batch)
            # one-batch device prefetch: the next host->device transfer is
            # enqueued while this step runs, hiding it behind compute
            # (device_put/make_array are async dispatches). A data-iterator
            # failure here must not lose THIS step's log + checkpoint —
            # stash it and re-raise after the step's bookkeeping runs.
            data_err: BaseException | None = None
            if i + 1 < num_steps:
                try:
                    pending = next_batch()
                except BaseException as e:
                    data_err = e
            if prof is not None:
                clock.enter("profile")
                # sync by fetching the step's scalars: a value on the
                # host means the step that produced it has finished
                prof.maybe_stop(step, sync=lambda: jax.device_get(metrics))
            steps_since_log += 1
            if step % self.config.log_every == 0 or i == num_steps - 1:
                clock.enter("fetch")
                metrics = jax.device_get(metrics)
                clock.fetched(outstanding=False)
                clock.enter("log")
                now = clock.mark()
                scalars = {k: float(v) for k, v in metrics.items()}
                scalars.update(_host_scalars(clock, last, now,
                                             steps_since_log))
                last = now
                steps_since_log = 0
                if first_interval:
                    scalars["includes_compile"] = 1.0
                    scalars["overlapped_projections_per_layer"] = float(
                        len(self.overlapped_sites))
                    # of the tiles the attention kernels visit, those that
                    # run with no mask (0 where no kernel was traced)
                    interior = sum(c[0] for c in self.attention_census)
                    visited = interior + sum(
                        c[1] for c in self.attention_census)
                    scalars["attention_interior_tile_share"] = (
                        interior / visited if visited else 0.0)
                    # of the traced KDA backwards and forward solves,
                    # those that ran the kernels (0 where none was traced)
                    for part, census in (
                            ("backward", self.kda_backward_census),
                            ("solve", self.kda_solve_census)):
                        scalars[f"kda_{part}_kernel_share"] = (
                            census.count("kernel") / len(census) if census
                            else 0.0)
                    first_interval = False
                self.metrics.write(step, scalars)
                if step_callback:
                    step_callback(step, scalars)
            if ckpt is not None:
                clock.enter("checkpoint")
                # manager applies save_interval_steps; final step forced below
                ckpt.save(step, state)
            if data_err is not None:
                raise data_err
        if prof is not None:
            clock.enter("profile")
            prof.close()
        if ckpt is not None:
            clock.enter("checkpoint")
            final = start_step + num_steps
            if ckpt.latest_step() != final:  # interval may have saved it already
                ckpt.save(final, state, force=True)
            ckpt.close()
        clock.hold_open = False
        clock.leave()
        return state

"""A JAXJob that trains for the window and is then deleted.

Entry the window drives: `Platform.apply` of a JAXJob (`target: trainer`,
`backend: thread`) whose Trainer runs on the cell's mesh, fed from a token
file made from the seed through the program's loader. The job logs every
step to its metrics file (step, loss, grad_norm, ts).

Window: it opens at the instant (the program's `ts`) the step after the
followed ones completes, i.e. after the interval that carries the compile,
and closes with the first step that completes --seconds after that or
later, so it begins and ends on a step's boundary and is less than a step
longer than --seconds. The rate is the tokens of every step completed in it
over all of its time: a stall that runs into the close is waited for (a
minute at most) and counted whole. Closing at --seconds on the dot would
count whole steps over a time that ends inside one: a rate in quanta of one
step in 53 (1.9 %), blind to any smaller change.

`correct`: set-up is the job's own first steps. A tap on the Trainer's
compiled step (the one object that goes on into the window) reads, without
changing what the step computes: after step 1 the norm of every leaf of the
optimizer's first moment (the gradient as the optimizer got it is
mu / (1 - b1)); after step 3 the norm of every leaf's change from the
initial parameters, which the reference's own init regenerates. After the
window, with the job deleted and the devices freed, the plain reference
(float32, highest precision, its own loader twin, its own AdamW) follows the
same three steps on the same rows and the numbers are compared.
"""

from __future__ import annotations

import functools
import json
import os
import statistics
import time

import numpy as np

from lib.harness import log, weight_seed

#: program leaf -> the reference's (Mistral's) name
LEAF = {"embed": "tok_embeddings", "final_norm": "norm", "lm_head": "output",
        "layers/wq": "layers/wq", "layers/wk": "layers/wk",
        "layers/wv": "layers/wv", "layers/wo": "layers/wo",
        "layers/w_gate": "layers/w1", "layers/w_up": "layers/w3",
        "layers/w_down": "layers/w2",
        "layers/attn_norm": "layers/attention_norm",
        "layers/mlp_norm": "layers/ffn_norm"}


# ---------------------------------------------------------------------------
# parent: only the clock
# ---------------------------------------------------------------------------

def parent(cell, args, child, t_start: float, deadline: float):
    child.expect("window_open", timeout=deadline - time.monotonic())
    setup_s = time.monotonic() - t_start
    log(f"window opens; setup_s={setup_s:.1f}")
    res = child.expect("result", timeout=deadline - time.monotonic())
    info = res["info"]
    log(f"reference {info['reference_s']}s; compared {info['compared']}")
    e2e = dict(res["end_to_end"], setup_s=setup_s)
    log(f"end to end: {e2e}; {res['steps']} steps in {res['window']}")
    return {"end_to_end": e2e, "numbers": res["numbers"],
            "attempted": res["steps"], "failed": res["failed"],
            "steps": res["records"], "window": res["window"],
            "memory_peak_bytes": res["memory_peak_bytes"],
            "trace": res.get("trace"), "config": cell.config,
            "traffic": cell.traffic, "chips": cell.chips}


# ---------------------------------------------------------------------------
# child
# ---------------------------------------------------------------------------

def write_corpus(path: str, seed: int, n: int, vocab: int) -> np.ndarray:
    """Flat little-endian uint32 token ids, uniform over the vocabulary:
    the loader's file format; every row cropped from it differs."""
    tokens = np.random.default_rng([seed, 0xC0A9]).integers(
        0, vocab, size=n, dtype=np.uint32)
    tokens.tofile(path)
    return tokens


def loader_rows(corpus: np.ndarray, seed: int, batch: int, seq: int,
                steps: int) -> list[np.ndarray]:
    """The reference's twin of the token loader, from its documented rule:
    crop starts are successive xorshift64* draws (Vigna) from the seed,
    modulo (len - seq), one per row, rows in order."""
    mask = (1 << 64) - 1
    s = seed if seed else 0x9E3779B97F4A7C15
    out = []
    for _ in range(steps):
        rows = np.empty((batch, seq), np.int32)
        for b in range(batch):
            s ^= s >> 12
            s = (s ^ (s << 25)) & mask
            s ^= s >> 27
            start = ((s * 2685821657736338717) & mask) % (len(corpus) - seq)
            rows[b] = corpus[start:start + seq].astype(np.int32)
        out.append(rows)
    return out


def trainer_config(cell, seed: int, corpus_path: str) -> dict:
    c, mix = cell.config, cell.traffic
    model = dict(vocab_size=c["vocab_size"], d_model=c["hidden_size"],
                 n_layers=c["num_hidden_layers"],
                 n_heads=c["num_attention_heads"],
                 n_kv_heads=c["num_key_value_heads"],
                 d_ff=c["intermediate_size"], max_seq_len=mix["seq_len"],
                 rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
                 **c["system"]["model_overrides"])
    return {"model": "llama", "model_overrides": model,
            "batch_size": mix["batch_size"], "seed": seed,
            "num_steps": mix["num_steps"], "log_every": 1,
            "mesh": c["system"]["mesh"],
            "dataset": {"type": "token_file", "path": corpus_path,
                        "seq_len": mix["seq_len"]},
            "optimizer": mix["optimizer"]}


class StateTap:
    """Reads the train state as the first steps leave it, through the step
    the Trainer compiles: nothing the step computes changes, and after the
    followed steps each call costs one comparison."""

    def __init__(self, cfg: dict, seed: int, follow: int, b1: float,
                 fault=None):
        self.cfg, self.seed, self.follow, self.b1 = cfg, seed, follow, b1
        self.calls = 0
        self.first_grad = None      # {leaf: norm} on the device
        self.change = None
        self.fault = fault          # tests only: break the step underneath

    def install(self):
        from kubeflow_tpu.training.trainer import Trainer

        tap, orig = self, Trainer.compiled_step

        def compiled_step(trainer, state, example_batch):
            step = orig(trainer, state, example_batch)
            if getattr(step, "_bench_tap", None) is tap:
                return step
            if tap.fault is not None:
                step = tap.fault(step)

            def tapped(state, batch):
                new_state, metrics = step(state, batch)
                tap.calls += 1
                if tap.calls == 1:
                    tap.first_grad = tap._first_moment_norms(new_state)
                if tap.calls == tap.follow:
                    tap.change = tap._change_norms(new_state)
                return new_state, metrics

            tapped._bench_tap = tap
            trainer._jit_step = tapped
            return tapped

        Trainer.compiled_step = compiled_step
        self._restore = lambda: setattr(Trainer, "compiled_step", orig)

    def remove(self):
        self._restore()

    def _first_moment_norms(self, state):
        import jax
        import optax

        from reference import mistral as ref

        adam = [s for s in jax.tree.leaves(
            state["opt_state"],
            is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState))
            if isinstance(s, optax.ScaleByAdamState)]
        (adam,) = adam
        return jax.jit(ref.tree_norms)(adam.mu)

    def _change_norms(self, state):
        """Per-leaf norm of (parameters now - initial parameters); the
        initial ones are drawn again by the reference's init inside the same
        program, so they are never held whole."""
        import jax

        from reference import mistral as ref

        cfg = self.cfg

        def change(params, seed):   # the seed traced: one program for all
            p0 = flat(ref.init_params(seed, cfg))
            mine = flat(params)
            return ref.tree_norms({k: mine[k] - p0[LEAF[k]] for k in mine})

        return jax.jit(change)(state["params"], self.seed)

    def readings(self) -> dict:
        import jax

        get = lambda t: {k: float(v) for k, v in jax.device_get(t).items()}
        return {"first_grad": {k: v / (1.0 - self.b1)
                               for k, v in get(self.first_grad).items()},
                "change": get(self.change)}


def flat(params: dict) -> dict:
    """{"embed": .., "layers/wq": ..}: the program's tree and the
    reference's have the same two levels."""
    out = {k: v for k, v in params.items() if k != "layers"}
    out.update({"layers/" + k: v for k, v in params["layers"].items()})
    return out


def reference_steps(cfg: dict, seed: int, rows: list[np.ndarray], opt: dict,
                    devices, lower: str | None = None,
                    fault: str | None = None) -> dict:
    """The plain reference through the same steps on the same rows, its
    parameters and moments sharded over the cell's chips so that float32
    fits, one row per chip at a time. Returns per step the loss and the
    gradient's global norm, and per leaf the first gradient as the optimizer
    gets it (clipped) and the change after the last step.

    `lower` (the control) and `fault` (a planted fault: "half_batch" leaves
    out every second row and takes the mean over the rest; "no_exchange"
    keeps only the first data shard's rows, as a gradient that is not
    reduced across shards would) are for prove_train.py and the tests."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from reference import mistral as ref

    n = len(devices)
    mesh = Mesh(np.array(devices), ("x",))

    def shard_of(x):
        for axis in (1, 0):   # a stacked leaf's rows, else the leaf's own
            if x.ndim > axis and x.ndim >= 2 and x.shape[axis] % n == 0:
                return NamedSharding(mesh, P(*([None] * axis + ["x"])))
        return NamedSharding(mesh, P())

    # the seed is an argument of every program that draws from it, never a
    # constant in one: a new seed must find its programs in the cache
    init = lambda s: ref.init_params(s, cfg)
    psh = jax.tree.map(shard_of, jax.eval_shape(init, seed))
    params = jax.jit(init, out_shardings=psh)(seed)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=psh)
    mu, nu = zeros(params), zeros(params)
    row_sh = NamedSharding(mesh, P("x", None))

    # float32 parameters, gradient and both moments are 4 x 2 GB a chip at
    # the cell's size, so nothing is held twice: the gradient accumulates
    # in place, and the initial parameters are drawn again at the end
    @functools.partial(jax.jit, donate_argnums=(1,),
                       out_shardings=(None, psh))
    def grad_rows(params, acc, toks):
        s, g = jax.value_and_grad(
            lambda p: ref.loss_sum(p, toks, cfg, lower)[0])(params)
        return s, jax.tree.map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0,),
                       out_shardings=(psh, None, None))
    def finish(grads, count):
        grads = jax.tree.map(lambda g: g / count, grads)
        clipped, gn = ref.clip(grads, opt["grad_clip"])
        return clipped, gn, ref.tree_norms(clipped)

    out = {"loss": [], "grad_norm": []}
    for t, batch in enumerate(rows, start=1):
        if fault == "half_batch":
            batch = batch[0::2]
        elif fault == "no_exchange":
            batch = batch[: max(1, len(batch) // 2)]
        total, grads, count = 0.0, zeros(params), 0
        for i in range(0, len(batch), n):
            toks = jax.device_put(batch[i:i + n], row_sh)
            s, grads = grad_rows(params, grads, toks)
            total += float(s)
            count += toks.shape[0] * (toks.shape[1] - 1)
        clipped, gn, norms = finish(grads, float(count))
        del grads
        out["loss"].append(total / count)
        out["grad_norm"].append(float(gn))
        if t == 1:
            out["first_grad"] = {k: float(v)
                                 for k, v in jax.device_get(norms).items()}
        upd = jax.jit(lambda p, m, v, g: ref.adamw(p, m, v, g, t, opt),
                      out_shardings=(psh, psh, psh),
                      donate_argnums=(0, 1, 2))
        params, mu, nu = upd(params, mu, nu, clipped)
        del clipped
    del mu, nu
    change = jax.jit(lambda a, s: ref.tree_norms(jax.tree.map(
        jnp.subtract, a, init(s))))(params, seed)
    out["change"] = {k: float(v) for k, v in jax.device_get(change).items()}
    return out


def compare(prog: dict, ref_out: dict) -> dict:
    """The numbers compared (see PERF.md): gaps between the program's and
    the reference's readings. Per-leaf gaps are of norms, measured against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger, worst leaf taken. Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone and are left
    out of the change."""
    k = len(ref_out["loss"])
    loss = max(abs(p - r) / abs(r)
               for p, r in zip(prog["loss"][:k], ref_out["loss"]))
    gnorm = max(abs(p - r) / abs(r)
                for p, r in zip(prog["grad_norm"][:k], ref_out["grad_norm"]))
    g_ref = ref_out["first_grad"]
    g_med = statistics.median(g_ref.values())

    def worst(mine: dict, theirs: dict, leaves) -> float:
        med = statistics.median(theirs[LEAF[l]] for l in leaves)
        return max(abs(mine[l] - theirs[LEAF[l]])
                   / max(theirs[LEAF[l]], med) for l in leaves)

    leaves = list(prog["first_grad"])
    moved = [l for l in leaves if g_ref[LEAF[l]] >= 1e-3 * g_med]
    return {"loss_gap_max": loss, "grad_norm_gap_max": gnorm,
            "first_grad_leaf_gap": worst(prog["first_grad"], g_ref, leaves),
            "param_change_leaf_gap": worst(prog["change"],
                                           ref_out["change"], moved)}


def read_records(path: str) -> list[dict]:
    out = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass    # a line half written
    return out


def run_job(ctx, seed: int, seconds: float, trace: bool, fault=None,
            on_open=lambda: None) -> dict:
    """Apply the JAXJob, let it train through the followed steps and the
    window, delete it, free the devices. Returns what the program gave:
    its step records, the tap's readings, the peak memory, the trace."""
    from kubeflow_tpu.api.platform import Platform
    from kubeflow_tpu.api.specs import jaxjob
    from kubeflow_tpu.control.conditions import has_condition

    cell = ctx.cell
    cfg, mix = cell.config, cell.traffic
    follow = int(mix["follow_steps"])
    root = os.path.join(ctx.tmp, f"job-{seed}")
    os.makedirs(root, exist_ok=True)
    corpus_path = os.path.join(root, "corpus.bin")
    corpus = write_corpus(corpus_path, seed, int(mix["corpus_tokens"]),
                          cfg["vocab_size"])
    metrics_file = os.path.join(root, "metrics.jsonl")
    tap = StateTap(cfg, seed, follow, mix["optimizer"]["b1"], fault=fault)
    tap.install()
    platform = Platform(n_devices=cell.chips, root=root,
                        components=("training",)).start()
    name = cfg["system"]["name"]
    try:
        platform.apply(jaxjob(
            name, target="trainer", backend="thread", tpu=cell.chips,
            restart_policy="Never", backoff_limit=0,
            env={"KTPU_TRAINER_CONFIG": json.dumps(
                     trainer_config(cell, seed, corpus_path)),
                 "KTPU_METRICS_FILE": metrics_file}))

        def wait_for(key: str, least: float, timeout: float) -> list[dict]:
            """The records, once the newest one's `key` has reached
            `least`."""
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                recs = read_records(metrics_file)
                if recs and recs[-1][key] >= least:
                    return recs
                status = platform.get("JAXJob", name).get("status", {})
                if has_condition(status, "Failed") or ctx.watch.errors:
                    raise RuntimeError(
                        f"JAXJob failed: {status} {ctx.watch.errors}\n"
                        + platform.job_logs(name)[-3000:])
                time.sleep(0.005)
            raise RuntimeError(
                f"{key} {least} not reached in {timeout:.0f}s")

        open_step = follow + 1
        recs = wait_for("step", open_step, 1100)
        ts_open = recs[open_step - 1]["ts"]
        on_open()
        if trace:
            ctx.trace_start(float(mix.get("trace_seconds", seconds)))
        while time.time() < ts_open + seconds:
            time.sleep(0.01)
        if trace:
            ctx.trace_stop()
        recs = wait_for("ts", ts_open + seconds, 60)
        ts_close = next(r["ts"] for r in recs if r["ts"] >= ts_open + seconds)
        peak = ctx.memory_peak_bytes()
        platform.delete("JAXJob", name)
        # the worker honours its cancel event between steps: wait until the
        # pod is gone AND its thread (executor: "pod-<name>") has ended
        import threading

        quiet = time.monotonic() + 120
        while time.monotonic() < quiet and (platform.list("Pod") or any(
                t.name.startswith("pod-") for t in threading.enumerate())):
            time.sleep(0.05)
        recs = read_records(metrics_file)
    finally:
        platform.stop()
        tap.remove()
    readings = tap.readings()
    tap.first_grad = tap.change = None
    freed = ctx.free_device()
    prog = dict(readings,
                loss=[r["metrics"]["loss"] for r in recs[:follow]],
                grad_norm=[r["metrics"]["grad_norm"] for r in recs[:follow]])
    return {"program": prog, "records": recs, "ts_open": ts_open,
            "ts_close": ts_close,
            "memory_peak_bytes": peak, "freed_bytes": freed,
            "corpus": corpus,
            "trace": ctx.trace_reduce() if trace else None}


def child(ctx, fault=None) -> None:
    cell, link, args = ctx.cell, ctx.link, ctx.args
    cfg, mix = cell.config, cell.traffic
    seed = weight_seed(args.seed)
    try:
        job = run_job(ctx, seed, args.seconds, bool(args.trace), fault=fault,
                      on_open=lambda: link.say("window_open"))
        ts_open, ts_close = job["ts_open"], job["ts_close"]
        inside = [r for r in job["records"] if ts_open < r["ts"] <= ts_close]
        span = ts_close - ts_open
        tokens = len(inside) * mix["batch_size"] * mix["seq_len"]
        e2e = {"train_tokens_per_s_per_chip": tokens / span / cell.chips}
        t = time.monotonic()
        rows = loader_rows(job["corpus"], seed, mix["batch_size"],
                           mix["seq_len"], int(mix["follow_steps"]))
        ref_out = reference_steps(cfg, seed, rows, mix["optimizer"],
                                  ctx.devices)
        got = compare(job["program"], ref_out)
        numbers = {k: {"value": v, "limit": mix["limits"][k]}
                   for k, v in got.items() if k in mix["limits"]}
        link.say("result", end_to_end=e2e, numbers=numbers,
                 steps=len(inside), failed=0,
                 memory_peak_bytes=job["memory_peak_bytes"],
                 trace=job["trace"],
                 window={"ts_open": ts_open, "span_s": span,
                         "seconds": args.seconds},
                 records=[{"step": r["step"], "ts": r["ts"],
                           "step_time_s": r["metrics"]["step_time_s"]}
                          for r in inside],
                 info={"compared": got, "program": job["program"],
                       "reference": ref_out,
                       "freed_bytes": job["freed_bytes"],
                       "reference_s": round(time.monotonic() - t, 1)})
        for cmd in link.commands():
            if cmd["kind"] == "quit":
                break
    finally:
        ctx.close()

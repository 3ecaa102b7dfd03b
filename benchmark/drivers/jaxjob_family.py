"""A JAXJob of ANY registered model family that trains for the window and is
then deleted: what `jaxjob_window` does for the one family it was written
for, with everything that names a family read from the cell's files.

  - the model's name and its overrides come from the configuration's `system`
    (`model`, `model_keys`: the published keys handed through under their own
    names, `model_overrides`: the program's own options);
  - the plain reference is `reference/<family>.py`, which also names each
    program leaf's twin (`leaf_of`) and, where it has them, the planted
    faults of its own (`fault=`);
  - EVERY key of a step's metrics is kept in the records the readers get, and
    the counters the mix lists under `counters` and limits under `limits` are
    compared as their largest value over the job's steps (a counter that has
    to stay 0, such as rows a router dropped);
  - a traced run also splits the device's time by the `jax.named_scope`s the
    configuration's `system.scopes` lists (lib/xscopes.py), under
    `trace["scopes"]`, for the readers of a scope's share.

Window, tap and comparison are `jaxjob_window`'s (see its docstring): the
window opens with the step after the followed ones and closes with the first
step that completes --seconds later; a tap on the Trainer's compiled step
reads the first moment after step 1 and the parameters' change after the
last followed step; the reference follows the same steps on the same rows.
"""

from __future__ import annotations

import functools
import glob
import importlib
import json
import os
import statistics
import time

import numpy as np

from drivers.jaxjob_window import (StateTap, loader_rows, parent,  # noqa: F401
                                   read_records, write_corpus)
from lib.harness import log, weight_seed


def reference_of(cfg: dict):
    return importlib.import_module(f"reference.{cfg['family']}")


def trainer_config(cell, seed: int, corpus_path: str) -> dict:
    c, mix = cell.config, cell.traffic
    system = c["system"]
    model = {k: c[k] for k in system["model_keys"]}
    model.update(system["model_overrides"])
    return {"model": system["model"], "model_overrides": model,
            "batch_size": mix["batch_size"], "seed": seed,
            "num_steps": mix["num_steps"], "log_every": 1,
            "mesh": system["mesh"],
            "dataset": {"type": "token_file", "path": corpus_path,
                        "seq_len": mix["seq_len"]},
            "optimizer": mix["optimizer"]}


class FamilyTap(StateTap):
    """`StateTap` with the family's own reference: leaf norms by its
    `tree_norms`, the initial parameters by its `init_params`."""

    def _first_moment_norms(self, state):
        import jax
        import optax

        ref = reference_of(self.cfg)
        (adam,) = [s for s in jax.tree.leaves(
            state["opt_state"],
            is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState))
            if isinstance(s, optax.ScaleByAdamState)]
        return jax.jit(ref.tree_norms)(adam.mu)

    def _change_norms(self, state):
        import jax

        ref, cfg = reference_of(self.cfg), self.cfg

        def change(params, seed):   # the seed traced: one program for all
            p0 = flat(ref.init_params(seed, cfg))
            mine = flat(params)
            return ref.tree_norms({k: mine[k] - p0[ref.leaf_of(k)]
                                   for k in mine})

        return jax.jit(change)(state["params"], self.seed)


def flat(params: dict) -> dict:
    """{"a/b/c": leaf} of a tree of dicts, however deep."""
    import jax

    return {"/".join(str(k.key) for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}


def reference_steps(cfg: dict, seed: int, rows: list[np.ndarray], opt: dict,
                    devices, lower: str | None = None,
                    fault: str | None = None) -> dict:
    """The plain reference through the same steps on the same rows, on the
    cell's first chip, a row at a time. Float32 rows of 8192 positions
    leave the chip room for the parameters and two gradient trees (the sum
    and a row's) beside a row's activations, and no more: the two moments
    wait in the host's memory while the gradients are computed and come to
    the chip for the update (the reference module's own `clip` and
    `adamw`). Returns per step the loss and the gradient's global norm, and
    per leaf the first gradient as the optimizer gets it (clipped) and the
    change after the last step.

    `lower` is a control of the reference's; `fault` a planted fault:
    "half_batch" leaves out every second row and takes the mean over the
    rest, "no_exchange" keeps the first data shard's rows, anything else is
    handed to the reference's `loss_sum(fault=)`."""
    import jax
    import jax.numpy as jnp

    ref = reference_of(cfg)
    chip = devices[0]
    own = {} if fault in (None, "half_batch", "no_exchange") else {
        "fault": fault}
    # the seed is an argument of every program that draws from it, never a
    # constant in one: a new seed must find its programs in the cache
    init = lambda s: ref.init_params(s, cfg)
    seed = jax.device_put(np.int32(seed), chip)
    params = jax.jit(init)(seed)

    @jax.jit
    def grad_row(params, toks):
        return jax.value_and_grad(
            lambda p: ref.loss_sum(p, toks, cfg, lower, **own)[0])(params)

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0, 1))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def finish(grads, count):
        grads = jax.tree.map(lambda g: g / count, grads)
        clipped, gn = ref.clip(grads, opt["grad_clip"])
        return clipped, gn, ref.tree_norms(clipped)

    to_host = lambda tree: jax.tree.map(np.array, tree)   # copies, then
    drop = lambda tree: jax.tree.map(lambda x: x.delete(), tree)  # frees
    out = {"loss": [], "grad_norm": []}
    moments = None
    for t, batch in enumerate(rows, start=1):
        if fault == "half_batch":
            batch = batch[0::2]
        elif fault == "no_exchange":
            batch = batch[: max(1, len(batch) // 2)]
        total, grads, count = 0.0, None, 0
        for row in batch:
            s, g = grad_row(params, jax.device_put(row[None], chip))
            grads = g if grads is None else add(grads, g)
            total += float(s)
            count += row.shape[0] - 1
        clipped, gn, norms = finish(grads, np.float32(count))
        out["loss"].append(total / count)
        out["grad_norm"].append(float(gn))
        if t == 1:
            out["first_grad"] = {k: float(v)
                                 for k, v in jax.device_get(norms).items()}
            moments = jax.jit(lambda p: (jax.tree.map(jnp.zeros_like, p),) * 2
                              )(params)
        else:
            moments = jax.device_put(moments, chip)
        params, *moments = jax.jit(
            lambda p, m, v, g: ref.adamw(p, m, v, g, t, opt),
            donate_argnums=(0, 1, 2, 3))(params, *moments, clipped)
        if t < len(rows):
            on_chip, moments = moments, to_host(moments)
            drop(on_chip)
    drop(moments)
    change = jax.jit(lambda a, s: ref.tree_norms(jax.tree.map(
        jnp.subtract, a, init(s))))(params, seed)
    out["change"] = {k: float(v) for k, v in jax.device_get(change).items()}
    drop(params)
    return out


def compare(prog: dict, ref_out: dict, leaf_of) -> dict:
    """`jaxjob_window.compare`'s numbers, the leaves' twins named by the
    family's `leaf_of`: the losses' and the gradient norms' widest gap over
    the followed steps, and per leaf the gap of the first gradient's norm and
    of the change's norm, against the reference's norm of that leaf or of
    the median leaf, whichever is larger, worst leaf taken. Leaves whose
    reference gradient is under a thousandth of the median leaf's (a buffer
    no gradient moves among them) are left out of the change."""
    k = len(ref_out["loss"])
    loss = max(abs(p - r) / abs(r)
               for p, r in zip(prog["loss"][:k], ref_out["loss"]))
    gnorm = max(abs(p - r) / abs(r)
                for p, r in zip(prog["grad_norm"][:k], ref_out["grad_norm"]))
    g_ref = ref_out["first_grad"]
    g_med = statistics.median(g_ref.values())

    def worst(mine: dict, theirs: dict, leaves) -> float:
        med = statistics.median(theirs[leaf_of(l)] for l in leaves)
        return max(abs(mine[l] - theirs[leaf_of(l)])
                   / max(theirs[leaf_of(l)], med) for l in leaves)

    leaves = list(prog["first_grad"])
    moved = [l for l in leaves if g_ref[leaf_of(l)] >= 1e-3 * g_med]
    return {"loss_gap_max": loss, "grad_norm_gap_max": gnorm,
            "first_grad_leaf_gap": worst(prog["first_grad"], g_ref, leaves),
            "param_change_leaf_gap": worst(prog["change"],
                                           ref_out["change"], moved)}


def worst_leaves(mine: dict, ref_out: dict, leaf_of, n: int = 3) -> dict:
    """Per kind of reading the n leaves whose norms differ most, relative to
    the reference's norm of that leaf: which leaf a gap above comes from."""
    out = {}
    for kind in ("first_grad", "change"):
        theirs = ref_out[kind]
        gaps = sorted(((abs(v - theirs[leaf_of(k)])
                        / max(theirs[leaf_of(k)], 1e-30), k)
                       for k, v in mine[kind].items()), reverse=True)
        out[kind] = [[k, float(f"{g:.3g}")] for g, k in gaps[:n]]
    return out


def scope_seconds(ctx) -> dict | None:
    """The capture's device time by the configuration's scopes; to be read
    before `ctx.trace_reduce` deletes the capture. None without a device
    plane (the CPU rehearsal) or without scopes to look for."""
    from lib import xscopes

    scopes = ctx.cell.config["system"].get("scopes")
    if not scopes:
        return None
    (path,) = glob.glob(os.path.join(ctx._trace_dir, "plugins", "profile",
                                     "*", "*.xplane.pb"))
    out = xscopes.scope_seconds(path, scopes)
    log(f"device seconds by scope: {out}")
    return out


def run_job(ctx, seed: int, seconds: float, trace: bool, fault=None,
            on_open=lambda: None) -> dict:
    """Apply the JAXJob, let it train through the followed steps and the
    window, delete it, free the devices (`jaxjob_window.run_job`, with this
    module's `trainer_config` and tap)."""
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
    tap = FamilyTap(cfg, seed, follow, mix["optimizer"]["b1"], fault=fault)
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
    log(f"job gone: freed {freed} bytes, in use now "
        f"{(ctx.devices[0].memory_stats() or {}).get('bytes_in_use')}")
    reduced = None
    if trace:
        scopes = scope_seconds(ctx)
        reduced = dict(ctx.trace_reduce(), scopes=scopes)
    prog = dict(readings,
                loss=[r["metrics"]["loss"] for r in recs[:follow]],
                grad_norm=[r["metrics"]["grad_norm"] for r in recs[:follow]])
    for name in mix.get("counters", []):   # the largest over the job's steps
        prog[name] = max(r["metrics"][name] for r in recs)
    return {"program": prog, "records": recs, "ts_open": ts_open,
            "ts_close": ts_close,
            "memory_peak_bytes": peak, "freed_bytes": freed,
            "corpus": corpus,
            "trace": reduced}


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
        leaf_of = reference_of(cfg).leaf_of
        got = compare(job["program"], ref_out, leaf_of)
        log(f"worst leaves: {worst_leaves(job['program'], ref_out, leaf_of)}")
        got.update({k: job["program"][k] for k in mix.get("counters", [])})
        numbers = {k: {"value": v, "limit": mix["limits"][k]}
                   for k, v in got.items() if k in mix["limits"]}
        link.say("result", end_to_end=e2e, numbers=numbers,
                 steps=len(inside), failed=0,
                 memory_peak_bytes=job["memory_peak_bytes"],
                 trace=job["trace"],
                 window={"ts_open": ts_open, "span_s": span,
                         "seconds": args.seconds},
                 records=[dict(r["metrics"], step=r["step"], ts=r["ts"])
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

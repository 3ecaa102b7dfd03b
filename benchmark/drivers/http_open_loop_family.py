"""Open-loop HTTP load on an InferenceService of ANY served model family:
what `http_open_loop` does for the one family it was written for, with
everything that names a family read from the cell's files.

The load, the clock, the sample and the window are `http_open_loop`'s own
(`parent` and the request sampling are imported from it; see its docstring).
What a served family's driver reads, beyond that:

  - the InferenceService comes from the configuration's `system`:
    `modelFormat`, `config` (the engine's options), and the model's sizes
    handed through under their PUBLISHED names: `model_keys` lists the keys
    of the configuration that become the `model:` block, `model_overrides`
    the program's own options on top;
  - the plain reference is `reference/<family>.py`. It gives
    `hidden(seed, tokens, cfg, lower=None, fault=None)` (the final-normed
    activations, weights drawn from the seed by its own code), `ends(seed,
    cfg)` and `head(ends, hidden, cfg, lower=None)`; the logits of one
    sequence at a time are all that is ever held;
  - EVERY key of `engine.metrics()` is kept in the counters the readers get
    (`counters["before"|"after"]`), beside the supervisor's books and the
    compile events;
  - the counters the mix names under `counters_zero` are compared too, each
    as its growth over the run, limit 0 (rows a router dropped);
  - a traced run also splits the device's time by the `jax.named_scope`s the
    configuration's `system.scopes` lists (lib/xscopes.py), under
    `trace["scopes"]`, for the readers of a scope's share.

`correct`: `served_logit_gap_max` as `http_open_loop` defines it (the widest
gap by which a served token's reference logit lies under the reference's
best at its position, the reference making ONE full forward pass over prompt
+ served tokens); where the mix's `limits` name it,
`served_tokens_off_best_share_max` (of the served tokens judged, the share
in percent that is not the reference's best at its position: where one
rounding can flip a discrete choice, as a router's, the WIDEST gap is that
flip's size whatever the precision, and the share of positions that moved is
what tells one precision from the next); `failed_requests` 0 and the
`counters_zero`.

The control (`lower=`) and the planted faults (`fault=`) stand in the
program's place, as `prove_family.py`'s do: the tokens judged are those the
lowered or faulty reference puts first at the same positions, nothing being
decoded. Faults that exist only in the program (a cache ring with no room
for a chunk) are planted under it by `BENCH_FAMILY_FAULT=<name>`, which
`prove_serve_family.py` and the tests set and a benchmark run never does.
"""

from __future__ import annotations

import importlib
import os
import time

from drivers.http_open_loop import (parent, served_model,  # noqa: F401
                                    verify_sample, warm_over_http)
from drivers.jaxjob_family import scope_seconds
from lib.harness import log, weight_seed


def reference_of(cfg: dict):
    return importlib.import_module(f"reference.{cfg['family']}")


def inference_service(cell, seed: int) -> dict:
    """The InferenceService a user would apply; weights random from the
    seed (there is no checkpoint to load)."""
    c = cell.config
    system = c["system"]
    model = {k: c[k] for k in system["model_keys"]}
    model.update(system.get("model_overrides") or {})
    config = dict(system["config"], model=model, seed=seed)
    return {"apiVersion": "kubeflow-tpu/v1", "kind": "InferenceService",
            "metadata": {"name": system["name"]},
            "spec": {"predictor": {"minReplicas": 1, "model": {
                "modelFormat": system["modelFormat"], "config": config}}}}


def served_gaps(cfg: dict, seed: int, samples: list[dict],
                lower: str | None = None, fault: str | None = None,
                pad_to: int = 128) -> dict:
    """The plain reference over each prompt with its served tokens. For
    every served token: how far its reference logit lies under the
    reference's best at that position. With `lower` (the control) or
    `fault` the tokens judged are those the lowered or faulty reference
    puts first at the same positions."""
    import jax
    import jax.numpy as jnp

    ref = reference_of(cfg)
    longest = max(len(s["prompt"]) + len(s["tokens"]) for s in samples)
    t = -(-longest // pad_to) * pad_to     # one shape; causal: pad unseen
    toks = jnp.asarray([(s["prompt"] + s["tokens"]
                         + [0] * t)[:t] for s in samples], jnp.int32)
    hidden = ref.hidden(seed, toks, cfg)
    stood_in = None
    if lower is not None or fault is not None:
        stood_in = ref.hidden(seed, toks, cfg, lower=lower, fault=fault)

    @jax.jit
    def gaps_of(ends, h, h_in, toks, first, n):
        lg = ref.head(ends, h, cfg)                          # [T, V]
        pos = jnp.arange(toks.shape[0])
        judged = (toks[jnp.minimum(pos + 1, toks.shape[0] - 1)]
                  if h_in is None
                  else jnp.argmax(ref.head(ends, h_in, cfg, lower), -1))
        gap = lg.max(-1) - jnp.take_along_axis(lg, judged[:, None], -1)[:, 0]
        served = (pos >= first) & (pos < first + n)
        return (jnp.max(jnp.where(served, gap, 0.0)),
                jnp.sum(jnp.where(served, gap == 0.0, False)))

    ends = ref.ends(seed, cfg)
    widest, agree, total = 0.0, 0, 0
    per_request, per_request_off = [], []
    for i, s in enumerate(samples):
        g, a = gaps_of(ends, hidden[i],
                       None if stood_in is None else stood_in[i], toks[i],
                       len(s["prompt"]) - 1, len(s["tokens"]))
        per_request.append(float(g))
        per_request_off.append(len(s["tokens"]) - int(a))
        widest = max(widest, float(g))
        agree += int(a)
        total += len(s["tokens"])
    return {"widest_gap": widest, "per_request": per_request,
            "per_request_off_best": per_request_off,
            "per_request_tokens": [len(s["tokens"]) for s in samples],
            "tokens_judged": total, "tokens_agreeing": agree,
            "off_best_share": 100.0 * (total - agree) / max(total, 1)}


def engine_counters(platform, name: str, ctx) -> dict:
    """EVERY count the engine keeps (metrics()), the supervisor's books and
    the compile events seen so far."""
    model = served_model(platform, name)
    out = dict(model.supervisor.engine.metrics())
    out["prefill_tokens_saved"] = (out.get("prefix_cache") or {}).get(
        "prefill_tokens_saved")
    books = model.supervisor.accounting()
    out["supervisor"] = {k: books.get(k) for k in
                         ("accepted", "completed", "restarts", "lost")}
    out["outages"] = len(books.get("outages") or [])
    out.update(ctx.meter.snapshot())
    out["logged_errors"] = list(ctx.watch.errors[-3:])
    return out


# -- faults that exist only in the program -----------------------------------

def _plant_ring_window_only(family) -> None:
    """The sliding layers' ring holds the window alone (rounded up to the
    KV block): no room for a prefill chunk's rows, so the junk rows past a
    prompt's end land on rows a query still sees. (A ring ONE block short
    of window + the largest bucket is still sound where no bucket pads a
    prompt by more than a block less than itself: 512 + 511 < 1024.)"""
    from kubeflow_tpu.ops import flash_decode

    def window_only(cfg, chunk, max_len):
        block = min(flash_decode.DEFAULT_BLOCK_KV, max_len)
        return min(-(-cfg.sliding_window // block) * block, max_len)
    family.ring_rows = window_only


PROGRAM_FAULTS = {"ring_window_only": _plant_ring_window_only}


def plant_program_fault(cfg: dict) -> str | None:
    name = os.environ.get("BENCH_FAMILY_FAULT")
    if name:
        PROGRAM_FAULTS[name](importlib.import_module(
            f"kubeflow_tpu.models.{cfg['family']}"))
        log(f"PLANTED under the program: {name}")
    return name


def child(ctx, fault=None) -> None:
    from kubeflow_tpu.api.platform import Platform
    from kubeflow_tpu.control.conditions import has_condition

    cell, link = ctx.cell, ctx.link
    plant_program_fault(cell.config)
    seed = weight_seed(ctx.args.seed)
    isvc = inference_service(cell, seed)
    name = isvc["metadata"]["name"]
    platform = Platform(n_devices=cell.chips, root=ctx.tmp,
                        components=("serving",)).start()
    try:
        t0 = time.monotonic()
        platform.apply(isvc)
        obj = platform.wait(
            "InferenceService", name,
            lambda o: bool(ctx.watch.errors) or any(
                has_condition(o.get("status", {}), c)
                for c in ("Ready", "Failed")), timeout=1100)
        if not has_condition(obj["status"], "Ready") or ctx.watch.errors:
            raise RuntimeError(f"InferenceService not Ready: "
                               f"{obj.get('status')} {ctx.watch.errors}")
        setup = {"ready_s": round(time.monotonic() - t0, 1),
                 "import_s": round(t0 - ctx.t0, 1), **ctx.meter.snapshot()}
        link.say("ready", url=obj["status"]["url"], model=name, setup=setup)
        zero_at_ready = {k: engine_counters(platform, name, ctx).get(k) or 0
                         for k in cell.traffic.get("counters_zero", [])}
        for cmd in link.commands():
            kind = cmd["kind"]
            if kind == "counters":
                link.say("counters", **engine_counters(platform, name, ctx))
            elif kind == "trace_start":
                ctx.trace_start(float(cell.traffic.get(
                    "trace_seconds", ctx.args.seconds)))
                link.say("trace_start")
            elif kind == "trace_stop":
                ctx.trace_stop()
                link.say("trace_stop")
            elif kind == "verify":
                peak = ctx.memory_peak_bytes()
                last = engine_counters(platform, name, ctx)
                model = served_model(platform, name)
                platform.stop()
                model.unload()   # stops the engine loop and its supervisor
                del model
                freed = ctx.free_device()
                # the capture by scope, before the reduction deletes it
                scopes = (scope_seconds(ctx) if ctx._trace_dir
                          and not ctx.args.no_chip else None)
                trace = ctx.trace_reduce()
                if trace is not None:
                    trace["scopes"] = scopes
                t = time.monotonic()
                pad = cell.traffic["verify_pad_tokens"]
                res = served_gaps(cell.config, seed, cmd["samples"],
                                  pad_to=pad)
                limits = cell.traffic["limits"]
                numbers = {"served_logit_gap_max": {
                    "value": res["widest_gap"],
                    "limit": limits["served_logit_gap_max"]}}
                if "served_tokens_off_best_share_max" in limits:
                    numbers["served_tokens_off_best_share_max"] = {
                        "value": res["off_best_share"],
                        "limit": limits["served_tokens_off_best_share_max"]}
                for k, at_ready in zero_at_ready.items():
                    numbers[k] = {"value": (last.get(k) or 0) - at_ready,
                                  "limit": 0}
                info = dict(res, freed_bytes=freed)
                # prove_serve_family.py only, never a timed run
                for key, names in (("lower", cmd.get("controls") or []),
                                   ("fault", cmd.get("faults") or [])):
                    for n in names:
                        info[f"{key}_{n}"] = served_gaps(
                            cell.config, seed, cmd["samples"], pad_to=pad,
                            **{key: n})
                info["reference_s"] = round(time.monotonic() - t, 1)
                link.say("verify", memory_peak_bytes=peak, trace=trace,
                         numbers=numbers, info=info)
            elif kind == "quit":
                break
    finally:
        platform.stop()
        ctx.close()

"""Open-loop HTTP load on an InferenceService: independent users of a
completions endpoint.

Entry the window drives: POST {status.url}/openai/v1/completions through
the router, `stream: true`, token ids as the prompt, greedy. Each request
is sent at its due instant whatever the system does (one thread each, no
concurrency cap), and every latency is taken from the instant it was DUE,
so a stall is charged to the requests it delays. How late the generator
itself ran is reported.

Sample and window: the requests due inside the window are the sample;
`serve_out_tokens_per_s` counts the tokens delivered inside it. After the
close the rest of each answer is waited for (a minute at most): late is
late, not wrong.

`correct`: a sample of the finished requests, drawn from the seed, with
the longest in it, goes to the child, which has by then read the peak
memory and deleted the program's state. The plain reference runs once over
each prompt with its served tokens; the number compared is the widest gap
by which a served token's logit lies under the reference's best at its
position. Nothing that changes with who shared a wave enters it.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
import urllib.parse

import numpy as np

from lib import stats, traffic
from lib.harness import log, weight_seed

PATH = "/openai/v1/completions"
DRAIN_S = 60.0


# ---------------------------------------------------------------------------
# parent: the load generator (no JAX)
# ---------------------------------------------------------------------------

def stream_one(host: str, port: int, model: str, req: traffic.Request,
               rec: dict, timeout_s: float) -> None:
    """One streamed completion (after loadgen/http_client.stream_completion);
    fills `rec` with the instant sent, every token's id and arrival instant,
    the usage object and any error."""
    body = json.dumps({"model": model, "prompt": req.prompt,
                       "max_tokens": req.max_tokens, "temperature": 0.0,
                       "stream": True}).encode()
    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    resp = None
    try:
        rec["sent"] = time.monotonic()
        conn.request("POST", PATH, body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["errors"].append(resp.read()[-300:].decode("utf-8", "replace"))
            return
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            now = time.monotonic()
            data = line[6:].strip()
            if data == b"[DONE]":
                rec["done"] = now
                break
            chunk = json.loads(data)
            if "error" in chunk:
                rec["errors"].append(str(chunk["error"])[-300:])
                continue
            if chunk.get("usage") is not None:
                rec["usage"] = chunk["usage"]
            for ch in chunk.get("choices", ()):
                if ch.get("token_id") is not None:
                    rec["token_ids"].append(int(ch["token_id"]))
                    rec["token_at"].append(now)
                if ch.get("finish_reason"):
                    rec["finish_reason"] = ch["finish_reason"]
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec["errors"].append(f"{type(e).__name__}: {e}")
    finally:
        if resp is not None:
            resp.close()
        conn.close()


def new_record(req: traffic.Request, due_abs: float) -> dict:
    return {"index": req.index, "due": due_abs, "sent": None, "status": None,
            "token_ids": [], "token_at": [], "usage": None, "done": None,
            "finish_reason": None, "errors": [], "prompt": req.prompt,
            "max_tokens": req.max_tokens}


def ok(rec: dict) -> bool:
    return (rec["status"] == 200 and not rec["errors"]
            and rec["done"] is not None
            and len(rec["token_ids"]) == rec["max_tokens"])


def offer(url: str, model: str, reqs: list[traffic.Request], t_open: float,
          seconds: float) -> tuple[list[dict], list[threading.Thread]]:
    """Send each request at its due instant; returns at the window's close
    with the records (still filling) and the threads to drain."""
    u = urllib.parse.urlparse(url)
    recs, threads = [], []
    for req in reqs:
        due_abs = t_open + req.due_s
        wait = due_abs - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        rec = new_record(req, due_abs)
        th = threading.Thread(
            target=stream_one, daemon=True,
            args=(u.hostname, u.port, model, req, rec, seconds + DRAIN_S))
        th.start()
        recs.append(rec)
        threads.append(th)
    left = t_open + seconds - time.monotonic()
    if left > 0:
        time.sleep(left)
    return recs, threads


def drain(threads: list[threading.Thread], close_at: float) -> None:
    """Wait for each answer, DRAIN_S past the close at most."""
    for th in threads:
        th.join(max(0.0, close_at + DRAIN_S - time.monotonic()))


def end_to_end(recs: list[dict], t_open: float, seconds: float) -> dict:
    ttft = [(r["token_at"][0] - r["due"]) * 1e3 for r in recs
            if r["token_at"]]
    gaps = [(b - a) * 1e3 for r in recs
            for a, b in zip(r["token_at"], r["token_at"][1:])]
    in_window = sum(1 for r in recs for t in r["token_at"]
                    if t_open <= t <= t_open + seconds)
    return {"ttft_p95_ms": stats.percentile(ttft, 95),
            "token_gap_p95_ms": stats.percentile(gaps, 95),
            "serve_out_tokens_per_s": in_window / seconds}


def verify_sample(recs: list[dict], seed: int, n: int) -> list[dict]:
    """Finished requests for the reference: the longest, and others drawn
    from the seed."""
    done = [r for r in recs if ok(r)]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["token_ids"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0x5A3F])
    picks = [rest[i] for i in rng.permutation(len(rest))[:max(0, n - 1)]]
    return [{"index": r["index"], "prompt": r["prompt"],
             "tokens": r["token_ids"]} for r in [longest] + picks]


def warm_over_http(url: str, model: str, mix: dict, vocab: int) -> None:
    """The engine's own warm-up compiles the program menu; this sends one
    wave per bucket through the whole HTTP path so that the first timed
    request meets nothing cold (imports, sockets, banking programs)."""
    rng = np.random.default_rng(0)
    reqs = [traffic.Request(i, 0.0, rng.integers(1, vocab, size=n).tolist(),
                            int(mix.get("warmup_max_tokens", 12)))
            for i, n in enumerate(mix.get("warmup_prompt_tokens", []))]
    if reqs:
        t = time.monotonic()
        recs, threads = offer(url, model, reqs, t, 0.0)
        drain(threads, t)
        bad = [r["errors"] or r["status"] for r in recs if not ok(r)]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad[:3]}")


def parent(cell, args, child, t_start: float, deadline: float):
    mix, vocab = cell.traffic, cell.config["vocab_size"]
    reqs = traffic.make_requests(mix, args.seed, args.seconds, vocab)
    ready = child.expect("ready", timeout=deadline - time.monotonic())
    url, model = ready["url"], ready["model"]
    warm_over_http(url, model, mix, vocab)
    before = child.ask("counters", 60)
    if args.trace:
        child.ask("trace_start", 120)
    t_open = time.monotonic()
    setup_s = t_open - t_start
    log(f"window opens: {len(reqs)} requests over {args.seconds}s; "
        f"setup_s={setup_s:.1f} (child: {ready['setup']})")
    recs, threads = offer(url, model, reqs, t_open, args.seconds)
    if args.trace:
        child.ask("trace_stop", 300)   # at the close; reduced after the drain
    drain(threads, t_open + args.seconds)
    after = child.ask("counters", 60)
    if after["compiles"] != before["compiles"]:
        log(f"compiled inside the window: {after['last_compiled']}")
    failed = [r for r in recs if not ok(r)]
    for r in failed[:5]:
        log(f"failed request {r['index']}: status={r['status']} "
            f"tokens={len(r['token_ids'])}/{r['max_tokens']} {r['errors']}")
    e2e = end_to_end(recs, t_open, args.seconds)
    e2e["setup_s"] = setup_s
    log(f"end to end: {e2e}")
    sample = verify_sample(recs, args.seed, int(mix["verify_requests"]))
    ver = child.ask("verify", 900, samples=sample)
    numbers = dict(ver["numbers"])
    numbers["failed_requests"] = {"value": len(failed), "limit": 0}
    log(f"reference: {ver['info']}")
    return {"end_to_end": e2e, "numbers": numbers, "attempted": len(recs),
            "failed": len(failed), "requests": recs,
            "counters": {"before": before, "after": after},
            "window": {"t_open": t_open, "seconds": args.seconds},
            "memory_peak_bytes": ver["memory_peak_bytes"],
            "trace": ver.get("trace"), "config": cell.config,
            "traffic": mix}


# ---------------------------------------------------------------------------
# child: the system under test, then the reference (owns the chip)
# ---------------------------------------------------------------------------

def inference_service(cell, seed: int) -> dict:
    """The InferenceService a user would apply: the configuration's
    `system.config` block with the model's sizes; weights random from the
    seed (there is no checkpoint to load)."""
    c = cell.config
    model = dict(vocab_size=c["vocab_size"], d_model=c["hidden_size"],
                 n_layers=c["num_hidden_layers"],
                 n_heads=c["num_attention_heads"],
                 n_kv_heads=c["num_key_value_heads"],
                 d_ff=c["intermediate_size"],
                 max_seq_len=c["system"]["config"]["max_len"],
                 rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"])
    config = dict(c["system"]["config"], model=model, seed=seed)
    return {"apiVersion": "kubeflow-tpu/v1", "kind": "InferenceService",
            "metadata": {"name": c["system"]["name"]},
            "spec": {"predictor": {"minReplicas": 1, "model": {
                "modelFormat": c["system"]["modelFormat"],
                "config": config}}}}


def served_gaps(cfg: dict, seed: int, samples: list[dict],
                lower: str | None = None, pad_to: int = 128) -> dict:
    """The plain reference over each prompt with its served tokens. For
    every served token: how far its reference logit lies under the
    reference's best at that position. With `lower` (the control) the
    tokens judged are those the lower precision puts first at the same
    positions, nothing being decoded."""
    import jax
    import jax.numpy as jnp

    from reference import mistral as ref

    # the seed an argument, not a constant: one program for every seed
    params = jax.jit(lambda s: ref.init_params(s, cfg))(seed)

    @jax.jit
    def gaps_of(params, toks, first, n):
        lg = ref.logits(params, toks[None], cfg)[0]          # [T, V]
        pos = jnp.arange(toks.shape[0])
        judged = (toks[jnp.minimum(pos + 1, toks.shape[0] - 1)]
                  if lower is None else jnp.argmax(
                      ref.logits(params, toks[None], cfg, lower)[0], -1))
        gap = lg.max(-1) - jnp.take_along_axis(lg, judged[:, None], -1)[:, 0]
        served = (pos >= first) & (pos < first + n)
        return (jnp.max(jnp.where(served, gap, 0.0)),
                jnp.sum(jnp.where(served, gap == 0.0, False)))

    widest, agree, total = 0.0, 0, 0
    per_request = []
    for s in samples:
        seq = s["prompt"] + s["tokens"]
        t = -(-len(seq) // pad_to) * pad_to   # one shape; causal: pad unseen
        toks = jnp.asarray(seq + [0] * (t - len(seq)), jnp.int32)
        g, a = gaps_of(params, toks, len(s["prompt"]) - 1,
                       len(s["tokens"]))
        per_request.append(float(g))
        widest = max(widest, float(g))
        agree += int(a)
        total += len(s["tokens"])
    return {"widest_gap": widest, "per_request": per_request,
            "tokens_judged": total, "tokens_agreeing": agree}


def served_model(platform, name: str):
    """The LLMModel behind the predictor (chip_smoke.py's way in)."""
    (inst,) = platform.serving._instances[("default", name, "predictor")]
    return inst.server.repository.get(name)


def engine_counters(platform, name: str, ctx) -> dict:
    """Counts the program keeps (engine metrics(), supervisor accounting)
    and the compile events seen so far."""
    model = served_model(platform, name)
    m = model.supervisor.engine.metrics()
    books = model.supervisor.accounting()
    keep = ("completed", "rejected", "cancelled", "prefix_hits",
            "prefix_misses", "prefill_tokens_computed",
            "decode_attention_impl", "prefill_attention_impl")
    out = {k: m.get(k) for k in keep}
    out["prefill_tokens_saved"] = (m.get("prefix_cache") or {}).get(
        "prefill_tokens_saved")
    out["supervisor"] = {k: books.get(k) for k in
                         ("accepted", "completed", "restarts", "lost")}
    out["outages"] = len(books.get("outages") or [])
    out.update(ctx.meter.snapshot())
    out["logged_errors"] = list(ctx.watch.errors[-3:])
    return out


def child(ctx, fault=None) -> None:
    from kubeflow_tpu.api.platform import Platform
    from kubeflow_tpu.control.conditions import has_condition

    cell, link = ctx.cell, ctx.link
    seed = weight_seed(ctx.args.seed)
    isvc = inference_service(cell, seed)
    name = isvc["metadata"]["name"]
    platform = Platform(n_devices=cell.chips, root=ctx.tmp,
                        components=("serving",)).start()
    trace = None
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
                model = served_model(platform, name)
                platform.stop()
                model.unload()   # stops the engine loop and its supervisor
                del model
                freed = ctx.free_device()
                trace = ctx.trace_reduce()
                t = time.monotonic()
                pad = cell.traffic["verify_pad_tokens"]
                res = served_gaps(cell.config, seed, cmd["samples"],
                                  pad_to=pad)
                limit = cell.traffic["limits"]["served_logit_gap_max"]
                info = dict(res, freed_bytes=freed,
                            reference_s=round(time.monotonic() - t, 1))
                if cmd.get("control"):   # prove.py only, never a timed run
                    info["control"] = served_gaps(
                        cell.config, seed, cmd["samples"],
                        lower=cmd["control"], pad_to=pad)
                link.say("verify", memory_peak_bytes=peak, trace=trace,
                         numbers={"served_logit_gap_max": {
                             "value": res["widest_gap"], "limit": limit}},
                         info=info)
            elif kind == "quit":
                break
    finally:
        platform.stop()
        ctx.close()

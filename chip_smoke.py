#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one four-chip host

Drives the two main paths once, through the entry points users have, at the
full width of the contract model, and checks what comes out:

  facts  the device as JAX reports it, the host->device->host round trip of
         a trivial program, and one long program timed by block_until_ready
         against a scalar fetch (facts, not metrics); then the two serving
         attention kernels, compiled, against the XLA reference on a small
         input at the 8B head layout (a check: it fails the run).
  serve  Platform -> InferenceService -> router -> ModelServer -> LLMModel
         -> supervised LLMEngine with the `config:` block of
         examples/llama-8b-serving-isvc.yaml as it stands, at Llama-3-8B
         widths (depth is the only cut; weights are random from a seed),
         answering completions over HTTP at status.url.
  train  Platform -> JAXJob (target: trainer, backend: thread) -> Trainer
         on the r01-r04 proxy (d2048 x L8, seq 2048, unrolled, no remat),
         fed from a token file through the C++ loader.

With --chips 4 the same phases run sharded: train at 8B widths, depth 4,
mesh fsdp=2 x tensor=2, full remat; serve with mesh tensor=4; and every
device must hold shards of the parameters and of the KV cache.

One process owns the chip at a time: this parent never imports JAX, and
each phase is one child (this file with --phase) run after the other. A
phase that fails, finds no TPU, or outlives the deadline fails the run:
exit code 1 and no result line. On success the last two lines of stdout
are `summary: {...}` (what each phase found; it ends with "claim": null)
and, LAST, the one JSON object the driver reads, with these keys and no
other: {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Each phase's full output is kept under chiprun_out/chip_smoke/.

The children share the persistent compile cache (runtime/compile_cache.py:
JAX_COMPILATION_CACHE_DIR if set, else one fixed directory in the
checkout), so a second run prints fewer compile seconds and more entries.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
PHASES = ("facts", "serve", "train")
#: the contract gives 1200 s, compilation included; phases share what is
#: left of this, so a slow phase cannot push the run past it
DEADLINE_S = 1150.0

W8B = dict(vocab_size=128256, d_model=4096, n_heads=32, n_kv_heads=8,
           d_ff=14336, max_seq_len=2048)
SERVE_DEPTH = 4   # LLMModel initialises in f32: full depth cannot on 16 GB


# ---------------------------------------------------------------------------
# parent: no JAX here
# ---------------------------------------------------------------------------

def run_phase(phase: str, chips: int, deadline: float) -> dict:
    """Run one phase as a child that owns the chip for its lifetime; returns
    its report ({"ok": False, "reason": ...} on any failure)."""
    os.makedirs(OUT_DIR, exist_ok=True)
    log_path = os.path.join(OUT_DIR, f"{phase}.log")
    report_path = os.path.join(OUT_DIR, f"{phase}.json")
    if os.path.exists(report_path):
        os.unlink(report_path)
    budget = deadline - time.monotonic()
    if budget <= 0:
        return {"ok": False, "reason": "no time left before the deadline"}
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--phase", phase,
             "--chips", str(chips), "--report", report_path],
            stdout=log, stderr=subprocess.STDOUT, cwd=HERE,
            start_new_session=True)   # own group: killable with its kids
        try:
            rc = proc.wait(timeout=budget)
            reason = None if rc == 0 else f"exit code {rc}"
        except subprocess.TimeoutExpired:
            reason = f"still running at the deadline ({budget:.0f}s left)"
        finally:
            # stop every process the phase started, finished or not
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    with open(log_path) as log:
        tail = log.read()[-6000:]
    print(f"--- {phase} ({time.monotonic() - t0:.0f}s) "
          f"[{os.path.relpath(log_path, HERE)}]\n{tail}", flush=True)
    if reason is None:
        try:
            with open(report_path) as f:
                return json.load(f)
        except (OSError, ValueError) as e:
            reason = f"no report: {e}"
    return {"ok": False, "reason": reason}


def parent(chips: int) -> int:
    deadline = time.monotonic() + DEADLINE_S
    reports = {}
    for phase in PHASES:
        reports[phase] = rep = run_phase(phase, chips, deadline)
        if not rep.get("ok"):
            print(f"chip_smoke: FAILED in {phase}: "
                  f"{rep.get('reason', 'phase reported a failure')}",
                  file=sys.stderr, flush=True)
            return 1
    device = reports["facts"]["device"]
    summary = {
        "chips": chips,
        "versions": reports["facts"]["versions"],
        "round_trip_ms_median": reports["facts"]["round_trip_ms_median"],
        "kernel_parity": reports["facts"]["kernel_parity"],
        "compile_s": {p: reports[p]["compile_s"] for p in PHASES},
        "cache": {"dir": reports["facts"]["cache"]["dir"],
                  "entries_before": reports["facts"]["cache"]["entries_start"],
                  "entries_after": reports["train"]["cache"]["entries_end"],
                  "hits": sum(reports[p]["cache"]["hits"] for p in PHASES)},
        "serve": {"attention": reports["serve"]["attention"],
                  "mosaic_calls": reports["serve"]["mosaic_calls"],
                  "requests_ok": len(reports["serve"]["requests"]),
                  "peak_bytes_in_use":
                      reports["serve"]["peak_bytes_in_use"]},
        "train": {k: reports["train"][k] for k in
                  ("batch", "losses", "attention_bodies",
                   "peak_bytes_in_use")},
        "claim": None,
    }
    print("summary: " + json.dumps(summary), flush=True)
    # the result line: exactly these keys, the device as JAX reported it
    print(json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# children: each owns the chip for its lifetime
# ---------------------------------------------------------------------------

class PhaseFailed(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


class CompileMeter:
    """Seconds JAX spent in backend compiles (a persistent-cache hit counts
    its retrieval), and the cache's hit count, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring

        self.compile_s = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def _secs(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class ErrorWatch(logging.Handler):
    """Errors the platform logs instead of raising: a controller retries a
    failed reconcile forever, and the smoke must fail on the first."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.errors: list[str] = []
        logging.getLogger().addHandler(self)

    def emit(self, record) -> None:
        self.errors.append(record.getMessage()[-2000:])

    def finished(self, status: dict, *terminal: str) -> bool:
        """wait() predicate body: a terminal condition, or a logged error."""
        from kubeflow_tpu.control.conditions import has_condition

        return bool(self.errors) or any(has_condition(status, c)
                                        for c in terminal)


def cache_entries(cache_dir: str | None) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(1 for n in os.listdir(cache_dir) if not n.endswith("-atime"))


class Child:
    """One phase's process: the chip must be there (prologue), and what
    the phase cost is reported the same way for all (close)."""

    def __init__(self, chips: int):
        import importlib.metadata

        import jax

        from kubeflow_tpu.runtime.compile_cache import ensure_compile_cache

        self.jax, self.chips = jax, chips
        self.meter, self.watch = CompileMeter(), ErrorWatch()
        devices = jax.devices()
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind, "count": len(devices)}
        self.versions = {"jax": jax.__version__,
                         "jaxlib": importlib.metadata.version("jaxlib"),
                         "libtpu": importlib.metadata.version("libtpu")}
        print(f"device: {self.device}  versions: {self.versions}",
              flush=True)
        check(self.device["platform"] == "tpu",
              f"no accelerator: jax.devices()[0].platform is "
              f"{self.device['platform']!r}, and a smoke on it proves "
              "nothing")
        check(self.device["count"] >= chips,
              f"--chips {chips} but JAX reports {self.device['count']} "
              "device(s)")
        cache_dir = ensure_compile_cache()
        self.cache = {"dir": cache_dir,
                      "entries_start": cache_entries(cache_dir)}
        print(f"compile cache: {self.cache}", flush=True)

    def close(self) -> dict:
        """Compile seconds, cache traffic, memory, native libraries."""
        from kubeflow_tpu import native

        self.cache.update(entries_end=cache_entries(self.cache["dir"]),
                          hits=self.meter.hits)
        stats = [d.memory_stats() or {}
                 for d in self.jax.devices()[:self.chips]]
        out = {"compile_s": round(self.meter.compile_s, 1),
               "cache": self.cache,
               "peak_bytes_in_use": [s.get("peak_bytes_in_use")
                                     for s in stats],
               "bytes_in_use": [s.get("bytes_in_use") for s in stats],
               "native_libraries": native.loaded()}
        print(f"compile_s={out['compile_s']} cache={self.cache} "
              f"peak_bytes_in_use={out['peak_bytes_in_use']} "
              f"native={out['native_libraries']}", flush=True)
        return out


def live_shard_bytes(jax, chips: int) -> list[int]:
    """Bytes of live array shards per device — who holds the state."""
    held = [0] * chips
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            if shard.device.id < chips:
                held[shard.device.id] += shard.data.nbytes
    return held


# -- facts -------------------------------------------------------------------

def phase_facts(chips: int) -> dict:
    import statistics

    import numpy as np

    me = Child(chips)
    jax = me.jax
    import jax.numpy as jnp

    # (1) trivial program, host -> device -> host: ROADMAP Speed 3's term
    bump = jax.jit(lambda x: x + 1)
    x = np.zeros((8,), np.float32)
    np.asarray(bump(x))
    trips = []
    for _ in range(200):
        t0 = time.perf_counter()
        np.asarray(bump(x))
        trips.append((time.perf_counter() - t0) * 1e3)
    rt = statistics.median(trips)
    print(f"round trip of a trivial jitted program (host->device->host): "
          f"median {rt:.3f} ms, p90 {sorted(trips)[180]:.3f} ms, n=200",
          flush=True)

    # (2) one long program: does block_until_ready wait as long as a fetch?
    @jax.jit
    def long_program(a):
        return jax.lax.fori_loop(
            0, 256, lambda _, c: (c @ a) * (1.0 / 64.0), a)

    a = jnp.full((4096, 4096), 1.0 / 64.0, jnp.bfloat16)
    long_program(a).block_until_ready()
    blocked, fetched = [], []
    for _ in range(5):
        t0 = time.perf_counter()
        long_program(a).block_until_ready()
        blocked.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        float(long_program(a)[0, 0])
        fetched.append((time.perf_counter() - t0) * 1e3)
    print(f"256 x (4096^3 bf16 matmul) in one program: block_until_ready "
          f"median {statistics.median(blocked):.1f} ms, scalar fetch median "
          f"{statistics.median(fetched):.1f} ms", flush=True)
    parity = kernel_parity(jax)
    return {"ok": True, "device": me.device, "versions": me.versions,
            "round_trip_ms_median": round(rt, 4), "kernel_parity": parity,
            "long_program_ms": {
                "block_until_ready": round(statistics.median(blocked), 2),
                "scalar_fetch": round(statistics.median(fetched), 2)},
            **me.close()}


def kernel_parity(jax) -> dict:
    """The serving attention kernels, compiled for this device, against
    the XLA reference on a small input: the 8B head layout (32/8 heads of
    128, bf16), int8 KV for decode and speculative verify, ragged spans,
    prefill from position 0 and as a continuation. The differential the
    interpret-mode tests run on the CPU (tests/test_flash_decode.py,
    test_flash_prefill.py), by the same entry points and the same bf16
    bound. Returns the largest |flash - xla| over the largest |xla|."""
    import numpy as np

    import jax.numpy as jnp

    from kubeflow_tpu.models import llama

    dt = jnp.bfloat16
    cfg = llama.LlamaConfig(**dict(W8B, n_layers=1, dtype=dt))
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(0)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    def rel_err(want, got):
        want, got = (np.asarray(x, np.float32) for x in (want, got))
        check(want.shape == got.shape and np.isfinite(got).all(),
              f"kernel output {got.shape} vs reference {want.shape}, or "
              "not finite")
        return float(np.abs(got - want).max() / np.abs(want).max())

    errs = {}
    span = 1024
    # the cache as the layer scan carries it: 3 layers of 4 slots, the
    # int8 payloads with their scale planes lane-major; layer 1 is read
    kq, ks = llama.quantize_kv(normal(3, 4, span, nkv, hd))
    vq, vs = llama.quantize_kv(normal(3, 4, span, nkv, hd))
    cache = {"k": kq, "v": vq, "k_s": jnp.swapaxes(ks, 2, 3),
             "v_s": jnp.swapaxes(vs, 2, 3)}
    for s_v in (1, 7):   # plain decode; verify of `speculative: 6`
        lengths = jnp.asarray([span - s_v, 0, 131, 700], jnp.int32)
        positions = lengths[:, None] + jnp.arange(s_v)[None]
        q = normal(4, s_v, nh, hd).astype(dt)
        errs[f"decode_int8_sv{s_v}"] = rel_err(*(
            jax.jit(lambda *a, impl=impl: llama.decode_attention(
                cfg, *a, impl=impl))(q, cache, jnp.int32(1), positions)
            for impl in ("xla", "flash")))
    for s, t, q_offset in ((256, 256, 0), (128, 384, 256)):
        q, k, v = (normal(2, n, h, hd).astype(dt)
                   for n, h in ((s, nh), (t, nkv), (t, nkv)))
        errs[f"prefill_bf16_q{q_offset}"] = rel_err(*(
            jax.jit(lambda *a, impl=impl: llama.prefill_attention(
                cfg, *a, q_offset=q_offset, impl=impl))(q, k, v)
            for impl in ("xla", "flash")))
    print(f"kernel vs XLA reference, max|diff|/max|ref|: {errs}", flush=True)
    check(max(errs.values()) < 2e-2,
          f"a compiled kernel disagrees with the XLA reference: {errs}")
    return {k: round(v, 5) for k, v in errs.items()}


# -- serve -------------------------------------------------------------------

def isvc_spec(chips: int) -> dict:
    """examples/llama-8b-serving-isvc.yaml with its config block as it
    stands; the widths are the model's, the depth is cut, and the weights
    come from the seed instead of a checkpoint directory."""
    from kubeflow_tpu.api.specs import load_yaml_file

    (isvc,) = load_yaml_file(
        os.path.join(HERE, "examples", "llama-8b-serving-isvc.yaml"))
    model = isvc["spec"]["predictor"]["model"]
    model.pop("storageUri", None)
    model["config"].update(model=dict(W8B, n_layers=SERVE_DEPTH), seed=0)
    if chips > 1:
        model["config"]["mesh"] = {"tensor": chips}
    return isvc


def complete(url: str, body: dict, timeout: float = 300.0):
    """POST one OpenAI completion; returns (status, parsed JSON | SSE text)."""
    import http.client
    import urllib.parse

    u = urllib.parse.urlparse(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    try:
        conn.request("POST", "/openai/v1/completions", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read().decode()
    finally:
        conn.close()
    return resp.status, (raw if body.get("stream") else json.loads(raw))


def check_completion(label: str, status: int, out: dict, want: int) -> dict:
    check(status == 200, f"{label}: HTTP {status}: {out}")
    choice = out["choices"][0]
    check(choice["finish_reason"] in ("stop", "length"),
          f"{label}: finish_reason {choice['finish_reason']!r}")
    got = len(choice["token_ids"])
    check(choice["finish_reason"] == "stop" or got == want,
          f"{label}: asked for {want} tokens, got {got}")
    return {"label": label, "finish_reason": choice["finish_reason"],
            "tokens": got,
            "cached_tokens": out["usage"].get("cached_tokens")}


def program_text(jax, program, *args) -> str:
    """StableHLO of an engine program as it is handed to the compiler."""
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=x.sharding), args)
    return program.lower(*abstract).as_text()


def phase_serve(chips: int) -> dict:
    import concurrent.futures
    import random
    import tempfile
    import urllib.request

    import numpy as np

    me = Child(chips)
    jax, watch = me.jax, me.watch
    from kubeflow_tpu.api.platform import Platform
    from kubeflow_tpu.control.conditions import has_condition

    isvc = isvc_spec(chips)
    name = isvc["metadata"]["name"]
    rng = random.Random(0)

    def prompt(n):   # token ids straight in: no tokenizer in the way
        return [rng.randrange(1, W8B["vocab_size"]) for _ in range(n)]

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as root, \
            Platform(n_devices=chips, root=root,
                     components=("serving",)) as platform:
        t0 = time.monotonic()
        platform.apply(isvc)
        obj = platform.wait(
            "InferenceService", name,
            lambda o: watch.finished(o.get("status", {}), "Ready", "Failed"),
            timeout=DEADLINE_S)
        check(has_condition(obj["status"], "Ready") and not watch.errors,
              f"InferenceService not Ready: {obj['status']} {watch.errors}")
        url = obj["status"]["url"]
        backend = ("http://127.0.0.1:"
                   f"{obj['status']['components']['predictor']['port']}")
        print(f"InferenceService Ready in {time.monotonic() - t0:.0f}s "
              f"(warmup compiled the menu); status.url={url}", flush=True)

        requests = []
        # several ~100-token prompts at once: one batched prefill wave
        with concurrent.futures.ThreadPoolExecutor(6) as pool:
            burst = [pool.submit(complete, url,
                                 {"model": name, "prompt": prompt(100 + i),
                                  "max_tokens": 24, "logprobs": 5})
                     for i in range(6)]
            for i, fut in enumerate(burst):
                requests.append(check_completion(
                    f"burst[{i}]", *fut.result(), want=24))
        # one prompt over the largest bucket: the chunked chain and its
        # q_offset > 0 continuation programs
        requests.append(check_completion(
            "long(1300)", *complete(url, {"model": name,
                                          "prompt": prompt(1300),
                                          "max_tokens": 16}), want=16))
        # the same prompt twice: the second reuses the banked prefix
        again = {"model": name, "prompt": prompt(300), "max_tokens": 16}
        requests.append(check_completion("repeat[0]",
                                         *complete(url, again), want=16))
        hit = check_completion("repeat[1]", *complete(url, again), want=16)
        check((hit["cached_tokens"] or 0) > 0,
              f"no prefix hit on a repeated prompt: {hit}")
        requests.append(hit)
        # one sampled request
        requests.append(check_completion(
            "sampled", *complete(url, {
                "model": name, "prompt": prompt(90), "max_tokens": 24,
                "temperature": 0.8, "top_p": 0.9, "seed": 7}), want=24))
        # one SSE stream
        status, sse = complete(url, {"model": name, "prompt": prompt(110),
                                     "max_tokens": 24, "stream": True})
        events = [json.loads(line[6:]) for line in sse.splitlines()
                  if line.startswith("data: ") and line != "data: [DONE]"]
        check(status == 200 and sse.rstrip().endswith("data: [DONE]"),
              f"stream: HTTP {status}, tail {sse[-200:]!r}")
        check(not any("error" in e for e in events), f"stream: {events[-1]}")
        n_tok = sum(1 for e in events if "token_id" in e["choices"][0])
        reason = events[-1]["choices"][0]["finish_reason"]
        check(reason in ("stop", "length") and (reason == "stop"
                                                or n_tok == 24),
              f"stream: {n_tok} tokens, finish_reason {reason!r}")
        requests.append({"label": "stream", "finish_reason": reason,
                         "tokens": n_tok})
        for r in requests:
            print(f"request {r}", flush=True)

        # what the replica says about itself, and what its supervisor saw
        with urllib.request.urlopen(backend + "/healthz", timeout=30) as r:
            healthz = json.loads(r.read())
        attention = healthz["attention"][name]
        print(f"/healthz attention={attention} "
              f"supervisor={healthz.get('supervisor')} "
              f"mesh={healthz.get('mesh')}", flush=True)
        (inst,) = platform.serving._instances[("default", name, "predictor")]
        model = inst.server.repository.get(name)
        books = model.supervisor.accounting()
        print(f"supervisor accounting: { {k: books[k] for k in ('accepted', 'completed', 'cancelled', 'restarts', 'lost')} } "
              f"outages={books['outages']}", flush=True)
        check(not books["outages"] and not books["permanent_failed"]
              and books["lost"] == 0 and books["cancelled"] == 0
              and not watch.errors,
              f"the supervisor saw trouble: {books} {watch.errors}")

        engine = model.supervisor.engine
        state = (engine.params, engine.cache, engine.lengths,
                 engine.last_tokens, engine.samp, engine.rng_key)
        decode_key = max(engine._spec_fns)   # (rounds, span, k): the menu's
        wave = engine._put(np.zeros(             # workhorse program
            (1, engine.buckets[0] + engine._row_extra), np.int32))
        active = engine._put(np.zeros((engine.n_slots,), bool))
        mosaic = {
            "decode": program_text(
                jax, engine._spec_fns[decode_key], *state, active
            ).count("tpu_custom_call"),
            "prefill": program_text(
                jax, engine._prefill_fns[engine.buckets[0], 1], *state, wave
            ).count("tpu_custom_call"),
        }
        print(f"Mosaic custom calls in the engine's programs: {mosaic} "
              f"(decode program {decode_key})", flush=True)
        held = live_shard_bytes(jax, chips)
        if chips == 1:
            # one chip, no mesh: the kernels must be the ones serving
            check(attention == {"decode": "flash", "prefill": "flash"},
                  f"attention impls resolved to {attention}, not flash")
            check(min(mosaic.values()) > 0,
                  f"no Mosaic custom call in a served program: {mosaic}")
        else:
            # under the GSPMD mesh the engine pins attention to xla by its
            # own rule (reported above); every device must hold its share
            kv_devices = {s.device.id
                          for s in engine.cache["k"].addressable_shards}
            param_devices = {s.device.id
                             for leaf in jax.tree.leaves(engine.params)
                             for s in leaf.addressable_shards}
            print(f"live shard bytes per device: {held}; params on "
                  f"{sorted(param_devices)}, KV on {sorted(kv_devices)}",
                  flush=True)
            check(kv_devices == param_devices == set(range(chips))
                  and min(held) > 0,
                  "a device holds no parameter or KV shard")
        tail = me.close()
        check(all(b and b > 0 for b in tail["bytes_in_use"]),
              f"a device reports no bytes in use: {tail['bytes_in_use']}")
        check("cb_scheduler" in tail["native_libraries"],
              "the C++ scheduler was not the one scheduling")
    return {"ok": True, "attention": attention, "mosaic_calls": mosaic,
            "requests": requests, "live_shard_bytes": held, **tail}


# -- train -------------------------------------------------------------------

def trainer_config(chips: int, batch: int, corpus: str) -> dict:
    if chips == 1:
        # the one-chip proxy: unrolled, no remat, bf16 first moment
        model = dict(vocab_size=32000, d_model=2048, n_layers=8, n_heads=16,
                     n_kv_heads=8, d_ff=7168, max_seq_len=2048, remat=False,
                     scan_layers=False)
        mesh = {"data": -1}
    else:
        model = dict(W8B, n_layers=4, remat=True, remat_policy="full")
        mesh = {"fsdp": 2, "tensor": 2}
    return {"model": "llama", "model_overrides": model, "batch_size": batch,
            "num_steps": 6, "log_every": 1, "mesh": mesh,
            "dataset": {"type": "token_file", "path": corpus,
                        "seq_len": 2048},
            "optimizer": {"warmup_steps": 2, "mu_dtype": "bfloat16"}}


def phase_train(chips: int) -> dict:
    import math
    import tempfile

    me = Child(chips)
    jax, watch = me.jax, me.watch
    from kubeflow_tpu.api.platform import Platform
    from kubeflow_tpu.api.specs import jaxjob
    from kubeflow_tpu.control.conditions import has_condition
    from kubeflow_tpu.ops import flash_attention
    from kubeflow_tpu.training.loader import write_corpus
    from kubeflow_tpu.training.metrics_writer import read_metrics
    from scripts.gen_corpus import synthetic_corpus

    vocab = 32000 if chips == 1 else W8B["vocab_size"]
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as root, \
            Platform(n_devices=chips, root=root,
                     components=("training",)) as platform:
        corpus = os.path.join(root, "corpus.bin")
        write_corpus(corpus, synthetic_corpus(2_000_000, vocab, seed=0))
        held: list[int] = []
        # batch is not a width: the proxy's 6 fills the chip to its last
        # GiB (14.87 of 15.75 by the compiler's own count), so step down
        # rather than fail if this process's overhead does not fit beside it
        for batch in ((6, 4) if chips == 1 else (4,)):
            held.clear()
            name = f"smoke-train-b{batch}"
            metrics_file = os.path.join(root, f"{name}.jsonl")
            platform.apply(jaxjob(
                name, target="trainer", backend="thread", tpu=chips,
                restart_policy="Never", backoff_limit=0,
                env={"KTPU_TRAINER_CONFIG": json.dumps(
                         trainer_config(chips, batch, corpus)),
                     "KTPU_METRICS_FILE": metrics_file}))

            def finished_or_stepped(o):
                # the first logged step: the state is live on the devices
                if not held and read_metrics(metrics_file):
                    held[:] = live_shard_bytes(jax, chips)
                return watch.finished(o.get("status", {}), "Succeeded",
                                      "Failed")

            job = platform.wait("JAXJob", name, finished_or_stepped,
                                timeout=DEADLINE_S)
            if has_condition(job["status"], "Succeeded"):
                break
            logs = platform.job_logs(name)
            print(f"JAXJob {name} failed:\n{logs[-3000:]}", flush=True)
            check("RESOURCE_EXHAUSTED" in logs and batch > 4,
                  f"JAXJob {name} did not succeed: {job['status']}")
            print(f"batch {batch} does not fit beside this process; "
                  "stepping down", flush=True)
        print(platform.job_logs(name)[-1500:], flush=True)
        losses = [rec["metrics"]["loss"] for rec in read_metrics(metrics_file)]
    print(f"JAXJob {name} Succeeded: losses {losses}", flush=True)
    check(len(losses) == 6 and all(math.isfinite(v) for v in losses),
          f"expected 6 finite losses, got {losses}")
    check(losses[-1] < losses[0],
          f"six steps on a learnable corpus did not lower the loss: {losses}")
    bodies = sorted(flash_attention.TRACED_BODIES)
    print(f"flash_attention bodies traced: {bodies}; live shard bytes per "
          f"device at the first step: {held}", flush=True)
    check(bodies == ["pallas"],
          f"the Pallas attention body is not what trained: {bodies}")
    tail = me.close()
    check("data_loader" in tail["native_libraries"],
          "the C++ loader was not the one feeding")
    check(len(held) == chips and min(held) > 0
          and all(b and b > 0 for b in tail["peak_bytes_in_use"]),
          f"a device held no shard of the train state: {held}")
    return {"ok": True, "batch": batch, "losses": losses,
            "attention_bodies": bodies, "live_shard_bytes": held, **tail}


def child(phase: str, chips: int, report_path: str) -> int:
    try:
        report = {"facts": phase_facts, "serve": phase_serve,
                  "train": phase_train}[phase](chips)
    except PhaseFailed as e:
        print(f"chip_smoke[{phase}]: FAILED: {e}", flush=True)
        return 1
    with open(report_path, "w") as f:
        json.dump(report, f)
    print(f"chip_smoke[{phase}]: ok", flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--phase", choices=PHASES, help=argparse.SUPPRESS)
    ap.add_argument("--report", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        return child(args.phase, args.chips, args.report)
    return parent(args.chips)


if __name__ == "__main__":
    sys.exit(main())

"""Model server — the kserve ModelServer analog (SURVEY.md §2.4, §3.5,
⊘ kserve `python/kserve/kserve/model_server.py` `ModelServer.start` and
`kserve/protocol/rest/server.py`).

Threaded HTTP server speaking both dataplanes:

    V1:  POST /v1/models/<m>:predict | :explain
    V2:  GET  /v2                     (server metadata)
         GET  /v2/health/live|ready
         GET  /v2/models/<m>         (model metadata)
         GET  /v2/models/<m>/ready
         POST /v2/models/<m>/infer
    GET /metrics                      (prometheus text, request counters)

Optional per-model dynamic batching (serving/batching.py). One server
instance is the "pod" of an InferenceService revision; the controller
manages instances and the router splits traffic — the Knative/Istio analog.
"""

from __future__ import annotations

import json
import math
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any

from kubeflow_tpu.obs import metrics as obs_metrics
from kubeflow_tpu.obs.build import build_stamp
from kubeflow_tpu.obs.metrics import render_metrics
from kubeflow_tpu.obs.trace import TRACE_HEADER, TRACER, new_trace_id
from kubeflow_tpu.serving.batching import DynamicBatcher
from kubeflow_tpu.serving.model import Model, ModelError, ModelRepository
from kubeflow_tpu.serving.protocol import (InferRequest, InferResponse,
                                           ProtocolError, v1_decode,
                                           v1_encode)


class NotReadyError(Exception):
    """Model exists but cannot serve yet (→ HTTP 503, retryable)."""


def _client_gone(sock) -> bool:
    """True when the streaming client hung up. A write into a dead socket
    only fails once the kernel send buffer fills, so an abandoned stream
    could decode for many chunks before the BrokenPipeError lands (the
    cancellation-storm gap, ROADMAP #4). The request body was fully read
    and SSE clients never pipeline a second request (Connection: close),
    so the socket becoming READABLE means EOF/RST: select + MSG_PEEK
    detects the disconnect before the next token write, and the engine
    slot frees at the next chunk boundary instead of at buffer-full.

    DOCUMENTED TRADE-OFF: a client that half-closes its WRITE side
    (shutdown(SHUT_WR)) after the request but keeps reading presents the
    same read-side EOF and is treated as gone — its stream is cancelled.
    Half-close is vanishingly rare for SSE consumers, and the
    alternative (decoding to completion for every silently-vanished
    client) is the capacity leak this probe exists to close."""
    import select
    import socket

    try:
        r, _, _ = select.select([sock], [], [], 0)
        if not r:
            return False
        sock.recv(1, socket.MSG_PEEK | socket.MSG_DONTWAIT)
    except (BlockingIOError, InterruptedError):
        return False  # spurious select wakeup (select(2) BUGS: readable
        #               then EAGAIN) / EINTR: the client is still there
    except (OSError, ValueError):
        return True   # closed/invalid fd: the client is gone either way
    # readable with data is ALSO treated as gone: an SSE client never
    # sends during the response (Connection: close — pipelining is
    # ignored anyway), and because MSG_PEEK never drains, one stray
    # byte would otherwise read as "readable, not EOF" on every token
    # and permanently blind the probe for this stream
    return True


class ModelServer:
    def __init__(self, repository: ModelRepository | None = None,
                 port: int = 0, name: str = "kubeflow-tpu-server",
                 batching: dict[str, Any] | None = None,
                 payload_logger: Any | None = None):
        self.repository = repository or ModelRepository()
        self.name = name
        self.payload_logger = payload_logger  # serving/agent.PayloadLogger
        self._batchers: dict[str, DynamicBatcher] = {}
        self._batch_cfg = batching or {}
        self._metrics_lock = threading.Lock()
        self.request_count: dict[tuple[str, str], int] = {}
        self.latency_sum: dict[str, float] = {}
        server = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):   # quiet
                pass

            def _send(self, code: int, payload: dict[str, Any]) -> None:
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                try:
                    if self.path == "/metrics":
                        # prometheus text exposition from the ONE process
                        # registry (ISSUE 17) — not JSON, not per-server
                        # dict merging
                        body = render_metrics().encode()
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "text/plain; version=0.0.4")
                        self.send_header("Content-Length", str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                        return
                    self._send(*server._handle_get(self.path))
                except Exception as e:
                    self._send(500, {"error": str(e)})

            def do_POST(self):
                t_enter = time.monotonic()   # usage.pre_submit_ms starts
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    raw = self.rfile.read(length)
                    if self.path in ("/openai/v1/completions",
                                     "/openai/v1/chat/completions"):
                        chat = self.path.endswith("chat/completions")
                        try:
                            body = json.loads(raw) if raw else {}
                        except json.JSONDecodeError as e:
                            return self._send(400,
                                              {"error": f"bad json: {e}"})
                        if not isinstance(body, dict):
                            return self._send(
                                400, {"error": "body must be an object"})
                        # trace id: the router's X-Trace-Id header, or
                        # minted here — this IS the edge for direct
                        # clients. Sampling decides later whether any
                        # span records for it.
                        trace = (self.headers.get(TRACE_HEADER)
                                 or new_trace_id())
                        if body.get("stream"):
                            return server._stream_completion(
                                self, body, chat, trace, t_enter)
                        return self._send(
                            *server._completion(body, chat, trace,
                                                t_enter))
                    self._send(*server._handle_post(self.path, raw))
                except Exception as e:
                    self._send(500, {"error": str(e)})

        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread: threading.Thread | None = None
        self._t_start = time.monotonic()
        self._stopped = False

    # -- lifecycle ------------------------------------------------------------

    def start(self, background: bool = True) -> "ModelServer":
        self._t_start = time.monotonic()
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True,
                                        name=f"model-server-{self.port}")
        self._thread.start()
        if not background:
            self._thread.join()
        return self

    def stop(self) -> None:
        self._stopped = True
        self._httpd.shutdown()
        self._httpd.server_close()
        for b in self._batchers.values():
            b.stop()
        self._batchers.clear()

    @property
    def alive(self) -> bool:
        """Liveness the supervisor/controller can poll without a socket
        round-trip: the server thread is serving and stop() has not run.
        A crashed/stopped replica reads False — the controller's
        restartPolicy machinery keys off this."""
        return (not self._stopped and self._thread is not None
                and self._thread.is_alive())

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    # -- routing --------------------------------------------------------------

    def health(self) -> dict[str, Any]:
        """The /healthz payload, computable in-process (the controller's
        dead-replica pruning calls this instead of a socket round-trip —
        same data either way). Cheap and model-free at its core:
        answering at all means the serving thread is alive; uptime lets
        flap detectors spot restarts. Models running a prefix KV cache
        report their reuse counters (the kvcache operator surface), and
        supervised LLM engines report their crash-recovery state
        (restarts, permanent_failed, last_mttr_s, journal_depth) — the
        router/controller/fleet tooling reads both without a model
        round-trip."""
        body: dict[str, Any] = {
            "alive": self.alive, "name": self.name,
            "uptime_s": round(time.monotonic() - self._t_start, 3),
            # version/runtime identification (ISSUE 17): kubeflow_tpu +
            # jax/jaxlib versions and the device the process landed on —
            # a fleet operator ties a misbehaving replica to its build
            # without shelling into the pod
            "build": build_stamp()}
        caches: dict[str, Any] = {}
        sups: dict[str, Any] = {}
        disaggs: dict[str, Any] = {}
        meshes: dict[str, Any] = {}
        slos: dict[str, Any] = {}
        attns: dict[str, Any] = {}
        for mname in self.repository.names():
            try:
                model = self.repository.get(mname)
                mm = model.metrics()
            except Exception:
                continue   # liveness must answer even if a model is
                # mid-load/broken — health first, detail best-effort
            trk = getattr(model, "slo_tracker", None)
            if trk is not None:
                try:
                    s = trk.summary()
                    if s["aggregate"]["n"]:
                        slos[mname] = s
                except Exception:
                    pass   # burn accounting is detail, never liveness
            # resolved attention impls (ISSUE 20 satellite): which
            # kernel path each phase actually runs (xla vs Pallas
            # flash) — an operator ties a TTFT/TPOT regression to a
            # kernel-selection change without a model round-trip. The
            # same pair rides /metrics as the serving_attention_impl_info
            # gauge, which the router's proxied scrape passes through.
            d_impl = (mm or {}).get("decode_attention_impl")
            p_impl = (mm or {}).get("prefill_attention_impl")
            if d_impl or p_impl:
                attns[mname] = {"decode": d_impl, "prefill": p_impl}
            pc = (mm or {}).get("prefix_cache")
            if pc:
                # tagged with the KV residency (slab rows vs paged block
                # pool) so the free_blocks/watermark_frac gauges read in
                # the right units at a glance
                caches[mname] = dict(
                    pc, kv_layout=(mm or {}).get("kv_layout", "slab"))
            mesh = (mm or {}).get("mesh")
            if mesh:
                # multichip observability (ISSUE 14): layout name, axis
                # names/sizes, device count, per-stage params bytes —
                # a fleet operator tells a single-chip replica from a
                # tp slice from a tp×pp stage-sharded one here, through
                # the same EngineSupervisor metrics passthrough as the
                # kv_cache section
                meshes[mname] = mesh
                pipe = (mm or {}).get("pipeline")
                if pipe:
                    meshes[mname] = dict(mesh, pipeline=pipe)
            sup = (mm or {}).get("supervisor")
            if sup:
                sups[mname] = {
                    "restarts": sup.get("restarts", 0),
                    "permanent_failed": bool(
                        sup.get("permanent_failed", False)),
                    "last_mttr_s": sup.get("last_mttr_s"),
                    "journal_depth": sup.get("journal_depth", 0),
                    "in_flight": sup.get("in_flight", 0),
                    "degraded_rejections": sup.get("shed", 0),
                }
            dg = (mm or {}).get("disagg")
            if dg:
                # disaggregated-serving observability (ISSUE 13):
                # handoff depth, queue wait, blocks in flight — what an
                # operator needs to see backpressure instead of
                # inferring it
                disaggs[mname] = {
                    "queue_depth": dg.get("queue_depth", 0),
                    "inflight_prefills": dg.get("inflight_prefills", 0),
                    "blocks_in_flight": dg.get("blocks_in_flight", 0),
                    "queue_wait_ms_mean": dg.get("queue_wait_ms_mean"),
                    "bypass": dg.get("bypass", 0),
                    "handoff": dg.get("handoff"),
                    "prefill_restarts": dg.get("prefill_restarts", 0),
                    "prefill_permanent_failed": bool(
                        dg.get("prefill_permanent_failed", False)),
                }
        if caches:
            body["kv_cache"] = caches
        if sups:
            body["supervisor"] = sups
        if disaggs:
            body["disagg"] = disaggs
        if meshes:
            body["mesh"] = meshes
        if slos:
            body["slo"] = slos
        if attns:
            body["attention"] = attns
        return body

    def _handle_get(self, path: str) -> tuple[int, dict[str, Any]]:
        if path == "/healthz":
            return 200, self.health()
        if path in ("/", "/v2"):
            return 200, {"name": self.name, "version": "2",
                         "extensions": ["health", "models", "metrics"]}
        if path == "/v2/health/live":
            return 200, {"live": True}
        if path == "/v2/health/ready":
            # a permanently-failed supervisor means this replica can
            # never serve again (restart budget exhausted): readiness
            # gates it out of rotation even though the HTTP thread
            # still answers (shared gate: ModelRepository)
            ready = all(self.repository.ready(n)
                        for n in self.repository.names()) \
                and not self.repository.permanently_failed()
            return (200 if ready else 503), {"ready": ready}
        if path == "/v1/models" or path == "/v2/models":
            return 200, {"models": self.repository.names()}
        if path == "/metrics":
            return 200, self._metrics()
        parts = path.strip("/").split("/")
        if len(parts) >= 3 and parts[0] == "v2" and parts[1] == "models":
            name = parts[2]
            if len(parts) == 4 and parts[3] == "ready":
                ok = self.repository.ready(name)
                return (200 if ok else 503), {"name": name, "ready": ok}
            if len(parts) == 3:
                try:
                    m = self.repository.get(name)
                except ModelError as e:
                    return 404, {"error": str(e)}
                return 200, {"name": name, "platform": "jax-tpu",
                             "inputs": m.input_spec(),
                             "outputs": m.output_spec()}
        return 404, {"error": f"no route {path}"}

    def _handle_post(self, path: str, raw: bytes) -> tuple[int, dict[str, Any]]:
        try:
            body = json.loads(raw) if raw else {}
        except json.JSONDecodeError as e:
            return 400, {"error": f"bad json: {e}"}
        parts = path.strip("/").split("/")
        try:
            if len(parts) == 3 and parts[0] == "v1" and parts[1] == "models":
                name, _, verb = parts[2].partition(":")
                return self._v1(name, verb or "predict", body)
            if (len(parts) == 4 and parts[0] == "v2"
                    and parts[1] == "models" and parts[3] == "infer"):
                return self._v2_infer(parts[2], body)
        except ProtocolError as e:
            return 400, {"error": str(e)}
        except ModelError as e:
            return 404, {"error": str(e)}
        return 404, {"error": f"no route {path}"}

    # -- OpenAI-compatible completions (⊘ kserve huggingfaceserver) ----------

    def _completion_request(self, body: dict[str, Any],
                            chat: bool = False):
        """Shared request parsing → (model, payload). Raises ProtocolError
        (→400), ModelError (→404), or NotReadyError (→503)."""
        name = body.get("model")
        if not name:
            raise ProtocolError('"model" is required')
        m = self.repository.get(name)
        if not hasattr(m, "stream") or not hasattr(m, "tokenizer"):
            raise ProtocolError(
                f"model {name!r} does not serve text completions")
        if not m.ready:
            raise NotReadyError(f"model {name!r} is not ready")
        if chat:
            from kubeflow_tpu.serving.tokenizer import chat_prompt_ids

            messages = body.get("messages")
            if not isinstance(messages, list) or not messages:
                raise ProtocolError('"messages" must be a non-empty list')
            for msg in messages:
                if not (isinstance(msg, dict)
                        and isinstance(msg.get("role"), str)
                        and isinstance(msg.get("content"), str)):
                    raise ProtocolError(
                        "each message needs string role and content")
            try:
                ids = chat_prompt_ids(m.tokenizer, messages)
            except Exception as e:
                # e.g. an HF chat template (jinja) rejecting the message
                # sequence: a malformed request, not a server fault
                raise ProtocolError(
                    f"chat template rejected messages: {e}") from e
        else:
            prompt = body.get("prompt", "")
            if isinstance(prompt, list):
                if not all(isinstance(t, int) for t in prompt):
                    raise ProtocolError(
                        "prompt must be a string or a list of token ids "
                        "(batched string prompts are not supported)")
                ids = list(prompt)
            elif isinstance(prompt, str):
                ids = m.tokenizer.encode(prompt)
            else:
                raise ProtocolError("prompt must be a string or token ids")
        if not ids:
            raise ProtocolError("prompt must be non-empty")
        try:
            max_new = int(body.get("max_tokens", 16))
        except (TypeError, ValueError):
            raise ProtocolError("max_tokens must be an int") from None
        try:
            temperature = float(body.get("temperature", 0.0))
        except (TypeError, ValueError):
            raise ProtocolError("temperature must be a number") from None
        if not (math.isfinite(temperature) and 0 <= temperature <= 100):
            # json.loads happily parses NaN/Infinity; they must not reach
            # the engine thread
            raise ProtocolError("temperature must be finite and in [0, 100]")
        payload: dict[str, Any] = {"prompt_tokens": ids,
                                   "max_new_tokens": max_new,
                                   "temperature": temperature}
        # -- sampling parity fields (⊘ kserve huggingfaceserver):
        # top_k/top_p run INSIDE the engine's compiled programs; stop is
        # matched host-side at chunk boundaries; logprobs=true returns
        # per-token logprobs, logprobs=N additionally the top-N
        # alternatives (N bounded by the engine's logprobs_topk build knob)
        try:
            top_k = int(body.get("top_k", 0))
        except (TypeError, ValueError):
            raise ProtocolError("top_k must be an int") from None
        kmax = getattr(m, "_sample_k_max", 64)
        if not 0 <= top_k <= kmax:
            raise ProtocolError(f"top_k must be 0..{kmax}")
        try:
            top_p = float(body.get("top_p", 1.0))
        except (TypeError, ValueError):
            raise ProtocolError("top_p must be a number") from None
        if not (math.isfinite(top_p) and 0 < top_p <= 1):
            raise ProtocolError("top_p must be in (0, 1]")
        stop = body.get("stop")
        if stop is not None:
            if isinstance(stop, str):
                stop = [stop]
            if (not isinstance(stop, list) or len(stop) > 8
                    or not all(isinstance(s, str) and s for s in stop)):
                raise ProtocolError(
                    "stop must be a non-empty string or a list of up to 8")
            payload["stop"] = stop
        lp_req = body.get("logprobs", False)
        if lp_req is not None and not isinstance(lp_req, (bool, int)):
            raise ProtocolError("logprobs must be a bool or an int")
        lp_n = int(lp_req or 0) if not isinstance(lp_req, bool) else 0
        if lp_n < 0 or lp_n > getattr(m, "_logprobs_topk", 0):
            raise ProtocolError(
                f"logprobs top-N must be 0..{getattr(m, '_logprobs_topk', 0)}"
                " (the engine's logprobs_topk build setting)")
        payload["want_logprobs"] = bool(lp_req)
        payload["logprobs_n"] = lp_n
        payload["top_k"] = top_k
        payload["top_p"] = top_p
        # -- OpenAI long tail (⊘ kserve huggingfaceserver): penalties are
        # logit edits INSIDE the compiled programs (nonzero values are
        # quantized to milli units with a ±1 milli floor — |v| < 0.0005
        # stays a minimal penalty rather than silently turning off);
        # seed makes sampling reproducible — the engine folds it onto 24
        # bits via a splitmix64 mixing hash, so any two distinct seeds
        # collide with probability ~2^-24 but colliding pairs are not
        # predictable from the values, and a given seed always replays
        # the same stream; n/best_of fan one request across decode slots;
        # echo prepends the prompt to the completion
        for fname in ("presence_penalty", "frequency_penalty"):
            try:
                v = float(body.get(fname, 0.0))
            except (TypeError, ValueError):
                raise ProtocolError(f"{fname} must be a number") from None
            if not (math.isfinite(v) and -2 <= v <= 2):
                raise ProtocolError(f"{fname} must be in [-2, 2]")
            payload[fname] = v
        seed = body.get("seed")
        if seed is not None:
            if not isinstance(seed, int) or isinstance(seed, bool) \
                    or seed < 0:
                raise ProtocolError("seed must be a non-negative integer")
            payload["seed"] = seed
        # OpenAI `user` → engine tenant: per-tenant fair scheduling and
        # admission caps key on it (loadgen subsystem)
        user = body.get("user")
        if user is not None:
            if not isinstance(user, str) or not 1 <= len(user) <= 256:
                # the length cap matters: tenant names are retained for
                # the engine's lifetime (the fairness map), so unbounded
                # client-chosen strings would be a memory lever
                raise ProtocolError("user must be a string of 1..256 chars")
            payload["tenant"] = user
        try:
            n = int(body.get("n", 1))
            best_of = int(body.get("best_of", n))
        except (TypeError, ValueError):
            raise ProtocolError("n/best_of must be integers") from None
        if not 1 <= n <= 8:
            raise ProtocolError("n must be 1..8")
        if not n <= best_of <= 8:
            raise ProtocolError("best_of must be n..8")
        payload["n"] = n
        payload["best_of"] = best_of
        echo = body.get("echo", False)
        if not isinstance(echo, bool):
            raise ProtocolError("echo must be a boolean")
        if echo and chat:
            raise ProtocolError("echo is not supported for chat")
        payload["echo"] = echo
        if body.get("timeout") is not None:
            try:
                payload["deadline_s"] = float(body["timeout"])
            except (TypeError, ValueError):
                raise ProtocolError("timeout must be a number") from None
            if payload["deadline_s"] <= 0:
                raise ProtocolError("timeout must be positive")
        return m, payload

    @staticmethod
    def _completion_error(e: Exception) -> tuple[int, dict[str, Any]]:
        from kubeflow_tpu.serving.scheduler import QueueFull

        code = (404 if isinstance(e, ModelError)
                else 503 if isinstance(e, (NotReadyError, QueueFull))
                else 400)   # ProtocolError / PromptTooLong: bad request
        return code, {"error": str(e)}

    @staticmethod
    def _completion_exceptions() -> tuple[type, ...]:
        from kubeflow_tpu.serving.scheduler import PromptTooLong, QueueFull

        # deliberately NOT bare ValueError: an internal engine bug must
        # surface as a 500, not masquerade as a client error
        return (ProtocolError, ModelError, NotReadyError, PromptTooLong,
                QueueFull)

    def _build_choice(self, m, payload: dict[str, Any],
                      result: dict[str, Any], index: int,
                      chat: bool) -> dict[str, Any]:
        """One OpenAI choice object from an engine result. With echo the
        prompt tokens prepend the completion (prompt positions carry null
        logprobs — prompt scoring is not computed; the static program
        menu emits sampled-position logprobs only, documented)."""
        tokens, reason = result["token_ids"], result["finish_reason"]
        prompt_ids = list(payload["prompt_tokens"])
        echo = bool(payload.get("echo"))
        out_tokens = (prompt_ids + tokens) if echo else tokens
        text = m.tokenizer.decode(out_tokens)
        choice: dict[str, Any] = {"index": index, "token_ids": out_tokens,
                                  "finish_reason": reason}
        if payload.get("want_logprobs"):
            pad: list[Any] = [None] * len(prompt_ids) if echo else []
            lp: dict[str, Any] = {
                "token_ids": out_tokens,
                "token_logprobs": pad + result["logprobs"]}
            n = payload.get("logprobs_n", 0)
            if n:
                # JSON object keys are strings; ids stay exact as strings
                lp["top_logprobs"] = pad + [
                    {str(t): v for t, v in sorted(
                        d.items(), key=lambda kv: -kv[1])[:n]}
                    for d in result["top_logprobs"]]
            choice["logprobs"] = lp
        if chat:
            choice["message"] = {"role": "assistant", "content": text}
        else:
            choice["text"] = text
        return choice

    def _completion(self, body: dict[str, Any], chat: bool = False,
                    trace: str | None = None,
                    t_enter: float | None = None
                    ) -> tuple[int, dict[str, Any]]:
        t0 = time.perf_counter()
        t_mono = time.monotonic()
        try:
            m, payload = self._completion_request(body, chat)
            if trace:
                payload["trace"] = str(trace)
            best_of = payload.get("best_of", 1)
            if best_of <= 1:
                results = [m.complete(payload)]
            else:
                # fan the request across decode slots: best_of clones
                # share the continuous batch (seeded requests salt the
                # seed per clone so the samples differ reproducibly)
                seed = payload.get("seed")
                clones = [dict(payload) if seed is None
                          else dict(payload, seed=seed + i)
                          for i in range(best_of)]
                results = m.complete_many(clones)
        except self._completion_exceptions() as e:
            return self._completion_error(e)
        self._observe(m.name, "completions", time.perf_counter() - t0)
        TRACER.record_span("server.http", "http", trace, t_mono,
                           time.monotonic(), model=m.name,
                           verb="completions", streamed=False)
        n_choices = payload.get("n", 1)
        if len(results) > 1:
            # OpenAI best_of: return the n best by per-token logprob
            def score(r):
                lps = r["logprobs"]
                return sum(lps) / max(1, len(lps))

            results = sorted(results, key=score, reverse=True)
        gen_tokens = sum(len(r["token_ids"]) for r in results)
        choices = [self._build_choice(m, payload, r, i, chat)
                   for i, r in enumerate(results[:n_choices])]
        usage = {"prompt_tokens": len(payload["prompt_tokens"]),
                 "completion_tokens": gen_tokens,
                 "total_tokens":
                     len(payload["prompt_tokens"]) + gen_tokens}
        # prompt tokens served from the prefix KV cache: the OpenAI
        # `cached_tokens` surface, mirrored under prompt_tokens_details
        # for clients reading the modern nested shape. One prompt, one
        # number — n/best_of candidates share the prompt, so the field
        # is the MAX any candidate reused (summing would exceed
        # prompt_tokens and break clients computing the uncached
        # remainder), never above prompt_tokens itself.
        if any("cached_tokens" in r for r in results):
            cached = min(usage["prompt_tokens"],
                         max(r.get("cached_tokens") or 0
                             for r in results))
            usage["cached_tokens"] = cached
            usage["prompt_tokens_details"] = {"cached_tokens": cached}
        # cancelled terminal state (deadline / disconnect): count over the
        # RETURNED choices only — a discarded best_of candidate that was
        # cancelled must not flag a fully-delivered answer as partial
        # (its tokens still bill via completion_tokens, like any other
        # discarded candidate's)
        n_cancelled = sum(r["finish_reason"] == "cancelled"
                          for r in results[:n_choices])
        if n_cancelled:
            usage["cancelled"] = n_cancelled
        # phase split (queue_wait_ms / prefill_ms / decode_ms): present
        # only when the model runs usage_timing (shape unchanged
        # otherwise — the cached_tokens precedent). One request, one
        # split: n/best_of clones report the first returned choice's.
        timed = next((r for r in results if r.get("timing")), None)
        if timed:
            self._usage_timing(usage, timed, t_enter)
        return 200, {
            "object": "chat.completion" if chat else "text_completion",
            "model": m.name, "choices": choices,
            # completion_tokens counts EVERY generated token (including
            # best_of candidates that were not returned) — the tokens the
            # accelerator actually produced; total_tokens is their sum
            # (the field OpenAI clients read for billing/limits)
            "usage": usage}

    @staticmethod
    def _usage_timing(usage: dict[str, Any], timed: dict[str, Any],
                      t_enter: float | None,
                      t_first_write: float | None = None) -> None:
        """A `usage_timing` model's phase split into the usage object,
        and the server thread's own two spans on the same clock:
        `pre_submit_ms` (handler entry -> the submit instant that
        queue_wait_ms starts from) and, streaming only,
        `first_write_lag_ms` (the engine's first token, i.e. the end of
        prefill_ms, -> the first SSE chunk written), beside the stream's
        own `stream_write_lag_max_ms` (the longest any token waited from
        its append to the stream picking it up). With the client's
        send-to-first-token they split the HTTP overhead into the
        server's two halves and, by subtraction, the router's relay."""
        timing = timed["timing"]
        for k, v in timing.items():
            if v is not None:
                usage[k] = v
        if "write_lag_max_ms" in timed:
            usage["stream_write_lag_max_ms"] = timed["write_lag_max_ms"]
        sub = timed.get("submit_s")
        if sub is None:
            return
        if t_enter is not None:
            usage["pre_submit_ms"] = round((sub - t_enter) * 1e3, 3)
        if (t_first_write is not None
                and timing.get("queue_wait_ms") is not None
                and timing.get("prefill_ms") is not None):
            first_token_s = sub + (timing["queue_wait_ms"]
                                   + timing["prefill_ms"]) / 1e3
            usage["first_write_lag_ms"] = round(
                (t_first_write - first_token_s) * 1e3, 3)

    def _stream_completion(self, handler, body: dict[str, Any],
                           chat: bool = False,
                           trace: str | None = None,
                           t_enter: float | None = None) -> None:
        """Server-sent events: one `data: {...}` chunk per token carrying
        the incremental TEXT delta (multi-byte sequences decode across
        chunk boundaries), a final chunk with finish_reason, then
        `data: [DONE]`. Connection: close (progressive writes without
        chunked framing). An ISVC Router relays this progressively
        (stream-aware failover, r11) — streaming works through the
        routed dataplane, not just the predictor's own port."""
        from kubeflow_tpu.serving.tokenizer import StreamDecoder

        finish: list[str] = []
        t_mono = time.monotonic()
        try:
            m, payload = self._completion_request(body, chat)
            if trace:
                payload["trace"] = str(trace)
            if payload.get("best_of", 1) > 1 or payload.get("n", 1) > 1:
                raise ProtocolError(
                    "streaming supports n=1 / best_of=1 only")
            # m.stream submits eagerly: PromptTooLong/QueueFull raise HERE,
            # before the 200 + SSE headers are committed. stream_info is
            # filled at finish time (cached_tokens for the usage chunk).
            stream_info: dict[str, Any] = {}
            token_iter = m.stream(payload, on_finish=finish.append,
                                  info=stream_info)
        except self._completion_exceptions() as e:
            return handler._send(*self._completion_error(e))
        t0 = time.perf_counter()
        handler.send_response(200)
        handler.send_header("Content-Type", "text/event-stream")
        handler.send_header("Cache-Control", "no-cache")
        handler.send_header("Connection", "close")
        handler.end_headers()
        handler.close_connection = True
        decoder = StreamDecoder(m.tokenizer)
        first = [True]
        want_lp = payload.get("want_logprobs")
        n_sent = 0
        t_first_write = None

        def chunk_of(text: str, token_id: int | None = None,
                     reason: str | None = None,
                     logprob: float | None = None,
                     usage: dict[str, Any] | None = None) -> bytes:
            choice: dict[str, Any] = {"index": 0, "finish_reason": reason}
            if chat:
                choice["delta"] = ({"role": "assistant", "content": text}
                                   if first[0] else {"content": text})
                first[0] = False
            else:
                choice["text"] = text
            if token_id is not None:
                choice["token_id"] = token_id
            if logprob is not None:
                choice["logprob"] = logprob
            body: dict[str, Any] = {
                "object": ("chat.completion.chunk" if chat
                           else "text_completion.chunk"),
                "model": m.name, "choices": [choice]}
            if usage is not None:
                body["usage"] = usage
            return ("data: " + json.dumps(body) + "\n\n").encode()

        try:   # everything after the headers: a disconnect anywhere here
               # must not fall back to do_POST's JSON 500 on this socket
            if payload.get("echo"):
                # echo streams the prompt text as the first chunk
                handler.wfile.write(chunk_of(
                    m.tokenizer.decode(list(payload["prompt_tokens"]))))
                handler.wfile.flush()
            try:
                for tok, lp in token_iter:
                    if _client_gone(handler.connection):
                        # detected BEFORE the kernel buffer masks it: jump
                        # to the disconnect path, which closes the
                        # generator and cancels the engine request
                        raise BrokenPipeError("stream client disconnected")
                    if tok is None:
                        # keepalive sentinel (a supervised engine mid-
                        # restart): an SSE comment keeps the connection
                        # alive without touching the event stream — and
                        # writing it is itself a disconnect probe, so a
                        # client that vanished during the outage frees
                        # its journal slot now, not at the next token
                        handler.wfile.write(b": keepalive\n\n")
                        handler.wfile.flush()
                        continue
                    n_sent += 1
                    handler.wfile.write(chunk_of(
                        decoder.push(tok), token_id=int(tok),
                        logprob=(float(lp) if want_lp else None)))
                    handler.wfile.flush()
                    if t_first_write is None:
                        t_first_write = time.monotonic()
            except (BrokenPipeError, ConnectionResetError, OSError):
                # the SOCKET died, not the engine: this must reach the
                # disconnect path below — the generic handler would "write"
                # an error chunk into the dead socket's userspace buffer,
                # appear to succeed, and abandon the request unc ancelled
                raise
            except Exception as e:
                handler.wfile.write(
                    f"data: {json.dumps({'error': str(e)})}\n\n".encode())
            else:
                tail = decoder.flush()
                reason = finish[0] if finish else "length"
                # the final chunk carries the usage object; a deadline-
                # cancelled stream (engine finish_reason "cancelled")
                # surfaces its terminal state HERE — the client sees how
                # many tokens were actually delivered and why it ended
                n_prompt = len(payload["prompt_tokens"])
                usage = {"prompt_tokens": n_prompt,
                         "completion_tokens": n_sent,
                         "total_tokens": n_prompt + n_sent}
                if "cached_tokens" in stream_info:
                    usage["cached_tokens"] = stream_info["cached_tokens"]
                    usage["prompt_tokens_details"] = {
                        "cached_tokens": stream_info["cached_tokens"]}
                if stream_info.get("timing"):   # usage_timing models only
                    self._usage_timing(usage, stream_info, t_enter,
                                       t_first_write)
                if reason == "cancelled":
                    # same type as the buffered path: a COUNT of
                    # cancelled returned choices (a stream has one)
                    usage["cancelled"] = 1
                handler.wfile.write(chunk_of(tail, reason=reason,
                                             usage=usage))
            handler.wfile.write(b"data: [DONE]\n\n")
            handler.wfile.flush()
        except (BrokenPipeError, ConnectionResetError, OSError):
            return   # client hung up mid-stream; the finally CLOSES the
                     # generator, whose GeneratorExit path cancels the
                     # engine request — the decode slot frees at the next
                     # chunk boundary instead of burning to max_new_tokens
                     # (SURVEY §2.6 Triton-class cancellation)
        finally:
            # no-op when the stream drained or errored to completion;
            # the live-generator case (disconnect) cancels + releases
            token_iter.close()
        self._observe(m.name, "completions", time.perf_counter() - t0)
        TRACER.record_span("server.http", "http", trace, t_mono,
                           time.monotonic(), model=m.name,
                           verb="completions", streamed=True,
                           tokens_sent=n_sent)

    # -- dataplanes -----------------------------------------------------------

    def _predictor(self, model: Model):
        cfg = self._batch_cfg.get(model.name)
        if not cfg:
            return model.predict
        with self._metrics_lock:  # guards _batchers too: two concurrent
            # first requests must not each spawn a batcher worker thread
            if model.name not in self._batchers:
                self._batchers[model.name] = DynamicBatcher(
                    model.predict,
                    max_batch_size=int(cfg.get("maxBatchSize", 16)),
                    max_latency_ms=float(cfg.get("maxLatencyMs", 5.0)))
            return self._batchers[model.name]

    def _observe(self, model: str, verb: str, dt: float) -> None:
        with self._metrics_lock:
            key = (model, verb)
            self.request_count[key] = self.request_count.get(key, 0) + 1
            self.latency_sum[model] = self.latency_sum.get(model, 0.0) + dt
        # the same observation feeds the process registry (GET /metrics
        # prometheus text); the per-instance dicts above stay the
        # metrics() JSON view so its shape survives multi-server tests
        # sharing one process registry
        obs_metrics.HTTP_REQUESTS.inc(model=model, verb=verb)
        obs_metrics.HTTP_LATENCY.observe(dt, model=model, verb=verb)

    def _logged(self, name: str, t0: float, code: int,
                resp: dict[str, Any], rid: str | None
                ) -> tuple[int, dict[str, Any]]:
        if self.payload_logger is not None and rid is not None:
            self.payload_logger.log_response(
                name, rid, resp, (time.perf_counter() - t0) * 1e3, code)
        return code, resp

    def _log_request(self, name: str, body: dict[str, Any]) -> str | None:
        if self.payload_logger is None:
            return None
        rid = self.payload_logger.next_id()
        self.payload_logger.log_request(name, rid, body)
        return rid

    def _log_error(self, name: str, t0: float, rid: str | None,
                   exc: Exception) -> None:
        """Pair error responses with their request records (the exception is
        converted to an HTTP status by _handle_post; mirror that here)."""
        if self.payload_logger is None or rid is None:
            return
        code = (400 if isinstance(exc, ProtocolError)
                else 404 if isinstance(exc, ModelError) else 500)
        self._logged(name, t0, code, {"error": str(exc)}, rid)

    def _v1(self, name: str, verb: str, body: dict[str, Any]
            ) -> tuple[int, dict[str, Any]]:
        rid = self._log_request(name, body)
        t0 = time.perf_counter()
        try:
            model = self.repository.get(name)
            if not model.ready:
                return self._logged(name, t0, 503,
                                    {"error": f"model {name!r} not ready"},
                                    rid)
            instances = v1_decode(body)
            t_infer = time.perf_counter()  # /metrics latency excludes decode
            payload = model.preprocess(instances)
            if verb == "predict":
                result = self._predictor(model)(payload)
            elif verb == "explain":
                result = model.explain(payload)
            else:
                return self._logged(name, t0, 400,
                                    {"error": f"unknown verb {verb!r}"}, rid)
            result = model.postprocess(result)
            self._observe(name, verb, time.perf_counter() - t_infer)
            return self._logged(name, t0, 200, v1_encode(result), rid)
        except Exception as e:
            self._log_error(name, t0, rid, e)
            raise

    def _v2_infer(self, name: str, body: dict[str, Any]
                  ) -> tuple[int, dict[str, Any]]:
        rid = self._log_request(name, body)
        t0 = time.perf_counter()
        try:
            model = self.repository.get(name)
            if not model.ready:
                return self._logged(name, t0, 503,
                                    {"error": f"model {name!r} not ready"},
                                    rid)
            req = InferRequest.from_json(name, body)
            t_infer = time.perf_counter()
            payload = model.preprocess(req.as_dict())
            result = model.postprocess(self._predictor(model)(payload))
            self._observe(name, "infer", time.perf_counter() - t_infer)
            return self._logged(
                name, t0, 200,
                InferResponse.from_result(name, result, id=req.id).to_json(),
                rid)
        except Exception as e:
            self._log_error(name, t0, rid, e)
            raise

    # -- metrics --------------------------------------------------------------

    def _metrics(self) -> dict[str, Any]:
        with self._metrics_lock:
            return {
                "request_count": {f"{m}:{v}": n for (m, v), n
                                  in self.request_count.items()},
                "latency_sum_s": dict(self.latency_sum),
            }

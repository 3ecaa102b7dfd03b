"""Request-scoped tracing: Trace/Span context, bounded ring buffer,
JSONL export (ISSUE 17 tentpole, piece 1).

Design constraints, in order:

1. ZERO hot-loop cost when sampled out. The sampling decision is a
   deterministic hash of the trace id, so every layer of one request —
   router, supervisor, engine, roles, stages — independently reaches
   the SAME keep/drop verdict without coordination, and a dropped
   trace costs one blake2b per span site, no allocation.
2. The hot decode loop NEVER creates per-token spans. Engines keep the
   timestamps they already track (submit/first-token/finish) and emit
   ONE retrospective span per request per phase via ``record_span``.
   What the engine THREAD does between those instants is the
   ``PhaseClock``'s: a closed set of named phases (``PHASES``) that
   partitions the thread's timeline, plus the per-step counters
   (steps, tokens) that annotate the decode span.
   scripts/check_observability.py enforces this statically.
3. Spans are plain dict-shaped facts in a bounded deque — an exporter
   crash or an unscraped buffer can only ever cost old spans
   (``dropped`` counts them), never memory.

Span kinds, the taxonomy (docs/ARCHITECTURE.md "Observability"):
``http`` (router relay / server handler), ``supervise`` (journal
lifetime incl. crash-replay chain), ``admit``, ``queue``, ``prefill``,
``handoff``, ``decode``, ``stage`` (pp microbatch wave), ``restart``,
``replay``, ``stall`` (one engine phase occurrence of ``STALL_NS`` or
more; recorded by the ``PhaseClock`` itself, sampled or not).

Clocks. Spans and the ``PhaseClock`` are on ``time.monotonic`` (the
clock ``request_timing`` uses; ``CLOCK_MONOTONIC`` on Linux, where
``perf_counter`` reads it too); the phases are also, through
``jax.profiler.TraceAnnotation``, on the profiler's own. One
process-wide anchor pair taken at import (``ANCHOR_MONOTONIC_NS``,
``ANCHOR_UNIX_NS``) lets ``Span.to_json`` add ``start_unix_ns``, which
is how a JSONL export is laid over a profiler trace.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Callable, NamedTuple

log = logging.getLogger(__name__)

#: one instant read on both clocks: monotonic seconds + this offset =
#: Unix time (as far as the wall clock was right at import)
ANCHOR_MONOTONIC_NS = time.monotonic_ns()
ANCHOR_UNIX_NS = time.time_ns()

#: HTTP header carrying the trace id across the router → server hop.
TRACE_HEADER = "X-Trace-Id"

_SAMPLE_SALT = b"ktpu-trace-v1"


def new_trace_id() -> str:
    """128-bit random hex — mint once at the edge (router or submit)."""
    return os.urandom(16).hex()


def _new_span_id() -> str:
    return os.urandom(8).hex()


class Span:
    """One timed hop of one request. Mutable until ``end()``; appended
    to the sink at end-time so half-open spans never export."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "kind",
                 "start_s", "end_s", "attrs", "_sink")

    def __init__(self, trace_id: str, name: str, kind: str,
                 parent_id: str | None = None, start_s: float | None = None,
                 attrs: dict[str, Any] | None = None,
                 sink: "SpanSink | None" = None):
        self.trace_id = trace_id
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        self.name = name
        self.kind = kind
        self.start_s = time.monotonic() if start_s is None else start_s
        self.end_s: float | None = None
        self.attrs: dict[str, Any] = dict(attrs or {})
        self._sink = sink

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def end(self, end_s: float | None = None, **attrs: Any) -> "Span":
        if self.end_s is not None:   # idempotent: double-end exports once
            return self
        self.end_s = time.monotonic() if end_s is None else end_s
        if attrs:
            self.attrs.update(attrs)
        if self._sink is not None:
            self._sink.append(self)
        return self

    def duration_ms(self) -> float | None:
        if self.end_s is None:
            return None
        return round((self.end_s - self.start_s) * 1e3, 3)

    def to_json(self) -> dict[str, Any]:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name,
                "kind": self.kind, "start_s": self.start_s,
                "start_unix_ns": (int(self.start_s * 1e9)
                                  - ANCHOR_MONOTONIC_NS + ANCHOR_UNIX_NS),
                "end_s": self.end_s, "duration_ms": self.duration_ms(),
                "attrs": self.attrs}

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


class _NoopSpan:
    """The sampled-out stand-in: absorbs set/end/ctx use for free."""

    __slots__ = ()
    trace_id = span_id = parent_id = None
    end_s = None

    def set(self, **attrs: Any) -> "_NoopSpan":
        return self

    def end(self, end_s: float | None = None, **attrs: Any) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NOOP_SPAN = _NoopSpan()


class SpanSink:
    """Bounded in-process ring buffer of ended spans."""

    def __init__(self, capacity: int = 4096):
        self._lock = threading.Lock()
        self._buf: deque[Span] = deque(maxlen=max(1, int(capacity)))
        self.dropped = 0

    @property
    def capacity(self) -> int:
        return self._buf.maxlen or 0

    def append(self, span: Span) -> None:
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(span)

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    def spans(self, trace_id: str | None = None) -> list[Span]:
        with self._lock:
            snap = list(self._buf)
        if trace_id is None:
            return snap
        return [s for s in snap if s.trace_id == trace_id]

    def export_jsonl(self, path: str | None = None,
                     trace_id: str | None = None) -> str:
        """One span per line, oldest first; optionally also written to
        ``path`` (the operator's trace-dump surface)."""
        text = "\n".join(json.dumps(s.to_json(), sort_keys=True)
                         for s in self.spans(trace_id))
        if text:
            text += "\n"
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self.dropped = 0


class Tracer:
    """Sampling + span minting over one sink. ``sample_rate`` in [0,1];
    the decision is a pure function of the trace id so every layer
    agrees without sharing state."""

    def __init__(self, sink: SpanSink | None = None,
                 sample_rate: float = 1.0):
        self.sink = sink if sink is not None else SpanSink()
        self.sample_rate = float(sample_rate)

    def set_sample_rate(self, rate: float) -> float:
        self.sample_rate = min(1.0, max(0.0, float(rate)))
        return self.sample_rate

    def sampled(self, trace_id: str | None) -> bool:
        if not trace_id or self.sample_rate <= 0.0:
            return False
        if self.sample_rate >= 1.0:
            return True
        h = hashlib.blake2b(_SAMPLE_SALT + trace_id.encode(),
                            digest_size=8).digest()
        return int.from_bytes(h, "big") < self.sample_rate * 2.0**64

    def span(self, name: str, kind: str, trace_id: str | None,
             parent_id: str | None = None, start_s: float | None = None,
             **attrs: Any) -> Span | _NoopSpan:
        """Open a live span (context-manager friendly); exported when
        ended. Sampled-out (or traceless) calls return the shared noop."""
        if not self.sampled(trace_id):
            return NOOP_SPAN
        return Span(trace_id, name, kind, parent_id=parent_id,
                    start_s=start_s, attrs=attrs, sink=self.sink)

    def record_span(self, name: str, kind: str, trace_id: str | None,
                    start_s: float, end_s: float,
                    parent_id: str | None = None, **attrs: Any) -> None:
        """Retrospective span from timestamps a layer already kept —
        the ONLY emission style allowed on engine hot paths (zero cost
        until the request finishes, nothing per token)."""
        if start_s is None or end_s is None or not self.sampled(trace_id):
            return
        Span(trace_id, name, kind, parent_id=parent_id, start_s=start_s,
             attrs=attrs, sink=self.sink).end(end_s=end_s)


class StepAggregator:
    """Per-step counter bumps only (no spans, no allocation), reduced to
    attrs for the ONE decode span a request gets. The engine's
    ``PhaseClock`` is one of these; snapshots of ``steps``/``tokens`` at
    first token and at finish annotate the retrospective decode span."""

    __slots__ = ("steps", "tokens")

    def __init__(self):
        self.steps = 0
        self.tokens = 0

    def note_step(self, n_tokens: int, steps: int = 1) -> None:
        """Count one dispatch (or a fused chunk of ``steps``) delivering
        up to ``n_tokens`` across the batch."""
        self.steps += int(steps)
        self.tokens += int(n_tokens)

    def snapshot(self) -> tuple[int, int]:
        return self.steps, self.tokens

    @staticmethod
    def window(at_start: tuple[int, int],
               at_end: tuple[int, int]) -> dict[str, int]:
        return {"decode_steps": at_end[0] - at_start[0],
                "decode_tokens": at_end[1] - at_start[1]}


# -- the engine thread's phase clock ------------------------------------------

#: the closed set: every instant of a driven engine thread lies in
#: exactly one (docs/ARCHITECTURE.md "Observability" says what runs in
#: each)
PHASES = ("idle", "sched", "prefill_pack", "prefill_dispatch",
          "prefix_bank", "prefill_fetch", "decode_plan", "decode_dispatch",
          "decode_fetch", "replay")
#: phases whose start is a program call: the device stops being empty
_DISPATCH = frozenset(("prefill_dispatch", "decode_dispatch"))
_ANNOTATION = {p: f"engine.{p}" for p in PHASES}
#: one non-idle phase occurrence this long is a stall (no config key)
STALL_NS = 500_000_000


class PhaseMark(NamedTuple):
    """The clock read at one instant; the open phase counted up to it."""
    at_ns: int
    ns: tuple[int, ...]          # per PHASES
    counts: tuple[int, ...]
    device_empty_ns: int
    steps: int
    tokens: int
    kv_blocks: tuple[int, int] = (0, 0)   # fetched, spanned


class PhaseClock(StepAggregator):
    """Partitions the engine thread's timeline into ``PHASES``.

    ``enter(phase)`` ends the phase before: two ``time.monotonic_ns``
    reads and one ``jax.profiler.TraceAnnotation`` per transition (a
    flag check when no profiler runs; under a capture the phases lie on
    the engine thread's host line, on the profiler's clock), nothing per
    token. Single writer: the engine thread; ``/metrics`` reads what
    has closed, ``usage`` takes ``mark()``s on the engine thread.

    ``device_empty_ns`` is an overlay, not a phase: from a fetch that
    left nothing dispatched and unfetched (``fetched(False)``) to the
    next program call. The device is certainly idle then, so the sum is
    a floor under a trace's idle share that needs no profiler.

    A driver that owns the whole thread (``LLMModel._loop``) sets
    ``hold_open``; without it ``leave()`` stops the clock between
    ``step()`` calls, so a caller's own time is nobody's phase."""

    __slots__ = ("engine", "context", "hold_open", "ns", "counts",
                 "device_empty_ns", "kv_blocks_fetched", "kv_blocks_spanned",
                 "_cur", "_t0", "_ann", "_empty_since",
                 "_longest", "_published", "_publish_lock", "_annotate")

    def __init__(self, engine: str = "engine",
                 context: Callable[[], dict[str, Any]] | None = None):
        super().__init__()
        self.engine = engine
        #: what a stall line reports beside the phase (in flight,
        #: queued, active); called on the engine thread, rarely
        self.context = context
        self.hold_open = False
        self.ns = dict.fromkeys(PHASES, 0)
        self.counts = dict.fromkeys(PHASES, 0)
        self.device_empty_ns = 0
        #: decode attention's KV blocks, per dispatch (note_kv_blocks)
        self.kv_blocks_fetched = 0
        self.kv_blocks_spanned = 0
        self._cur: str | None = None
        self._t0 = 0
        self._ann = None
        self._empty_since: int | None = None
        # (duration_ns, end_ns, phase), durations falling from the left:
        # an occurrence shorter than a later one can never again be the
        # longest of a window that ends after both
        self._longest: deque[tuple[int, int, str]] = deque(maxlen=64)
        self._published = dict(self.ns, device_empty=0, kv_fetched=0,
                               kv_spanned=0)
        self._publish_lock = threading.Lock()
        try:
            from jax.profiler import TraceAnnotation
        except Exception:    # the obs layer itself needs no jax
            TraceAnnotation = None
        self._annotate = TraceAnnotation

    # -- the engine thread ----------------------------------------------------

    def enter(self, phase: str) -> None:
        if phase == self._cur:
            return
        now = time.monotonic_ns()
        if self._cur is not None:
            self._close(now)
        if self._empty_since is not None and phase in _DISPATCH:
            self.device_empty_ns += now - self._empty_since
            self._empty_since = None
        self._cur = phase
        self._t0 = now
        self.counts[phase] += 1
        if self._annotate is not None:
            self._ann = self._annotate(_ANNOTATION[phase])
            self._ann.__enter__()

    def leave(self) -> None:
        """End of one driven step: stop the clock unless the driver
        holds the thread (``hold_open``)."""
        if self._cur is not None and not self.hold_open:
            self._close(time.monotonic_ns())
            self._cur = None

    def note_kv_blocks(self, fetched: int, spanned: int) -> None:
        """One decode dispatch: of the `spanned` KV blocks its attention
        grid covers (slots x span / block), the lengths handed to the
        kernel let `fetched` through (ops/flash_decode.py: a block past
        a slot's context is not copied)."""
        self.kv_blocks_fetched += int(fetched)
        self.kv_blocks_spanned += int(spanned)

    def fetched(self, outstanding: bool) -> None:
        """A device fetch returned; ``outstanding``: some program is
        still dispatched and unfetched."""
        if not outstanding and self._empty_since is None:
            self._empty_since = time.monotonic_ns()

    def _close(self, now: int) -> None:
        cur = self._cur
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        d = now - self._t0
        self.ns[cur] += d
        if cur == "idle":
            return
        longest = self._longest
        while longest and longest[-1][0] <= d:
            longest.pop()
        longest.append((d, now, cur))
        if d >= STALL_NS:
            self._stall(cur, d, now)

    def _stall(self, phase: str, d: int, end_ns: int) -> None:
        from kubeflow_tpu.obs import metrics as obs_metrics

        ctx = {}
        if self.context is not None:
            try:
                ctx = dict(self.context())
            except Exception:    # telemetry never takes the engine down
                pass
        ms = round(d / 1e6, 3)
        log.warning("engine stall: %s phase %s lasted %.1f ms (%s)",
                    self.engine, phase, ms,
                    ", ".join(f"{k}={v}" for k, v in ctx.items()))
        obs_metrics.ENGINE_STALLS.inc(engine=self.engine, phase=phase)
        # a span of its own, sampled or not: stalls are rare by
        # construction, and the ring is where an operator looks first
        end_s = end_ns / 1e9
        Span("", f"{self.engine}.stall", "stall", start_s=end_s - d / 1e9,
             attrs=dict(ctx, phase=phase, duration_ms=ms),
             sink=TRACER.sink).end(end_s=end_s)

    # -- readers --------------------------------------------------------------

    def mark(self) -> PhaseMark:
        now = time.monotonic_ns()
        cur, t0, since = self._cur, self._t0, self._empty_since
        ns = [self.ns[p] for p in PHASES]
        if cur is not None:
            ns[PHASES.index(cur)] += now - t0
        return PhaseMark(
            now, tuple(ns), tuple(self.counts[p] for p in PHASES),
            self.device_empty_ns + (now - since if since is not None else 0),
            self.steps, self.tokens,
            (self.kv_blocks_fetched, self.kv_blocks_spanned))

    def longest_since(self, start_s: float,
                      now_ns: int) -> tuple[int, str] | None:
        """(ns, phase) of the longest single non-idle occurrence that
        overlapped [start_s (time.monotonic), now_ns]; the open one
        counts up to ``now_ns``. Engine thread only."""
        start_ns = int(start_s * 1e9)
        best = None
        for d, end_ns, phase in self._longest:    # longest first
            if end_ns > start_ns:
                best = (d, phase)
                break
        if self._cur not in (None, "idle"):
            d = now_ns - self._t0
            if best is None or d > best[0]:
                best = (d, self._cur)
        return best

    def usage(self, first: PhaseMark,
              submit_s: float | None) -> dict[str, Any]:
        """The ``engine`` object of a request's ``usage``: what the
        engine thread did from ``first`` (its first token) to now (its
        finish), the decode dispatches' ``kv_blocks`` [fetched, spanned]
        in that window, and the longest occurrence since ``submit_s``."""
        end = self.mark()
        out: dict[str, Any] = {
            "phases": {
                p: [round((e - s) / 1e6, 3), ce - cs]
                for p, s, e, cs, ce in zip(PHASES, first.ns, end.ns,
                                           first.counts, end.counts)
                if e > s or ce > cs},
            "device_empty_ms": round(
                (end.device_empty_ns - first.device_empty_ns) / 1e6, 3)}
        if end.kv_blocks[1] > first.kv_blocks[1]:
            out["kv_blocks"] = [e - s for s, e in zip(first.kv_blocks,
                                                      end.kv_blocks)]
        longest = (self.longest_since(submit_s, end.at_ns)
                   if submit_s is not None else None)
        if longest is not None:
            out["phase_max_ms"] = round(longest[0] / 1e6, 3)
            out["phase_max"] = longest[1]
        return out

    def publish(self) -> None:
        """Scrape-hook body: add what closed since the last scrape to
        the two cumulative series."""
        from kubeflow_tpu.obs import metrics as obs_metrics

        with self._publish_lock:    # two scrapes must not add one delta twice
            last = self._published
            now = dict(self.ns, device_empty=self.device_empty_ns,
                       kv_fetched=self.kv_blocks_fetched,
                       kv_spanned=self.kv_blocks_spanned)
            self._published = now
        for p in PHASES:
            obs_metrics.ENGINE_PHASE_SECONDS.inc(
                (now[p] - last[p]) / 1e9, engine=self.engine, phase=p)
        obs_metrics.ENGINE_DEVICE_EMPTY_SECONDS.inc(
            (now["device_empty"] - last["device_empty"]) / 1e9,
            engine=self.engine)
        obs_metrics.ENGINE_KV_BLOCKS_FETCHED.inc(
            now["kv_fetched"] - last["kv_fetched"], engine=self.engine)
        obs_metrics.ENGINE_KV_BLOCKS_SPANNED.inc(
            now["kv_spanned"] - last["kv_spanned"], engine=self.engine)


#: the process tracer every layer shares (tests may swap the sink).
TRACER = Tracer()

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
   partitions the thread's timeline and says who had its time (the
   thread's CPU, the device, the collector), plus the per-step
   counters (steps, tokens) that annotate the decode span. The
   Trainer's loop runs on the same class (``TRAINER_PHASES``).
   scripts/check_observability.py enforces this statically.
3. Spans are plain dict-shaped facts in a bounded deque — an exporter
   crash or an unscraped buffer can only ever cost old spans
   (``dropped`` counts them), never memory.

Span kinds, the taxonomy (docs/ARCHITECTURE.md "Observability"):
``http`` (router relay / server handler), ``supervise`` (journal
lifetime incl. crash-replay chain), ``queue``, ``prefill``,
``handoff``, ``decode``, ``stage`` (pp microbatch wave), ``restart``,
``replay``, ``stall`` (one phase occurrence of ``STALL_NS`` or more on
a loop thread; recorded by the ``PhaseClock`` itself, sampled or not).

Clocks. Spans and the ``PhaseClock`` are on ``time.monotonic`` (the
clock ``request_timing`` uses; ``CLOCK_MONOTONIC`` on Linux, where
``perf_counter`` reads it too); the phases are also, through
``jax.profiler.TraceAnnotation``, on the profiler's own. One
process-wide anchor pair taken at import (``ANCHOR_MONOTONIC_NS``,
``ANCHOR_UNIX_NS``) lets ``Span.to_json`` add ``start_unix_ns``, which
is how a JSONL export is laid over a profiler trace.
"""

from __future__ import annotations

import gc
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


# -- what stops every thread: the collector's pauses ---------------------------


class GcPauses:
    """The collector's pauses on ``time.monotonic_ns``: a collection holds
    the interpreter, so every thread of the process stands still for it
    whichever thread set it off. ONE instance (``GC``), fed by one
    ``gc.callbacks`` hook registered at import; collections do not nest,
    so the hook is the single writer. Every ``PhaseClock`` reads
    ``total_ns`` at its transitions; ``/metrics`` gets the seconds by
    generation and the longest pause."""

    __slots__ = ("total_ns", "ns", "longest_ns", "_t0", "_published",
                 "_publish_lock")

    def __init__(self):
        self.total_ns = 0
        self.ns = [0, 0, 0]          # by generation
        self.longest_ns = 0
        self._t0: int | None = None
        self._published = [0, 0, 0]
        self._publish_lock = threading.Lock()

    def __call__(self, phase: str, info: dict[str, Any]) -> None:
        now = time.monotonic_ns()
        if phase == "start":
            self._t0 = now
        elif self._t0 is not None:
            d = now - self._t0
            self._t0 = None
            self.ns[info["generation"]] += d
            self.total_ns += d
            if d > self.longest_ns:
                self.longest_ns = d

    def publish(self) -> None:
        """Scrape-path body (``obs.metrics.render_metrics``)."""
        from kubeflow_tpu.obs import metrics as obs_metrics

        with self._publish_lock:    # two scrapes must not add one delta twice
            last, now = self._published, list(self.ns)
            self._published = now
        for gen, (a, b) in enumerate(zip(last, now)):
            obs_metrics.GC_PAUSE_SECONDS.inc((b - a) / 1e9,
                                             generation=str(gen))
        obs_metrics.GC_PAUSE_MAX_SECONDS.set(self.longest_ns / 1e9)


GC = GcPauses()
gc.callbacks.append(GC)


# -- a loop thread's phase clock -----------------------------------------------

#: the engine thread's closed set: every instant of a driven engine
#: thread lies in exactly one (docs/ARCHITECTURE.md "Observability" says
#: what runs in each, and what wall less CPU means there)
PHASES = ("idle", "sched", "prefill_pack", "prefill_dispatch",
          "prefix_bank", "prefill_fetch", "decode_plan", "decode_dispatch",
          "decode_fetch", "replay")
#: phases whose start is a program call: the device stops being empty
_DISPATCH = frozenset(("prefill_dispatch", "decode_dispatch"))
#: phases in which the thread is meant to wait
_WAITS = frozenset(("idle",))
#: the Trainer's loop (training/trainer.py) on the same clock
TRAINER_PHASES = ("data_wait", "dispatch", "fetch", "log", "checkpoint",
                  "profile")
#: one occurrence this long is a stall (no config key)
STALL_NS = 500_000_000


class PhaseMark(NamedTuple):
    """The clock read at one instant; the open phase counted up to it.
    The tuples are per the clock's ``phases``."""
    at_ns: int
    ns: tuple[int, ...]
    counts: tuple[int, ...]
    device_empty_ns: int
    steps: int
    tokens: int
    kv_blocks: tuple[int, int] = (0, 0)   # fetched, spanned
    cpu_ns: tuple[int, ...] = ()
    device_empty_by_phase: tuple[int, ...] = ()
    gc_ns: int = 0


class PhaseClock(StepAggregator):
    """Partitions one loop thread's timeline into ``phases`` (the engine
    thread's ``PHASES`` unless told otherwise; the Trainer's loop hands
    ``TRAINER_PHASES``).

    ``enter(phase)`` ends the phase before: one read each of
    ``time.monotonic_ns`` and ``time.thread_time_ns`` and one
    ``jax.profiler.TraceAnnotation("<engine>.<phase>")`` per transition
    (a flag check when no profiler runs; under a capture the phases lie
    on the thread's host line, on the profiler's clock), nothing per
    token. Single writer: the loop thread; ``/metrics`` reads what has
    closed, ``usage`` takes ``mark()``s on the loop thread.

    Beside each phase's wall (``ns``) stand the thread's own CPU time
    in it (``cpu_ns``: wall less CPU is what the thread WAITED, for the
    device in a fetch, for the runtime's queue in a dispatch, for the
    interpreter or the OS anywhere else; accumulated as the CPU clock
    reads, so sums over many occurrences are fair whatever its tick)
    and the collector's pauses that ended in it (``gc_ns``, from
    ``GC``).

    ``device_empty_ns`` is an overlay, not a phase: from a fetch that
    left nothing dispatched and unfetched (``fetched(False)``) to the
    next program call (a phase of ``dispatch``). The device is certainly
    idle then, so the sum is a floor under a trace's idle share that
    needs no profiler. It accrues phase by phase as each closes
    (``device_empty_by_phase``), so the split sums to it exactly.

    A phase of ``waits`` (``idle`` here, the Trainer's ``fetch``) is
    where the thread is meant to wait: an occurrence is neither a stall
    nor a candidate for the longest one, UNLESS it began with
    ``context()`` reporting queued or active work.

    A driver that owns the whole thread (``LLMModel._loop``,
    ``Trainer.train``) sets ``hold_open``; without it ``leave()`` stops
    the clock between ``step()`` calls, so a caller's own time is
    nobody's phase (nor device-empty time)."""

    __slots__ = ("engine", "context", "hold_open", "phases", "ns", "cpu_ns",
                 "gc_ns", "counts", "device_empty_by_phase",
                 "kv_blocks_fetched", "kv_blocks_spanned",
                 "_dispatch", "_waits", "_annotation", "_cur", "_t0", "_c0",
                 "_g0", "_wait_busy", "_ann", "_empty_since",
                 "_longest", "_published", "_publish_lock", "_annotate")

    def __init__(self, engine: str = "engine",
                 context: Callable[[], dict[str, Any]] | None = None,
                 phases: tuple[str, ...] = PHASES,
                 dispatch: frozenset[str] = _DISPATCH,
                 waits: frozenset[str] = _WAITS):
        super().__init__()
        self.engine = engine
        #: what a stall line reports beside the phase (in flight,
        #: queued, active); called on the loop thread: once per stall
        #: and once at the start of each waiting phase
        self.context = context
        self.hold_open = False
        self.phases = phases
        self._dispatch = dispatch
        self._waits = waits
        self._annotation = {p: f"{engine}.{p}" for p in phases}
        self.ns = dict.fromkeys(phases, 0)
        self.cpu_ns = dict.fromkeys(phases, 0)
        self.gc_ns = dict.fromkeys(phases, 0)
        self.counts = dict.fromkeys(phases, 0)
        self.device_empty_by_phase = dict.fromkeys(phases, 0)
        #: decode attention's KV blocks, per dispatch (note_kv_blocks)
        self.kv_blocks_fetched = 0
        self.kv_blocks_spanned = 0
        self._cur: str | None = None
        self._t0 = self._c0 = self._g0 = 0
        self._wait_busy = False
        self._ann = None
        self._empty_since: int | None = None
        # (duration_ns, end_ns, phase), durations falling from the left:
        # an occurrence shorter than a later one can never again be the
        # longest of a window that ends after both
        self._longest: deque[tuple[int, int, str]] = deque(maxlen=64)
        self._published = self._closed()
        self._publish_lock = threading.Lock()
        try:
            from jax.profiler import TraceAnnotation
        except Exception:    # the obs layer itself needs no jax
            TraceAnnotation = None
        self._annotate = TraceAnnotation

    @property
    def device_empty_ns(self) -> int:
        return sum(self.device_empty_by_phase.values())

    # -- the loop thread ------------------------------------------------------

    def enter(self, phase: str) -> None:
        if phase == self._cur:
            return
        # ONE read of the CPU clock a transition: it is a real system
        # call (5.8 us on the sandboxed hosts the chips hang off, where
        # monotonic_ns is 77 ns, PERF.md section 6), so it closes the
        # phase before and opens this one; the two clocks are read
        # back to back, so a phase that ran all the way through can
        # read a fraction of a microsecond more CPU than wall
        cpu = time.thread_time_ns()
        now = time.monotonic_ns()
        if self._cur is not None:
            self._close(now, cpu)
        if phase in self._dispatch:
            self._empty_since = None
        self._wait_busy = phase in self._waits and self._has_work()
        self._cur = phase
        self._t0 = now
        self._c0 = cpu
        self._g0 = GC.total_ns
        self.counts[phase] += 1
        if self._annotate is not None:
            self._ann = self._annotate(self._annotation[phase])
            self._ann.__enter__()

    def leave(self) -> None:
        """End of one driven step: stop the clock unless the driver
        holds the thread (``hold_open``)."""
        if self._cur is not None and not self.hold_open:
            cpu = time.thread_time_ns()
            self._close(time.monotonic_ns(), cpu)
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

    def _context(self) -> dict[str, Any]:
        if self.context is None:
            return {}
        try:
            return dict(self.context())
        except Exception:    # telemetry never takes the loop down
            return {}

    def _has_work(self) -> bool:
        ctx = self._context()
        return bool(ctx.get("queued") or ctx.get("active"))

    def _empty_in_open(self, now: int) -> int:
        """Device-empty nanoseconds of the open phase up to ``now``."""
        since = self._empty_since
        return 0 if since is None else now - max(self._t0, since)

    def _close(self, now: int, cpu_now: int) -> None:
        cur = self._cur
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        d = now - self._t0
        # as read, not cut to the wall: where the thread CPU clock ticks
        # coarsely (10 ms on a sandboxed host) a tick lands whole in
        # whichever phase is open, which is fair over many occurrences
        # and more than the wall of a short one
        cpu = cpu_now - self._c0
        paused = GC.total_ns - self._g0
        self.ns[cur] += d
        self.cpu_ns[cur] += cpu
        self.gc_ns[cur] += paused
        self.device_empty_by_phase[cur] += self._empty_in_open(now)
        if cur in self._waits and not self._wait_busy:
            return
        longest = self._longest
        while longest and longest[-1][0] <= d:
            longest.pop()
        longest.append((d, now, cur))
        if d >= STALL_NS:
            self._stall(cur, d, now, cpu, paused)

    def _stall(self, phase: str, d: int, end_ns: int, cpu: int,
               paused: int) -> None:
        from kubeflow_tpu.obs import metrics as obs_metrics

        ms = round(d / 1e6, 3)
        # long, low cpu_ms, no gc_ms: the thread waited for the
        # interpreter or the OS; gc_ms near the length: the collector
        ctx = dict(self._context(), cpu_ms=round(cpu / 1e6, 3),
                   gc_ms=round(paused / 1e6, 3))
        log.warning("%s stall: phase %s lasted %.1f ms (%s)",
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
        """Loop thread only: the open phase's CPU is this thread's."""
        cpu_now = time.thread_time_ns()
        now = time.monotonic_ns()
        cur = self._cur
        ns, cpu, empty = ([d[p] for p in self.phases] for d in (
            self.ns, self.cpu_ns, self.device_empty_by_phase))
        paused = sum(self.gc_ns.values())
        if cur is not None:
            i = self.phases.index(cur)
            ns[i] += now - self._t0
            # (a mark read from another thread, as tests do, reads that
            # thread's CPU clock: never below zero)
            cpu[i] += max(0, cpu_now - self._c0)
            empty[i] += self._empty_in_open(now)
            paused += GC.total_ns - self._g0
        return PhaseMark(
            now, tuple(ns), tuple(self.counts[p] for p in self.phases),
            sum(empty), self.steps, self.tokens,
            (self.kv_blocks_fetched, self.kv_blocks_spanned),
            tuple(cpu), tuple(empty), paused)

    def longest_since(self, start_s: float,
                      now_ns: int) -> tuple[int, str] | None:
        """(ns, phase) of the longest single non-waiting occurrence that
        overlapped [start_s (time.monotonic), now_ns]; the open one
        counts up to ``now_ns``. Loop thread only."""
        start_ns = int(start_s * 1e9)
        best = None
        for d, end_ns, phase in self._longest:    # longest first
            if end_ns > start_ns:
                best = (d, phase)
                break
        cur = self._cur
        if cur is not None and (cur not in self._waits or self._wait_busy):
            d = now_ns - self._t0
            if best is None or d > best[0]:
                best = (d, cur)
        return best

    def usage(self, first: PhaseMark,
              submit_s: float | None) -> dict[str, Any]:
        """The ``engine`` object of a request's ``usage``: what the
        engine thread did from ``first`` (its first token) to now (its
        finish): per phase wall ``[ms, count]`` and CPU ms, the
        device-empty overlay and its split by phase, the collector's
        pauses, the decode dispatches' ``kv_blocks`` [fetched, spanned]
        in that window, and the longest occurrence since ``submit_s``."""
        end = self.mark()

        def ms(a: int, b: int) -> float:
            return round((b - a) / 1e6, 3)

        seen = [e > s or ce > cs for s, e, cs, ce in zip(
            first.ns, end.ns, first.counts, end.counts)]
        out: dict[str, Any] = {
            "phases": {
                p: [ms(s, e), ce - cs]
                for p, on, s, e, cs, ce in zip(
                    self.phases, seen, first.ns, end.ns, first.counts,
                    end.counts) if on},
            # never more than the phase's wall, whatever the CPU clock's
            # tick (what a coarse tick overshoots is cut here, per window)
            "cpu_ms": {p: ms(0, min(ce - cs, e - s))
                       for p, on, cs, ce, s, e in zip(
                           self.phases, seen, first.cpu_ns, end.cpu_ns,
                           first.ns, end.ns) if on},
            "device_empty_ms": ms(first.device_empty_ns,
                                  end.device_empty_ns),
            "device_empty_by_phase_ms": {
                p: ms(s, e) for p, s, e in zip(
                    self.phases, first.device_empty_by_phase,
                    end.device_empty_by_phase) if e > s},
            "gc_ms": ms(first.gc_ns, end.gc_ns)}
        if end.kv_blocks[1] > first.kv_blocks[1]:
            out["kv_blocks"] = [e - s for s, e in zip(first.kv_blocks,
                                                      end.kv_blocks)]
        longest = (self.longest_since(submit_s, end.at_ns)
                   if submit_s is not None else None)
        if longest is not None:
            out["phase_max_ms"] = round(longest[0] / 1e6, 3)
            out["phase_max"] = longest[1]
        return out

    def _closed(self) -> dict[str, Any]:
        return {"ns": dict(self.ns), "cpu": dict(self.cpu_ns),
                "empty": dict(self.device_empty_by_phase),
                "kv_fetched": self.kv_blocks_fetched,
                "kv_spanned": self.kv_blocks_spanned}

    def publish(self) -> None:
        """Scrape-hook body: add what closed since the last scrape to
        the cumulative series."""
        from kubeflow_tpu.obs import metrics as obs_metrics

        with self._publish_lock:    # two scrapes must not add one delta twice
            last, now = self._published, self._closed()
            self._published = now
        for key, series in (
                ("ns", obs_metrics.ENGINE_PHASE_SECONDS),
                ("cpu", obs_metrics.ENGINE_PHASE_CPU_SECONDS),
                ("empty", obs_metrics.ENGINE_DEVICE_EMPTY_SECONDS)):
            for p in self.phases:
                series.inc((now[key][p] - last[key][p]) / 1e9,
                           engine=self.engine, phase=p)
        obs_metrics.ENGINE_KV_BLOCKS_FETCHED.inc(
            now["kv_fetched"] - last["kv_fetched"], engine=self.engine)
        obs_metrics.ENGINE_KV_BLOCKS_SPANNED.inc(
            now["kv_spanned"] - last["kv_spanned"], engine=self.engine)


#: the process tracer every layer shares (tests may swap the sink).
TRACER = Tracer()

"""The child's common part: it must find the chips, it counts compiles, it
traces a window and reduces the trace, and it reads the devices' memory."""

from __future__ import annotations

import gc
import glob
import logging
import os
import shutil
import tempfile
import time


class Refused(Exception):
    pass


class CompileMeter:
    """Backend compiles and persistent-cache hits, from jax.monitoring
    (chip_smoke.py's idiom). A hit still counts its retrieval as a compile
    event, so `compiles - hits` is what was really compiled."""

    def __init__(self):
        import jax.monitoring

        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def _secs(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.hits,
                "compile_s": self.compile_s,
                "last_compiled": self.names[-8:]}


class ErrorWatch(logging.Handler):
    """Errors the platform logs instead of raising (a controller retries a
    failed reconcile forever)."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.errors: list[str] = []
        logging.getLogger().addHandler(self)

    def emit(self, record) -> None:
        self.errors.append(record.getMessage()[-2000:])


class Context:
    def __init__(self, cell, args, link):
        import jax

        self.cell, self.args, self.link, self.jax = cell, args, link, jax
        self.t0 = time.monotonic()
        self.meter, self.watch = CompileMeter(), ErrorWatch()
        devices = jax.devices()
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)}
        print(f"[bench] child device: {self.device}", flush=True)
        if not args.no_chip:
            if self.device["platform"] != "tpu":
                raise Refused("no accelerator: jax.devices()[0].platform is "
                              f"{self.device['platform']!r}; a number from it "
                              "would not be a device number")
            cell.peaks(self.device["kind"])   # unknown kind: refuse now
        if self.device["count"] < cell.chips:
            raise Refused(f"cell {cell.name} needs {cell.chips} chip(s), "
                          f"JAX reports {self.device['count']}")
        self.devices = devices[:cell.chips]
        self.device["count"] = cell.chips if args.no_chip else len(devices)
        # scratch for the platform's root, the token file and the trace:
        # under TMPDIR, which the driver gives each side of its own
        self.tmp = tempfile.mkdtemp(prefix="bench-")
        self._trace_dir = None
        self._trace_t0 = None
        if link is not None:
            link.say("hello", device=self.device)

    # -- memory ---------------------------------------------------------------

    def memory_peak_bytes(self) -> int:
        """Peak on the fullest chip, as the allocator reports it."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))

    def free_device(self) -> int:
        """Delete every live device array: the program's state has to be
        gone before the reference takes the chip. Returns bytes freed."""
        gc.collect()
        freed = 0
        for arr in self.jax.live_arrays():
            try:
                freed += arr.nbytes
                arr.delete()
            except Exception:   # already deleted / donated
                pass
        gc.collect()
        return freed

    # -- trace ----------------------------------------------------------------

    def trace_start(self, seconds: float) -> None:
        """Trace from now for `seconds` (the mix's `trace_seconds`, or the
        whole window): the profiler stops itself, so that a long window
        does not make a trace too large to read back within the run."""
        import threading

        self._trace_dir = os.path.join(self.tmp, "trace")
        self._trace_window_s = None
        self._trace_lock = threading.Lock()
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # device planes are what is read;
        opts.host_tracer_level = 1      # a Python tracer slows the host
        self.jax.profiler.start_trace(self._trace_dir,
                                      profiler_options=opts)
        self._trace_t0 = time.monotonic()
        self._trace_timer = threading.Timer(seconds, self.trace_stop)
        self._trace_timer.daemon = True
        self._trace_timer.start()

    def trace_stop(self) -> None:
        """Stop the profiler (once); the reduction waits until the system
        has drained (trace_reduce)."""
        with self._trace_lock:
            if self._trace_window_s is None:
                self._trace_window_s = time.monotonic() - self._trace_t0
                self.jax.profiler.stop_trace()
        self._trace_timer.cancel()

    def trace_reduce(self) -> dict | None:
        """Reduce the trace taken (lib/tracered.py) and delete it."""
        from lib import tracered

        if self._trace_dir is None:
            return None
        t = time.monotonic()
        (path,) = glob.glob(os.path.join(
            self._trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        size = os.path.getsize(path)
        planes = tracered.read_xplane(path)
        if self.args.no_chip and not tracered.device_planes(planes):
            # the CPU rehearsal has no device plane; the reduction itself
            # is tested on the recorded trace in benchmark/tests
            planes = [{"name": "/device:TPU:0", "lines": [
                {"name": tracered.OPS_LINE, "events": [["none", 0, 1]]}]}]
        out = tracered.reduce(planes, n_devices=self.cell.chips)
        # the window by the host's clock, from start_trace's return to
        # stop_trace's call: device time before the first and after the
        # last operation is idle time too
        out["device_span_s"] = out["window_s"]
        out["window_s"] = max(self._trace_window_s, out["window_s"])
        out["trace_bytes"] = size
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        print(f"[bench] trace: {size / 1e6:.1f} MB reduced in "
              f"{time.monotonic() - t:.1f}s; busy {out['busy_s']:.3f}s of "
              f"{out['window_s']:.3f}s", flush=True)
        return out

    def close(self) -> None:
        shutil.rmtree(self.tmp, ignore_errors=True)

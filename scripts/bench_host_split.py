#!/usr/bin/env python3
"""`benchmark/run.py` of the checkout in the working directory, and beside its
result line the phase clock's host-time split of the run, whatever --trace says
(the harness reads per-layer metrics under --trace 1 only, and the profiler
slows the host it measures):

    cd <checkout> && SPLIT_DUMP=out.json python3 <repo>/scripts/bench_host_split.py \\
        --workload serve_chat_open --seed N --seconds 30 --trace 0

Written to $SPLIT_DUMP (JSON):

  - `scrapes`, served cells: `/metrics` read at the window's opening, after
    $SPLIT_TRACE_SECONDS (default 10: the mix's traced stretch) and at the close,
    the series of `SERIES`: the deltas are the engine thread's wall, CPU and
    device-empty seconds per phase over that stretch, as the clocks read them;
  - `readers`: every reader of `READERS` that finds something in the run;
  - `usages`, served cells: the requests' `usage.engine` summed (phases, CPU,
    device-empty split), `gc_ms`, the largest stream lags, and how many requests
    break one of the three identities (split sums to the whole, CPU <= wall,
    phases sum to `decode_ms`);
  - `steps`, trained cells that keep every key of a record: median and maximum
    of the Trainer's `host_*` scalars, and how far they are from `step_time_s`.

`--report <dump>` prints the scrapes' deltas of such a file.

Works on a checkout without the counters too (a parent commit): what is not
there is left out. Nothing under `benchmark/` is edited: the driver module's
`offer` and the cell's driver are wrapped from outside."""

import json
import os
import statistics
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.join(os.getcwd(), "benchmark"))
from drivers import http_open_loop as hol  # noqa: E402
from lib import spec  # noqa: E402

SERIES = ("serving_engine_phase_seconds_total",
          "serving_engine_phase_cpu_seconds_total",
          "serving_engine_device_empty_seconds_total",
          "process_gc_pause_seconds_total", "process_gc_pause_max_seconds",
          "serving_engine_stalls_total")
READERS = ("engine_host_offcpu_share", "engine_device_empty_replay_share",
           "engine_device_empty_plan_share", "engine_device_empty_bank_share",
           "stream_write_lag_max_ms", "trainer_device_empty_share",
           "trainer_host_phase_max_ms", "engine_device_empty_share",
           "engine_phase_max_ms", "engine_host_ms_per_decode_chunk_p50",
           "stream_first_write_lag_p50_ms", "step_p50_ms")
scrapes = []


def scrape(url, tag):
    try:
        with urllib.request.urlopen(url.rstrip("/") + "/metrics",
                                    timeout=10) as r:
            text = r.read().decode()
    except OSError as e:
        scrapes.append({"tag": tag, "error": repr(e)})
        return
    values = {}
    for line in text.splitlines():
        if line.startswith(SERIES):
            series, value = line.rsplit(" ", 1)
            values[series] = float(value)
    scrapes.append({"tag": tag, "at": time.monotonic(), "values": values})


def offer(url, model, reqs, t_open, seconds, _offer=hol.offer):
    if seconds <= 0:    # the warm-up wave
        return _offer(url, model, reqs, t_open, seconds)
    scrape(url, "open")
    mid = float(os.environ.get("SPLIT_TRACE_SECONDS", "10"))
    timer = threading.Timer(max(0.0, t_open + mid - time.monotonic()),
                            scrape, (url, "traced_end"))
    timer.daemon = True
    timer.start()
    out = _offer(url, model, reqs, t_open, seconds)
    scrape(url, "close")
    return out


def summed_usages(usages):
    tot = {"n": len(usages), "decode_ms": sum(u["decode_ms"] for u in usages),
           "device_empty_ms": sum(u["engine"]["device_empty_ms"]
                                  for u in usages), "phases": {}}
    for u in usages:
        for p, (ms, n) in u["engine"]["phases"].items():
            acc = tot["phases"].setdefault(p, [0.0, 0])
            acc[0] += ms
            acc[1] += n
    for key in ("cpu_ms", "device_empty_by_phase_ms"):
        acc = tot[key] = {}
        for u in usages:
            for p, v in (u["engine"].get(key) or {}).items():
                acc[p] = acc.get(p, 0.0) + v
    gcs = [u["engine"]["gc_ms"] for u in usages if "gc_ms" in u["engine"]]
    if gcs:
        tot["gc_ms"] = {"median": statistics.median(gcs), "max": max(gcs),
                        "sum": sum(gcs)}
    lags = sorted((u["stream_write_lag_max_ms"] for u in usages
                   if u.get("stream_write_lag_max_ms") is not None),
                  reverse=True)
    tot["stream_write_lag_top"] = lags[:8]
    tot["stream_write_lag_median"] = statistics.median(lags) if lags else None
    bad = 0
    for u in usages:
        e = u["engine"]
        if "device_empty_by_phase_ms" not in e:
            continue
        bad += (abs(sum(e["device_empty_by_phase_ms"].values())
                    - e["device_empty_ms"]) > 0.011)
        bad += any(e["cpu_ms"][p] > e["phases"][p][0] + 0.5
                   for p in e["cpu_ms"])
        bad += (abs(sum(ms for ms, _ in e["phases"].values())
                    - u["decode_ms"]) > 0.01 * u["decode_ms"] + 0.01)
    tot["identity_violations"] = bad
    return tot


def host_steps(steps):
    keys = [k for k in steps[0] if k.startswith("host_")
            or k in ("device_empty_ms", "gc_pause_ms", "step_time_s")]
    out = {k: {"median": statistics.median(s[k] for s in steps),
               "max": max(s[k] for s in steps)} for k in keys}
    phases = [k for k in keys if k.startswith("host_") and k.endswith("_ms")
              and k not in ("host_cpu_ms", "host_phase_max_ms")]
    out["n"] = len(steps)
    out["sum_gap_max"] = max(
        abs(sum(s[k] for k in phases) - s["step_time_s"] * 1e3)
        / (s["step_time_s"] * 1e3) for s in steps)
    return out


def dump(run):
    out = {"scrapes": scrapes, "readers": {}}
    for name in READERS:
        try:
            value = spec.metric_reader(name)(run)
        except (KeyError, FileNotFoundError):   # another kind of cell; a
            continue                            # checkout without the reader
        if value is not None:
            out["readers"][name] = value
    usages = [r["usage"] for r in run.get("requests", [])
              if r.get("usage") and r["usage"].get("engine")]
    if usages:
        out["usages"] = summed_usages(usages)
    steps = run.get("steps") or []
    if steps and "host_dispatch_ms" in steps[0]:
        out["steps"] = host_steps(steps)
    if os.environ.get("SPLIT_DUMP"):
        with open(os.environ["SPLIT_DUMP"], "w") as f:
            json.dump(out, f)


def driver(self, _driver=spec.Cell.driver):
    mod = _driver(self)

    class Wrapped:
        def __getattr__(self, name):
            return getattr(mod, name)

        def parent(self, *args, **kw):
            run = mod.parent(*args, **kw)
            if run is not None:
                dump(run)
            return run

    return Wrapped()


def report(path):
    """The scrapes' deltas of one dump: per stretch the engine thread's wall
    and CPU per phase, the off-CPU share of the host-only phases, and the
    device-empty seconds by phase."""
    import re

    with open(path) as f:
        got = {s["tag"]: s for s in json.load(f)["scrapes"] if "values" in s}

    def by_phase(delta, series):
        found = (re.match(series + r'\{engine="engine",phase="(\w+)"\}', k)
                 for k in delta)
        return {m.group(1): delta[m.group(0)] for m in found if m}

    host = ("sched", "prefill_pack", "decode_plan", "replay")
    for a, b in (("open", "traced_end"), ("traced_end", "close"),
                 ("open", "close")):
        if a not in got or b not in got:
            continue
        delta = {k: v - got[a]["values"].get(k, 0.0)
                 for k, v in got[b]["values"].items()}
        wall = by_phase(delta, SERIES[0])
        cpu = by_phase(delta, SERIES[1])
        empty = by_phase(delta, SERIES[2])
        print(f"{a} -> {b}: {got[b]['at'] - got[a]['at']:.2f} s")
        print("  wall s", {p: round(v, 3) for p, v in wall.items()})
        if cpu:
            hw, hc = (sum(d[p] for p in host) for d in (wall, cpu))
            print("  cpu s ", {p: round(v, 3) for p, v in cpu.items()})
            print(f"  host-only phases: wall {hw:.3f} s, CPU {hc:.3f} s, "
                  f"off the CPU {100 * (hw - hc) / hw:.1f} %")
        print(f"  device empty {sum(empty.values()):.3f} s:",
              {p: round(v, 3) for p, v in empty.items() if v > 0.0005})
        print("  gc s", {k: round(v, 4) for k, v in delta.items()
                         if k.startswith("process_gc")})


if __name__ == "__main__":
    if sys.argv[1:2] == ["--report"]:
        report(sys.argv[2])
        sys.exit(0)
    hol.offer = offer
    spec.Cell.driver = driver
    import run as bench_run

    sys.exit(bench_run.main(sys.argv[1:]))

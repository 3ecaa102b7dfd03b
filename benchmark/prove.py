#!/usr/bin/env python3
"""Settle a served cell's `correct` on the chip: many seeds after ONE set-up.

    python3 benchmark/prove.py --workload serve_chat_open --seeds 1,2,3 \
        --seconds 12 [--control int4] [--sweep 6,8,10,12,14]

Set-up is long, so one child serves every window: first the optional rate
sweep (one window per rate, to find the knee), then one short window per
seed at the cell's own load. The weights are those of the first seed; each
seed changes the traffic (order, token ids, who shares a wave). When all
windows are done the child frees the program and runs the reference once
over every sample, and with --control the lower precision over the same
prompts and tokens. Prints one JSON line per window and a summary; the
benchmark's own runs never come here.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from drivers import http_open_loop as drv   # noqa: E402
from lib import harness, proto, stats, traffic   # noqa: E402


def one_window(child, cell, mix, url, model, seed, seconds):
    reqs = traffic.make_requests(mix, seed, seconds,
                                 cell.config["vocab_size"])
    before = child.ask("counters", 60)
    t_open = time.monotonic()
    recs, threads = drv.offer(url, model, reqs, t_open, seconds)
    inflight_at_close = sum(1 for r in recs if r["done"] is None)
    drv.drain(threads, t_open + seconds)
    after = child.ask("counters", 60)
    e2e = drv.end_to_end(recs, t_open, seconds)
    ttft = [((r["token_at"][0] - r["due"]) * 1e3, r["due"] - t_open)
            for r in recs if r["token_at"]]
    third = seconds / 3
    first = [v for v, d in ttft if d < third]
    last = [v for v, d in ttft if d >= 2 * third]
    out = dict(e2e, seed=seed, requests=len(recs),
               failed=sum(1 for r in recs if not drv.ok(r)),
               ttft_p50_ms=stats.percentile([v for v, _ in ttft], 50),
               ttft_p50_first_third=stats.percentile(first, 50),
               ttft_p50_last_third=stats.percentile(last, 50),
               inflight_at_close=inflight_at_close,
               drain_s=max([r["done"] or 0 for r in recs] + [0])
               - (t_open + seconds),
               queue_wait_p95_ms=stats.percentile(
                   [r["usage"]["queue_wait_ms"] for r in recs
                    if r.get("usage") and r["usage"].get("queue_wait_ms")
                    is not None], 95),
               compiles=after["compiles"] - before["compiles"],
               compiled=(after["last_compiled"]
                         if after["compiles"] != before["compiles"] else []))
    return out, recs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--control")
    ap.add_argument("--sweep", help="rates, requests/s, one window each")
    ap.add_argument("--rate", help="override the mix's rate; several, "
                    "comma-separated, run every seed at each")
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument("--auto-rate", action="store_true")
    ap.add_argument("--no-chip", action="store_true")
    ap.add_argument("--toy")
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    args = argparse.Namespace(workload=a.workload, seed=seeds[0],
                              seconds=a.seconds, trace=0, no_chip=a.no_chip,
                              toy=a.toy)
    cell = harness.load_cell(args)
    child = harness.start_child(args)
    try:
        child.expect("hello", 300)
        ready = child.expect("ready", 1150)
        url, model = ready["url"], ready["model"]
        print("setup", json.dumps(ready["setup"]), flush=True)
        drv.warm_over_http(url, model, cell.traffic,
                           cell.config["vocab_size"])
        mix = copy.deepcopy(cell.traffic)
        knee = None
        for i, rate in enumerate(
                [float(r) for r in (a.sweep or "").split(",") if r]):
            mix["arrivals"]["rate_per_s"] = rate
            out, _ = one_window(child, cell, mix, url, model, 900 + i,
                                a.seconds)
            grows = (out["inflight_at_close"] > 16 + 2 * rate
                     or (out["ttft_p50_last_third"] or 0)
                     > 3 * (out["ttft_p50_first_third"] or 1e9))
            if not grows and not out["failed"]:
                knee = rate
            print("sweep", json.dumps(dict(out, rate=rate, grows=grows)),
                  flush=True)
        mix = copy.deepcopy(cell.traffic)
        if a.auto_rate and knee:
            mix["arrivals"]["rate_per_s"] = round(0.8 * knee, 2)
        rates = ([float(r) for r in a.rate.split(",")] if a.rate
                 else [mix["arrivals"]["rate_per_s"]])
        samples, owner = [], []
        for rate in rates:
            mix["arrivals"]["rate_per_s"] = rate
            print("rate", rate, "knee", knee, flush=True)
            for seed in seeds:
                out, recs = one_window(child, cell, mix, url, model, seed,
                                       a.seconds)
                for smp in drv.verify_sample(recs, seed,
                                             int(mix["verify_requests"])):
                    samples.append(smp)
                    owner.append(seed)
                print("seed", json.dumps(dict(out, rate=rate)), flush=True)
        if a.no_verify:
            return 0
        ver = child.ask("verify", 1800, samples=samples, control=a.control)
        info = ver["info"]
        by_seed = {}
        for seed, g in zip(owner, info["per_request"]):
            by_seed[seed] = max(by_seed.get(seed, 0.0), g)
        print("program widest gap by seed", json.dumps(by_seed))
        if a.control:
            cby = {}
            for seed, g in zip(owner, info["control"]["per_request"]):
                cby[seed] = max(cby.get(seed, 0.0), g)
            print(f"control {a.control} widest gap by seed", json.dumps(cby))
            print("control", json.dumps({k: v for k, v in
                                         info["control"].items()
                                         if k != "per_request"}))
        print("reference", json.dumps({k: v for k, v in info.items()
                                       if k not in ("per_request",
                                                    "control")}))
        print("memory_peak_bytes", ver["memory_peak_bytes"])
    except proto.ChildDied as e:
        print("FAILED", e)
        return 1
    finally:
        child.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

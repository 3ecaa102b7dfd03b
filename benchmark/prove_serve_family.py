#!/usr/bin/env python3
"""Settle a served cell's `correct` and its rate on the chip, for a cell
whose mix names the `http_open_loop_family` driver: `prove.py` for any
served family. Many windows after ONE set-up.

    python3 benchmark/prove_serve_family.py --workload serve_laguna_xs2_mixed_open \
        --sweep 2,3,4,5,6 --seeds 3000000301,3000000302,... --seconds 12 \
        [--controls int8 --faults no_gate,window_511,...] \
        [--program-faults ring_window_only]

One child serves every window: first the rate sweep (one window per rate:
a rate is SUSTAINED while `ttft_p95_ms` stays under 1000 and the tokens/s
completed still rise with the rate), then one window per seed at the cell's
own rate (or `--rate`). The weights are those of the first seed; each seed
changes the traffic's token ids. When all windows are done the child frees
the program and runs the reference once over every sample; each control
(the reference in a lower precision) and each planted fault (the reference
with the fault) then stands in the program's place over the same samples.
`--program-faults` are planted under the program itself, each in a child of
its own (a whole set-up each): one window, then the same comparison. Prints
one JSON line per window and a summary; the benchmark's own runs never come
here.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from drivers import http_open_loop_family as drv   # noqa: E402
from lib import harness, proto   # noqa: E402
from prove import one_window   # noqa: E402


def by_seed(owner: list[int], gaps: list[float]) -> dict:
    out: dict = {}
    for seed, g in zip(owner, gaps):
        out[seed] = max(out.get(seed, 0.0), g)
    return out


def off_best_by_seed(owner: list[int], got: dict) -> dict:
    """Per seed, the share (%) of its served tokens judged that are not
    the reference's best."""
    off, n = {}, {}
    for seed, o, t in zip(owner, got["per_request_off_best"],
                          got["per_request_tokens"]):
        off[seed] = off.get(seed, 0) + o
        n[seed] = n.get(seed, 0) + t
    return {seed: round(100.0 * off[seed] / n[seed], 2) for seed in off}


def serve(args, a, seeds, sweep, controls, faults) -> int:
    cell = harness.load_cell(args)
    vocab = cell.config["vocab_size"]
    child = harness.start_child(args)
    try:
        child.expect("hello", 300)
        ready = child.expect("ready", 1150)
        url, model = ready["url"], ready["model"]
        print("setup", json.dumps(ready["setup"]), flush=True)
        drv.warm_over_http(url, model, cell.traffic, vocab)
        mix = copy.deepcopy(cell.traffic)
        best, knee = 0.0, None
        for i, rate in enumerate(sweep):
            mix["arrivals"]["rate_per_s"] = rate
            out, _ = one_window(child, cell, mix, url, model, 900 + i,
                                a.seconds)
            tps = out["serve_out_tokens_per_s"]
            sustains = (not out["failed"]
                        and (out["ttft_p95_ms"] or 1e9) < 1000.0
                        and tps > best)
            if sustains:
                knee, best = rate, tps
            print("sweep", json.dumps(dict(out, rate=rate,
                                           sustains=sustains)), flush=True)
        if sweep:
            print("knee", knee, "four fifths", knee and round(0.8 * knee, 2),
                  flush=True)
        mix = copy.deepcopy(cell.traffic)
        if a.rate:
            mix["arrivals"]["rate_per_s"] = a.rate
        elif a.auto_rate and knee:
            mix["arrivals"]["rate_per_s"] = round(0.8 * knee, 2)
        samples, owner = [], []
        for seed in seeds:
            out, recs = one_window(child, cell, mix, url, model, seed,
                                   a.seconds)
            for smp in drv.verify_sample(recs, seed,
                                         int(mix["verify_requests"])):
                samples.append(smp)
                owner.append(seed)
            print("seed", json.dumps(dict(
                out, rate=mix["arrivals"]["rate_per_s"],
                longest=max((len(r["prompt"]) + len(r["token_ids"])
                             for r in recs), default=0))), flush=True)
        if a.no_verify or not samples:
            return 0
        ver = child.ask("verify", 3000, samples=samples, controls=controls,
                        faults=faults)
        info = ver["info"]
        print("program widest gap by seed",
              json.dumps(by_seed(owner, info["per_request"])))
        print("program off-best share by seed",
              json.dumps(off_best_by_seed(owner, info)))
        for key, names in (("lower", controls), ("fault", faults)):
            for n in names:
                got = info[f"{key}_{n}"]
                print(f"{key} {n} widest gap by seed",
                      json.dumps(by_seed(owner, got["per_request"])),
                      "off-best share by seed",
                      json.dumps(off_best_by_seed(owner, got)),
                      json.dumps({k: v for k, v in got.items()
                                  if not k.startswith("per_request")}))
        print("numbers", json.dumps(ver["numbers"]))
        print("reference", json.dumps({
            k: v for k, v in info.items()
            if not k.startswith(("per_request", "lower_", "fault_"))}))
        print("memory_peak_bytes", ver["memory_peak_bytes"], flush=True)
    except proto.ChildDied as e:
        print("FAILED", e)
        return 1
    finally:
        child.close()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--sweep", default="",
                    help="rates, requests/s, one window each, rising")
    ap.add_argument("--rate", type=float,
                    help="the seeds' rate, in place of the mix's own")
    ap.add_argument("--auto-rate", action="store_true",
                    help="the seeds run at four fifths of the sweep's knee")
    ap.add_argument("--controls", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--program-faults", default="")
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument("--no-chip", action="store_true")
    ap.add_argument("--toy")
    a = ap.parse_args()
    split = lambda s: [x for x in s.split(",") if x]   # noqa: E731
    seeds = [int(s) for s in split(a.seeds)]
    sweep = [float(r) for r in split(a.sweep)]
    first = seeds[0] if seeds else 1
    args = argparse.Namespace(workload=a.workload, seed=first,
                              seconds=a.seconds, trace=0, no_chip=a.no_chip,
                              toy=a.toy)
    rc = 0
    if seeds or sweep:
        rc = serve(args, a, seeds, sweep, split(a.controls),
                   split(a.faults))
    for name in split(a.program_faults):
        print("program fault", name, flush=True)
        os.environ["BENCH_FAMILY_FAULT"] = name
        try:
            rc |= serve(args, a, seeds[:1] or [first], [], [], [])
        finally:
            del os.environ["BENCH_FAMILY_FAULT"]
    return rc


if __name__ == "__main__":
    sys.exit(main())

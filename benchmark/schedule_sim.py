#!/usr/bin/env python3
"""How far a run's speed moves `serve_out_tokens_per_s` under one fixed
schedule of a served mix: an engine simulation, for choosing a mix's
`order_seed` (PR 38, `long_reasoning_open.json`).

    python3 benchmark/schedule_sim.py --traffic long_reasoning_open \
        --order-seeds 0-399 [--seconds 30]

Every seed of a run meets the same schedule (lib/traffic.py); the tokens
counted are those delivered inside the window, so an answer still open at
the close counts as many tokens as the system gave it in the window, and
a run that lost a second anywhere before the close loses that second of
every such answer. Which answers are open at the close, and with how much
history, is a property of the schedule alone.

The engine, as `serving/llm.py` runs the openPangu cell (prefill_wave_max
1, decode_chunk 8): one prefill chunk of at most 1,024 rows, then a decode
chunk of 8 steps over the live slots. A step costs `STEP_S` + `SLOT_S` a
live slot + `EXPERT_S` for each (layer, held expert) a live slot's token
touches (the grouped matmul visits no empty group, and an expert's weights
are 94 MB: 0.115 ms at 819 GB/s); a prefill chunk `ROW_S` a row and
`KEY_S` a (row, key it sees) pair. The constants were fitted to the 36
finish times of two 30 s windows of the cell on the chip (my chip run,
PR 38): the windows sit at 0.98 and 1.04 x this speed. A run's noise:
each slot's touched experts change with a probability a step
(1 - `persist`), host stalls of 0.2-0.5 s at `stall` a second, and a
jitter on every chunk.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from lib import traffic   # noqa: E402

STEP_S, SLOT_S, EXPERT_S = 0.008, 0.0001, 0.000115
ROW_S, KEY_S = 55e-6, 2e-9
LAYERS, HELD, TOP_K, EXPERTS = 4, 8, 8, 256
CHUNK_ROWS, DECODE_CHUNK = 1024, 8


def schedule(mix: dict, order_seed: int, seconds: float):
    """(due, prompt length, answer length) of every request."""
    reqs = traffic.make_requests(dict(mix, order_seed=order_seed), 1,
                                 seconds, 100)
    return [(r.due_s, len(r.prompt), r.max_tokens) for r in reqs]


def run(sched, seconds: float, rng, speed: float = 1.0, persist=0.98,
        stall=0.0, jitter=0.01) -> tuple[float, list]:
    """tokens/s counted in [0, seconds] and each request's finish."""
    touch = 1 - (1 - 1 / EXPERTS) ** TOP_K      # a held expert, one token

    def draw():
        return rng.random((LAYERS, HELD)) < touch

    n = len(sched)
    masks = [draw() for _ in range(n)]
    t, i, count = 0.0, 0, 0
    pending, rows_left, live, done = [], {}, {}, [None] * n
    next_stall = rng.exponential(1 / stall) if stall else float("inf")

    def noisy(dt):
        return dt * speed * (1 + rng.normal() * jitter)

    while True:
        if t > next_stall:
            t += rng.uniform(0.2, 0.5)
            next_stall = t + rng.exponential(1 / stall)
        while i < n and sched[i][0] <= t:
            pending.append(i)
            rows_left[i] = sched[i][1]
            i += 1
        if not pending and not live:
            if i >= n:
                break
            t = sched[i][0]
            continue
        if pending:
            j = pending[0]
            rows = min(CHUNK_ROWS, rows_left[j])
            seen = sched[j][1] - rows_left[j] + rows / 2
            t += noisy(ROW_S * rows + KEY_S * rows * seen)
            rows_left[j] -= rows
            if not rows_left[j]:
                pending.pop(0)
                live[j] = 1                     # the first token
                count += t <= seconds
        if live:
            for _ in range(DECODE_CHUNK):
                union = np.zeros((LAYERS, HELD), bool)
                for j in live:
                    if rng.random() > persist:
                        masks[j] = draw()
                    union |= masks[j]
                t += noisy(STEP_S + SLOT_S * len(live)
                           + EXPERT_S * union.sum())
            for j in list(live):
                k = min(DECODE_CHUNK, sched[j][2] - live[j])
                live[j] += k
                count += k if t <= seconds else 0
                if live[j] >= sched[j][2]:
                    del live[j]
                    done[j] = t
    return count / seconds, done


def elasticity(sched, seconds: float, speed: float = 1.0,
               step: float = 0.05) -> float:
    """|d log(tokens/s) / d log(speed)| with no noise, over +-step."""
    def at(k):
        return run(sched, seconds, np.random.default_rng(7), speed=k,
                   persist=1.0, jitter=0.0)[0]
    lo, hi = at(speed * (1 - step)), at(speed * (1 + step))
    return abs(np.log(hi / lo) / np.log((1 + step) / (1 - step)))


def spread(values: list[float]) -> float:
    """The driver's: quartile distance over the median, the run farthest
    from the median left out where that narrows it; in percent."""
    def iqr(v):
        q = statistics.quantiles(v, n=4)
        return (q[2] - q[0]) / statistics.median(v)
    med = statistics.median(values)
    rest = list(values)
    rest.remove(max(values, key=lambda v: abs(v - med)))
    return 100 * min(iqr(values), iqr(rest))


def noisy_spread(sched, seconds: float, speed: float = 1.0, sets: int = 4,
                 **noise) -> float:
    vals = [run(sched, seconds, np.random.default_rng(7000 + r),
                speed=speed, **noise)[0] for r in range(6 * sets)]
    return float(np.mean([spread(vals[6 * k:6 * k + 6])
                          for k in range(sets)]))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--order-seeds", default="0-399")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--top", type=int, default=10)
    a = ap.parse_args()
    with open(os.path.join(HERE, "traffic", a.traffic + ".json")) as f:
        mix = json.load(f)
    lo, hi = (int(x) for x in a.order_seeds.split("-"))
    rows = []
    for s in range(lo, hi + 1):
        sched = schedule(mix, s, a.seconds)
        rows.append((max(elasticity(sched, a.seconds, k)
                         for k in (0.9, 1.0, 1.1)), s))
    rows.sort()
    for el, s in rows[:a.top]:
        sched = schedule(mix, s, a.seconds)
        by_speed = [noisy_spread(sched, a.seconds, k, stall=0.1,
                                 persist=0.95) for k in (0.95, 1.0, 1.1)]
        print(json.dumps({"order_seed": s, "elasticity_max": round(el, 3),
                          "spread_pct_at_0.95_1.0_1.1": [
                              round(x, 2) for x in by_speed]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

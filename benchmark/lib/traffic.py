"""The one general traffic generator. A traffic mix is a data file of
parameters (benchmark/traffic/<mix>.json); this reads it and makes the
requests of one run from the seed.

Every seed gets the same work at the same instants: lengths and
inter-arrival gaps are the stratified quantiles of their distributions
(quantile (i + 0.5) / N for i < N), shuffled ONCE by the mix's own
`order_seed` into one fixed schedule of (instant due, prompt length, output
length). The run's seed draws the token ids (and, in the drivers, the
weights). Two other ways were tried on the chip first (PERF.md Findings): a
free permutation per seed moved ttft_p95_ms by a factor of two between
seeds at four fifths of the knee, and a rotation of one fixed sequence still
moved serve_out_tokens_per_s by 3 % (which long answers straddle the close)
where one schedule repeats to 0.4 %. The seed was changing the work.

Keys of a mix (all lengths in tokens):
  driver         which benchmark/drivers/<kind>.py drives it
  arrivals       {"process": "poisson", "rate_per_s": r}
                 {"process": "bursty", "rate_per_s": r, "burst_every_s": e,
                  "burst_size": k}   (k at once every e seconds, the rest Poisson)
                 {"process": "replay", "at_s": [...]}   (fixed instants)
  prompt_tokens  a length law (below)
  output_tokens  a length law
  sharing        null, or {"templates": n, "template_tokens": law,
                 "zipf": s}: a prompt is one of n fixed templates (drawn
                 Zipf(s)) followed by its own `prompt_tokens` tokens; with
                 "turns": law and "think_s": t each arrival is a session
                 whose later turns (every t seconds) extend the turn before
                 by a synthetic answer and a new user turn.
A length law is one of
  {"fixed": n} | {"uniform": [lo, hi]} | {"list": [...]} |
  {"bins": [[lo, hi, weight], ...]}   (uniform inside a bin) |
  {"bounded_pareto": {"lo": a, "hi": b, "shape": s}}
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due_s: float            # from the window's opening
    prompt: list[int]
    max_tokens: int


def quantile(law: dict, u: float) -> int:
    """Inverse CDF of a length law at u in (0, 1)."""
    if "fixed" in law:
        return int(law["fixed"])
    if "uniform" in law:
        lo, hi = law["uniform"]
        return int(lo + math.floor(u * (hi - lo + 1)))
    if "list" in law:
        vals = law["list"]
        return int(vals[min(len(vals) - 1, int(u * len(vals)))])
    if "bins" in law:
        total = sum(w for _, _, w in law["bins"])
        acc = 0.0
        for lo, hi, w in law["bins"]:
            if u * total < acc + w or (lo, hi, w) == tuple(law["bins"][-1]):
                inside = min(max((u * total - acc) / w, 0.0), 1.0 - 1e-12)
                return int(lo + math.floor(inside * (hi - lo + 1)))
            acc += w
    if "bounded_pareto" in law:
        p = law["bounded_pareto"]
        lo, hi, a = float(p["lo"]), float(p["hi"]), float(p["shape"])
        x = lo / (1.0 - u * (1.0 - (lo / hi) ** a)) ** (1.0 / a)
        return int(min(hi, max(lo, round(x))))
    raise ValueError(f"unknown length law {law}")


def stratified(law: dict, n: int, rng: np.random.Generator) -> list[int]:
    vals = [quantile(law, (i + 0.5) / n) for i in range(n)]
    return [vals[i] for i in rng.permutation(n)]


def arrival_times(arr: dict, seconds: float, rng) -> list[float]:
    proc = arr["process"]
    if proc == "replay":
        return sorted(t for t in arr["at_s"] if t < seconds)
    rate = float(arr["rate_per_s"])
    bursts: list[float] = []
    if proc == "bursty":
        every, size = float(arr["burst_every_s"]), int(arr["burst_size"])
        starts = np.arange(every / 2, seconds, every)
        bursts = [float(t) for t in starts for _ in range(size)]
        rate = max(rate - size / every, 1e-9)
    elif proc != "poisson":
        raise ValueError(f"unknown arrival process {proc!r}")
    n = max(1, round(rate * seconds))
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                     for i in range(n)])
    times = np.cumsum(gaps[rng.permutation(n)])
    return sorted([float(t) for t in times if t < seconds] + bursts)


def make_requests(mix: dict, seed: int, seconds: float,
                  vocab_size: int) -> list[Request]:
    order = np.random.default_rng([int(mix.get("order_seed", 0)), 0xA221])
    ids = np.random.default_rng([int(seed), 0x70C5])

    def tokens(n: int) -> list[int]:
        return ids.integers(1, vocab_size, size=n).tolist()

    times = arrival_times(mix["arrivals"], seconds, order)
    n = len(times)
    plens = stratified(mix["prompt_tokens"], n, order)
    olens = stratified(mix["output_tokens"], n, order)
    share = mix.get("sharing")
    if not share:
        return [Request(i, t, tokens(p), o)
                for i, (t, p, o) in enumerate(zip(times, plens, olens))]
    # shared prefixes: fixed templates (from the mix's own seed, so every
    # run and both sides of a pair see the same), drawn Zipf
    k = int(share["templates"])
    trng = np.random.default_rng([int(share.get("seed", 0)), 0x7E3A])
    tlens = stratified(share["template_tokens"], k, trng)
    templates = [trng.integers(1, vocab_size, size=m).tolist()
                 for m in tlens]
    w = np.array([1.0 / (r + 1) ** float(share.get("zipf", 1.0))
                  for r in range(k)])
    picks = order.choice(k, size=n, p=w / w.sum())   # fixed, like the rest
    turns = (stratified(share["turns"], n, order) if "turns" in share
             else [1] * n)
    out: list[Request] = []
    for i, (t, p, o) in enumerate(zip(times, plens, olens)):
        prompt = templates[picks[i]] + tokens(p)
        for turn in range(turns[i]):
            due = t + turn * float(share.get("think_s", 0.0))
            if due < seconds:
                out.append(Request(0, due, list(prompt), o))
            prompt = prompt + tokens(o) + tokens(p)   # answer, next turn
    out.sort(key=lambda r: r.due_s)
    for i, r in enumerate(out):
        r.index = i
    return out

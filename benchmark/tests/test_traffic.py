"""The open-loop schedule: due instants from the seed, the same sizes for
every seed in another order, latency taken from the due instant, lateness
reported."""

import json
import os
import time

from drivers import http_open_loop as drv
from lib import spec, traffic

from conftest import BENCH

MIX = json.load(open(os.path.join(BENCH, "traffic", "chat_open.json")))


def test_same_seed_same_requests():
    a = traffic.make_requests(MIX, 2**31 + 77, 30, 32768)
    b = traffic.make_requests(MIX, 2**31 + 77, 30, 32768)
    assert [(r.due_s, r.prompt, r.max_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_tokens) for r in b]


def test_every_seed_offers_the_same_schedule_with_other_tokens():
    a = traffic.make_requests(MIX, 1, 30, 32768)
    b = traffic.make_requests(MIX, 2, 30, 32768)
    assert [(r.due_s, len(r.prompt), r.max_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_tokens) for r in b]
    assert a[0].prompt != b[0].prompt
    rate = MIX["arrivals"]["rate_per_s"]
    assert abs(len(a) - 30 * rate) <= 2
    assert all(0 <= r.due_s < 30 for r in a)
    assert all(32 <= len(r.prompt) <= 1024 and 32 <= r.max_tokens <= 256
               for r in a)
    assert all(1 <= t < 32768 for t in a[0].prompt)
    # the sizes are the law's stratified quantiles: their mean is the law's
    assert 315 < sum(len(r.prompt) for r in a) / len(a) < 340
    assert 60 < sum(r.max_tokens for r in a) / len(a) < 70


def test_laws():
    law = {"bins": [[32, 127, 0.35], [128, 511, 0.45], [512, 1024, 0.2]]}
    assert traffic.quantile(law, 0.0001) == 32
    assert traffic.quantile(law, 0.9999) == 1024
    assert 128 <= traffic.quantile(law, 0.5) <= 511
    par = {"bounded_pareto": {"lo": 32, "hi": 256, "shape": 1.5}}
    assert traffic.quantile(par, 1e-9) == 32
    assert traffic.quantile(par, 1 - 1e-9) == 256


def test_the_other_laws():
    assert traffic.quantile({"fixed": 7}, 0.3) == 7
    uni = {"uniform": [16, 64]}
    assert traffic.quantile(uni, 1e-9) == 16
    assert traffic.quantile(uni, 1 - 1e-9) == 64
    vals = traffic.stratified(uni, 49, traffic.np.random.default_rng(0))
    assert sorted(vals) == list(range(16, 65))      # each length once
    lst = {"list": [1100, 1500, 1900]}
    assert [traffic.quantile(lst, u) for u in (0.1, 0.5, 0.9)] == \
        [1100, 1500, 1900]


def sized(mix, seed, seconds=30):
    return [(r.due_s, len(r.prompt), r.max_tokens)
            for r in traffic.make_requests(mix, seed, seconds, 32768)]


def test_bursty_arrivals():
    mix = dict(MIX, arrivals={"process": "bursty", "rate_per_s": 9.6,
                              "burst_every_s": 5, "burst_size": 12})
    reqs = traffic.make_requests(mix, 1, 30, 32768)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and all(0 <= t < 30 for t in due)
    # 12 at once in the middle of every 5 s, the rest Poisson at what is
    # left of the rate, so the whole still offers 9.6 a second
    for t in (2.5, 7.5, 12.5, 17.5, 22.5, 27.5):
        assert due.count(t) == 12
    assert abs(len(reqs) - 30 * 9.6) <= 2
    assert sized(mix, 1) == sized(mix, 2)


def test_replayed_arrivals():
    mix = dict(MIX, arrivals={"process": "replay",
                              "at_s": [4.0, 0.5, 1.0, 31.0, 29.9]},
               prompt_tokens={"list": [1100, 1500, 1900, 1200]},
               output_tokens={"uniform": [16, 64]})
    reqs = traffic.make_requests(mix, 3, 30, 32768)
    assert [r.due_s for r in reqs] == [0.5, 1.0, 4.0, 29.9]
    assert sorted(len(r.prompt) for r in reqs) == [1100, 1200, 1500, 1900]
    assert all(16 <= r.max_tokens <= 64 for r in reqs)


SHARED = dict(MIX, prompt_tokens={"uniform": [16, 64]},
              output_tokens={"uniform": [32, 128]},
              sharing={"templates": 8, "zipf": 1.1,
                       "template_tokens": {"uniform": [512, 1024]}})


def test_shared_templates():
    a = traffic.make_requests(SHARED, 1, 30, 32768)
    b = traffic.make_requests(SHARED, 2, 30, 32768)
    assert sized(SHARED, 1) == sized(SHARED, 2)
    # a prompt is one of 8 fixed templates, the same for every seed, and
    # then tokens of its own, which the seed draws
    heads = {}
    for ra, rb in zip(a, b):
        n = next(i for i, (x, y) in enumerate(zip(ra.prompt, rb.prompt))
                 if x != y)
        assert 512 <= n and 16 <= len(ra.prompt) - n + 2   # a chance match
        heads.setdefault(tuple(ra.prompt[:512]), []).append(ra)
    assert len(heads) <= 8
    counts = sorted((len(v) for v in heads.values()), reverse=True)
    assert counts[0] > 3 * counts[-1]               # Zipf: a few are hot
    for group in heads.values():                    # one template each
        m = min(len(r.prompt) for r in group) - 64
        assert len({tuple(r.prompt[:m]) for r in group}) == 1


def test_sessions_extend_the_turn_before():
    share = dict(SHARED["sharing"], turns={"uniform": [2, 4]}, think_s=6.0)
    mix = dict(SHARED, sharing=share,
               arrivals={"process": "poisson", "rate_per_s": 2.0})
    reqs = traffic.make_requests(mix, 5, 30, 32768)
    assert [r.index for r in reqs] == list(range(len(reqs)))
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
    assert all(r.due_s < 30 for r in reqs)
    assert sized(mix, 5) == sized(mix, 6)
    # every turn after a session's first is due think_s after the turn
    # before and repeats its whole prompt, an answer of max_tokens, and a
    # new user turn
    firsts = later = 0
    for r in reqs:
        prev = [q for q in reqs if abs(q.due_s - (r.due_s - 6.0)) < 1e-9
                and q.prompt == r.prompt[:len(q.prompt)]]
        if prev:
            (q,) = prev
            assert len(r.prompt) - len(q.prompt) - q.max_tokens in range(16, 65)
            later += 1
        else:
            firsts += 1
    assert abs(firsts - 60) <= 2 and firsts <= later <= 3 * firsts


def test_latency_from_due_instant_and_lateness():
    t_open = 100.0
    req = traffic.Request(0, 2.0, [1, 2, 3], 2)
    rec = drv.new_record(req, t_open + req.due_s)
    # sent 5 ms late, first token 200 ms after it was DUE, next 50 ms on
    rec.update(sent=102.005, status=200, token_ids=[7, 8],
               token_at=[102.2, 102.25], done=102.26)
    e2e = drv.end_to_end([rec], t_open, 30.0)
    assert abs(e2e["ttft_p95_ms"] - 200.0) < 1e-6
    assert abs(e2e["token_gap_p95_ms"] - 50.0) < 1e-6
    assert abs(e2e["serve_out_tokens_per_s"] - 2 / 30.0) < 1e-12
    late = spec.metric_reader("generator_lateness_p95_ms")(
        {"requests": [rec]})
    assert abs(late - 5.0) < 1e-6
    assert drv.ok(rec)


def test_offer_sends_at_due_instants_whatever_the_server_does():
    """Open loop: a server that never answers delays no later request."""
    import socket
    import threading

    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(16)
    held = []
    stop = threading.Event()

    def accept():
        srv.settimeout(0.1)
        while not stop.is_set():
            try:
                held.append(srv.accept()[0])   # accept and say nothing
            except OSError:
                pass

    th = threading.Thread(target=accept, daemon=True)
    th.start()
    reqs = [traffic.Request(i, 0.1 * i, [1, 2], 1) for i in range(5)]
    t0 = time.monotonic()
    recs, threads = drv.offer(f"http://127.0.0.1:{srv.getsockname()[1]}",
                              "m", reqs, t0, 0.6)
    assert time.monotonic() - t0 < 1.0
    sent = [r["sent"] - r["due"] for r in recs]
    assert all(0 <= s < 0.05 for s in sent), sent
    stop.set()
    for c in held:
        c.close()
    srv.close()
    for t in threads:
        t.join(5)
    assert not any(drv.ok(r) for r in recs)

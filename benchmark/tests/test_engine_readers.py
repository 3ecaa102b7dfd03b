"""The readers of `usage.engine` (benchmark/metrics/_engine.py) on a
hand-made run: present -> the arithmetic their docstrings state; absent (a
program without the phase clock, as the parent commit of the PR that added
them) -> None, never a raise and never 0."""

import importlib

import pytest

import conftest  # noqa: F401  (puts benchmark/ on sys.path)

NAMES = ("engine_device_empty_share", "engine_host_ms_per_decode_chunk_p50",
         "decode_prefill_interference_share",
         "prefix_bank_ms_per_prompt_p50", "engine_phase_max_ms",
         "stream_first_write_lag_p50_ms")


def reader(name):
    return importlib.import_module(f"metrics.{name}").read


def request(decode_ms, phases, empty, longest, lag):
    return {"token_at": [1.0, 1.1], "token_ids": [1, 2], "usage": {
        "queue_wait_ms": 1.0, "prefill_ms": 50.0, "decode_ms": decode_ms,
        "first_write_lag_ms": lag,
        "engine": {"phases": phases, "device_empty_ms": empty,
                   "phase_max_ms": longest[0], "phase_max": longest[1]}}}


RUN = {"requests": [
    # 400 ms of decode: 4 chunks; one prefill burst of another request
    request(400.0, {"sched": [2.0, 5], "decode_plan": [4.0, 4],
                    "decode_dispatch": [6.0, 4], "decode_fetch": [300.0, 4],
                    "replay": [8.0, 5], "prefill_pack": [3.0, 1],
                    "prefill_dispatch": [5.0, 1], "prefix_bank": [12.0, 1],
                    "prefill_fetch": [60.0, 1]},
            empty=40.0, longest=(96.0, "decode_fetch"), lag=2.0),
    # 200 ms, 2 chunks, two bursts
    request(200.0, {"sched": [1.0, 3], "decode_plan": [1.0, 2],
                    "decode_dispatch": [2.0, 2], "decode_fetch": [150.0, 2],
                    "replay": [2.0, 3], "prefill_pack": [2.0, 2],
                    "prefill_dispatch": [2.0, 2], "prefix_bank": [8.0, 2],
                    "prefill_fetch": [32.0, 2]},
            empty=10.0, longest=(2000.0, "sched"), lag=4.0),
    # decoded undisturbed: no prefill phase at all, 1 chunk
    request(100.0, {"decode_plan": [1.0, 1], "decode_dispatch": [1.0, 1],
                    "decode_fetch": [97.0, 1], "replay": [1.0, 1]},
            empty=0.0, longest=(97.0, "decode_fetch"), lag=3.0),
    # failed before a usage object came back
    {"token_at": [], "token_ids": [], "usage": None},
]}

EXPECTED = {
    # median of 10 %, 5 %, 0 %
    "engine_device_empty_share": 5.0,
    # (2+4+6+8)/4 = 5.0; (1+1+2+2)/2 = 3.0; (0+1+1+1)/1 = 3.0
    "engine_host_ms_per_decode_chunk_p50": 3.0,
    # 80/400 = 20 %; 44/200 = 22 %; 0 %
    "decode_prefill_interference_share": 20.0,
    # only the two windows that saw a bank: 12/1, 8/2 -> median 8.0
    "prefix_bank_ms_per_prompt_p50": 8.0,
    "engine_phase_max_ms": 2000.0,
    "stream_first_write_lag_p50_ms": 3.0,
}


@pytest.mark.parametrize("name", NAMES)
def test_reader_arithmetic(name):
    assert reader(name)(RUN) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_without_the_field(name):
    """What the parent commit sends: the three durations, no `engine`, no
    server spans."""
    old = {"requests": [
        {"token_at": [1.0], "token_ids": [1, 2],
         "usage": {"queue_wait_ms": 1.0, "prefill_ms": 50.0,
                   "decode_ms": 100.0}},
        {"token_at": [], "token_ids": [], "usage": None}]}
    assert reader(name)(old) is None
    assert reader(name)({"requests": []}) is None


def test_every_reader_is_declared_for_the_served_cell_only():
    import json
    import os

    B = json.load(open(os.path.join(conftest.ROOT, "BENCHMARK.json")))
    mine = {m["name"]: m for m in B["per_layer"] if m["name"] in NAMES}
    assert set(mine) == set(NAMES)
    assert all(m["workloads"] == ["serve_chat_open"] for m in mine.values())
    # appended, nothing before them moved
    assert [m["name"] for m in B["per_layer"]][-len(NAMES):] == list(NAMES)

"""`decode_kv_fetched_block_share` on a recorded `usage.engine`: with
`kv_blocks` -> the median of fetched over spanned, in percent; without it
(the parent commit of the PR that added the counter) -> None, never a raise
and never 0."""

import pytest

import conftest  # noqa: F401  (puts benchmark/ on sys.path)
from metrics.decode_kv_fetched_block_share import read


def request(kv_blocks=None, decode_ms=400.0):
    engine = {"phases": {"decode_dispatch": [6.0, 4],
                         "decode_fetch": [300.0, 4]},
              "device_empty_ms": 40.0, "phase_max_ms": 96.0,
              "phase_max": "decode_fetch"}
    if kv_blocks is not None:
        engine["kv_blocks"] = kv_blocks
    return {"token_at": [1.0, 1.1], "token_ids": [1, 2], "usage": {
        "queue_wait_ms": 1.0, "prefill_ms": 50.0, "decode_ms": decode_ms,
        "engine": engine}}


FAILED = {"token_at": [], "token_ids": [], "usage": None}


def test_median_of_fetched_over_spanned():
    run = {"requests": [request([64, 256]), request([12, 32]),
                        request([100, 128]), FAILED]}
    # 25 %, 37.5 %, 78.125 %
    assert read(run) == pytest.approx(37.5)


def test_a_window_with_no_dispatch_is_left_out():
    run = {"requests": [request([0, 0]), request([8, 32])]}
    assert read(run) == pytest.approx(25.0)


@pytest.mark.parametrize("requests", [
    [request(), request(), FAILED],            # the parent: no counter
    [FAILED],
    [{"token_at": [1.0], "token_ids": [1], "usage": {
        "queue_wait_ms": 1.0, "prefill_ms": 50.0, "decode_ms": 100.0}}],
    []], ids=["no_kv_blocks", "no_usage", "no_engine", "no_requests"])
def test_finds_nothing_without_the_counter(requests):
    assert read({"requests": requests}) is None


def test_declared_for_the_served_cell():
    import json
    import pathlib

    bench = json.loads((pathlib.Path(__file__).parents[2]
                        / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "decode_kv_fetched_block_share")
    assert entry == {"name": "decode_kv_fetched_block_share", "unit": "%",
                     "better": "lower", "source": "program_counter",
                     "layer": "kernels", "moves": "token_gap_p95_ms",
                     "workloads": ["serve_chat_open"]}

"""The trace reducer on small traces kept beside it: a hand-made one whose
sums are known (small_trace.json), and a slice recorded on the chip
(recorded_trace.json, when present)."""

import json
import os

import pytest

from lib import tracered

from conftest import HERE


def load(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def test_busy_union_idle_and_collectives_by_hand():
    out = tracered.reduce(load("small_trace.json"), n_devices=2)
    # chip 0: busy 900 + 1000 ns; chip 1: 900 ns; averaged over two chips
    assert out["busy_s"] == pytest.approx((1900 + 900) / 2 * 1e-9)
    assert out["window_s"] == pytest.approx(3000e-9)   # 1000 .. 4000
    # collectives on chip 0: 200 + 50 + 250 ns, none overlapped by compute
    assert out["collective_s"] == pytest.approx(500 / 2 * 1e-9)
    assert out["collective_exposed_s"] == pytest.approx(500 / 2 * 1e-9)
    ops = {name: (s, c) for name, s, c in out["ops"]}
    assert ops["custom-call.7"][0] == pytest.approx(1200 / 2 * 1e-9)
    assert ops["custom-call.7"][1] == pytest.approx(3 / 2)
    # the one idle gap on chip 0 lies before the second jit_step
    assert out["breakdown"]["idle_gaps"][0][0] == "before jit_step"
    assert out["breakdown"]["idle_gaps"][0][1] == pytest.approx(1100 / 2 * 1e-9)
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_one_chip_only_counts_that_chip():
    out = tracered.reduce(load("small_trace.json"), n_devices=1)
    assert out["busy_s"] == pytest.approx(1900e-9)


def test_overlap_is_not_exposed():
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ["all-reduce.1", 0, 100], ["fusion.2", 50, 100]]}]}]
    out = tracered.reduce(planes, n_devices=1)
    assert out["busy_s"] == pytest.approx(150e-9)
    assert out["collective_exposed_s"] == pytest.approx(50e-9)


def test_an_enclosing_while_hides_nothing():
    # a scanned layer: the `while` spans its body, the all-reduce is one of
    # the body's operations on a serial line, so all of it is exposed
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ["while.1", 0, 1000], ["fusion.2", 0, 300], ["all-reduce.3", 300, 200],
        ["fusion.4", 500, 400], ["all-gather-start.5", 900, 10],
        ["fusion.6", 910, 60], ["all-gather-done.5", 970, 30]]}]}]
    out = tracered.reduce(planes, n_devices=1)
    assert out["busy_s"] == pytest.approx(1000e-9)
    assert out["collective_s"] == pytest.approx(240e-9)
    assert out["collective_exposed_s"] == pytest.approx(240e-9)
    ops = {name: s for name, s, _ in out["ops"]}
    assert ops["while.1"] == pytest.approx(0.0)     # self time: all nested
    assert ops["all-reduce.3"] == pytest.approx(200e-9)


def test_recorded_train_trace_collectives_sit_inside_the_scan():
    # two chips' first 1000 operations of a traced train_fsdp2tp2 step, as
    # recorded on the chip: the tensor-parallel all-reduces run inside the
    # scan's `while.47`, synchronous, so every one is exposed in full
    planes = load("recorded_train_trace.json")
    out = tracered.reduce(planes, n_devices=2)
    by_hand = 0
    for plane in planes:
        events = tracered.line_events(plane, tracered.OPS_LINE)
        assert any(n.startswith("while") for n, _, _ in events)
        by_hand += sum(d for n, _, d in events
                       if tracered.COLLECTIVE.search(n))
    assert out["collective_s"] == pytest.approx(by_hand / 2 * 1e-9)
    assert out["collective_exposed_s"] == pytest.approx(out["collective_s"])
    assert out["collective_exposed_s"] > 0.03 * out["busy_s"]


def test_no_device_plane_is_an_error():
    with pytest.raises(RuntimeError):
        tracered.reduce([{"name": "/host:CPU", "lines": []}], n_devices=1)


def test_recorded_trace():
    path = os.path.join(HERE, "recorded_trace.json")
    if not os.path.exists(path):
        pytest.skip("no slice recorded on the chip is kept here")
    out = tracered.reduce(load("recorded_trace.json"), n_devices=1)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["ops"]

"""BENCHMARK.json against the contract's limits, and every name in it
against the files the harness will look for."""

import json
import os
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
B = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_shape_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["benchmark"] and 1 <= B["run_seconds"] <= 51
    assert len(B["command"]) <= 32 and all(line(w) for w in B["command"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    cells = B["workloads"]
    assert 1 <= len(cells) <= 24
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    for c in cells:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4) and line(c["why"])
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    used = {c["config"] for c in cells}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("benchmark/") and line(c["source"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
        assert not any(re.search(r"_dim$|_rank$|hidden_size|intermediate|"
                                 r"head", k) for k in c["reduced"])
    assert len({c["file"] for c in B["configs"]}) == len(B["configs"])


def test_metrics():
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = {c["name"] for c in B["workloads"]}
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
        assert line(m["layer"]) and m["moves"] in e2e
        # each cell it lists reports the end-to-end metric it moves
        for w in m.get("workloads", cells):
            assert w in e2e[m["moves"]].get("workloads", cells)
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for c in cells:   # set-up, one other end-to-end metric, one per-layer
        mine = [m for m in B["end_to_end"]
                if c in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(c in m.get("workloads", cells) for m in B["per_layer"])
    # a roofline or an mfu share is in percent
    for m in B["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_name_has_its_file():
    for c in B["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        for k in c["reduced"]:
            assert cfg["published"][k] != cfg[k]
        assert os.path.exists(os.path.join(
            BENCH, "reference", cfg["family"] + ".py"))
    for c in B["workloads"]:
        mix = json.load(open(os.path.join(BENCH, "traffic",
                                          c["traffic"] + ".json")))
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           mix["driver"] + ".py"))
        assert mix["limits"]
    for m in B["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
    for root, _, files in os.walk(BENCH):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.fullmatch(r"[A-Za-z0-9_.\-]+", f), f

"""The yardstick's own light tests, under the tier-1 command (ISSUE 29).

`benchmark/tests/` is collected by nobody (the tier-1 command collects
`tests/`), so the trace reducer, the operation counts, the traffic
schedule, `BENCHMARK.json`'s shape and the `usage.engine` readers were
tested only by hand. This module loads the files of it (LIGHT: seven of
ISSUE 29, one of ISSUE 34, one of ISSUE 36, one of ISSUE 38) that read
recorded traces, counts and JSON and compile nothing (two seconds
together) and re-exports their tests, one name each, so each counts.
Nothing under `benchmark/` is edited for it. The three heavy files
(`test_reference.py`, `test_harness.py`, `test_kimi_linear_cell.py`: they
build and run models) stay run by hand, as `benchmark/tests/conftest.py`
says.

The files say `from conftest import HERE` and `from lib import ...`,
meaning THEIR conftest and the benchmark's packages: so the benchmark's
conftest is loaded by path (it puts the repo root and `benchmark/` on
`sys.path`) and answers to the name `conftest` while the seven are
imported, and this directory's conftest has the name back afterwards.
"""

import importlib.util
import os
import sys

import pytest

BENCH_TESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests")

LIGHT = ("test_tracered", "test_opcount", "test_traffic",
         "test_benchmark_json", "test_engine_readers",
         "test_decode_kv_fetched_block_share",
         "test_quant_matmul_stacked_roofline", "test_laguna_cell_light",
         "test_host_readers", "test_pangu_cell_light",
         "test_nemotron_cell_light")

#: tests of a LIGHT file that build and run models: by hand only
HEAVY = {"test_host_readers__cpu_rehearsal_prints_the_new_metrics",
         "test_nemotron_cell_light__cpu_rehearsal"}

#: tests known to fail, by name, each with its reason
XFAIL = {
    "test_engine_readers__every_reader_is_declared_for_the_served_cell_only":
        "asserts that PR 25's six metrics are the LAST of per_layer; PR 26 "
        "appended one; PERF.md section 7 leaves the assert to a benchmark PR "
        "(nothing under benchmark/ may change here)",
    "test_host_readers__every_reader_is_declared_for_cells_that_exist":
        "two of its asserts break: PR 36's served metrics list exactly the "
        "two served cells of PR 36, and they are the LAST of per_layer; "
        "ISSUE 38 appends its served cell to the lists and four metrics "
        "after them. Everything else it asserts is asserted again by "
        "test_host_readers_declared_with_the_latent_cell below",
}

#: the LIGHT modules as loaded, by stem
MODULES: dict = {}


def _load(name: str, as_name: str):
    spec = importlib.util.spec_from_file_location(
        as_name, os.path.join(BENCH_TESTS, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _collect() -> dict:
    tests = {}
    ours = sys.modules.get("conftest")
    sys.modules["conftest"] = _load("conftest", "benchmark_tests_conftest")
    try:
        for stem in LIGHT:
            module = MODULES[stem] = _load(stem, "benchmark_tests_" + stem)
            for attr, fn in vars(module).items():
                if attr.startswith("test_") and callable(fn):
                    name = f"{stem}__{attr[len('test_'):]}"
                    if name in HEAVY:
                        continue
                    if name in XFAIL:
                        fn = pytest.mark.xfail(
                            strict=False, reason=XFAIL[name])(fn)
                    tests[name] = fn
    finally:
        if ours is None:
            del sys.modules["conftest"]
        else:
            sys.modules["conftest"] = ours
    return tests


globals().update(_collect())


def test_host_readers_declared_with_the_latent_cell():
    """`test_host_readers__every_reader_is_declared_for_cells_that_exist`
    without its two asserts that ISSUE 38's appends break: the served
    metrics list the THREE served cells (a later served cell is added to
    none of them), and the seven are one run of per_layer, in order,
    followed only by metrics of one served cell each, neither the chat
    cell nor the Laguna cell."""
    import json

    m = MODULES["test_host_readers"]
    with open(os.path.join(m.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    end_to_end = {e["name"]: e for e in bench["end_to_end"]}
    declared = {d["name"]: d for d in bench["per_layer"]}
    served = {"serve_chat_open", "serve_laguna_xs2_mixed_open",
              "serve_pangu_ultra_moe_long_open"}
    for name in m.NAMES:
        d = declared[name]
        assert os.path.isfile(os.path.join(m.BENCH, "metrics", name + ".py"))
        assert d["source"] == "program_span" and d["better"] == "lower"
        assert d["unit"] == ("ms" if name.endswith("_ms") else "%")
        assert set(d["workloads"]) <= cells
        assert set(d["workloads"]) <= set(
            end_to_end[d["moves"]]["workloads"])
        if name in m.TRAINED:
            assert d["workloads"] == ["train_kimi_linear_ep32_s8k"]
            assert d["layer"] == "trainer"
        elif name == "engine_device_empty_bank_share":
            assert d["workloads"] == ["serve_chat_open"]
        else:
            assert set(d["workloads"]) == served
    order = [d["name"] for d in bench["per_layer"]]
    at = order.index(m.NAMES[0])
    assert order[at:at + len(m.NAMES)] == list(m.NAMES)
    # each metric after the seven lists exactly one cell: a served cell
    # added after them, when the chat and Laguna cells were the served ones
    served_since = set(end_to_end["serve_out_tokens_per_s"]["workloads"]
                          ) - {"serve_chat_open",
                               "serve_laguna_xs2_mixed_open"}
    for later in bench["per_layer"][at + len(m.NAMES):]:
        assert len(later["workloads"]) == 1, later["name"]
        assert later["workloads"][0] in served_since, later["name"]


@pytest.mark.parametrize("entries,keys", [
    ("configs", ("why", "source")), ("workloads", ("why",)),
    ("per_layer", ("layer",))])
def test_every_text_field_fits_one_line(entries, keys):
    """Each `why`, `source` and `layer` is 1 to 200 printable characters
    on one line, a configuration's `why` included, which
    `test_benchmark_json__shape_and_limits` does not hold to the limit."""
    import json

    m = MODULES["test_benchmark_json"]
    with open(os.path.join(m.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench[entries]:
        for key in keys:
            text = entry[key]
            assert m.line(text) and text.isprintable(), (
                entry["name"], key, len(text))

"""The yardstick's own light tests, under the tier-1 command (ISSUE 29).

`benchmark/tests/` is collected by nobody (the tier-1 command collects
`tests/`), so the trace reducer, the operation counts, the traffic
schedule, `BENCHMARK.json`'s shape and the `usage.engine` readers were
tested only by hand. This module loads the files of it (LIGHT: seven of ISSUE 29, one of
ISSUE 34, one of ISSUE 36) that read
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
         "test_host_readers")

#: tests of a LIGHT file that build and run models: by hand only
HEAVY = {"test_host_readers__cpu_rehearsal_prints_the_new_metrics"}

#: tests known to fail, by name, each with its reason
XFAIL = {
    "test_engine_readers__every_reader_is_declared_for_the_served_cell_only":
        "asserts that PR 25's six metrics are the LAST of per_layer; PR 26 "
        "appended one; PERF.md section 7 leaves the assert to a benchmark PR "
        "(nothing under benchmark/ may change here)",
}


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
            module = _load(stem, "benchmark_tests_" + stem)
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

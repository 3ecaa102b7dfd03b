#!/usr/bin/env python
"""Observability lint (ISSUE 17, CI satellite): the two telemetry
invariants the obs layer's design rests on, enforced statically.

Rules (AST, no imports of the checked code):

1. Metric names live in ONE place. Instrument creation —
   `<registry>.counter("name", ...)` / `.gauge(...)` / `.histogram(...)`
   with a string-literal name — is allowed only in the central registry
   modules (`kubeflow_tpu/utils/metrics.py`, `kubeflow_tpu/obs/metrics.py`).
   Every other module imports the instrument object; a metric minted at
   a call site would dodge the naming convention, the /metrics
   regression tests, and the one-name-one-type guarantee
   (`Registry._get_or_make` raises on label drift only if both creators
   actually meet in one module).
2. Decode hot paths never mint spans. Inside the engine step/decode/
   prefill driver functions (the per-token loop), `span(...)` /
   `record_span(...)` calls are banned. What those functions may do is
   enter PHASES on the engine's `PhaseClock` (`clock.enter(...)`: two
   clock reads and a profiler annotation per transition, nothing per
   token) and bump its counters (`StepAggregator.note_step`), with the
   ONE retrospective span per request emitted at finish time
   (`_obs_finish`, off the hot path). A live span per step would put an
   allocation + deque append + lock in the tokens/sec denominator. The
   one span the clock itself records is a `stall` (a phase occurrence of
   500 ms or more: rare by construction), inside `obs/trace.py`, which
   is not a hot path file.

Run: `python scripts/check_observability.py` — exit 0 clean, 1 with
findings (one per line). The fast lane runs it via
tests/test_observability_lint.py.
"""

from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "kubeflow_tpu")

#: the only modules allowed to CREATE instruments (rule 1)
REGISTRY_MODULES = (
    os.path.join("kubeflow_tpu", "utils", "metrics.py"),
    os.path.join("kubeflow_tpu", "obs", "metrics.py"),
)

_INSTRUMENT_METHODS = ("counter", "gauge", "histogram")

#: engine files whose hot functions rule 2 covers, and the function-name
#: markers of the per-token loop in each (lexical nesting counts: a
#: helper defined INSIDE a hot function is hot too)
HOT_PATHS = {
    os.path.join("kubeflow_tpu", "serving", "llm.py"):
        ("step", "_step", "_do_decode", "_replay", "_run_prefill_actions",
         "_decode", "_decode_fn", "_prefill", "_prefill_cont",
         "_prefill_fn"),
    os.path.join("kubeflow_tpu", "serving", "multichip.py"):
        ("step", "_do_decode", "_decode_driver", "_decode_fn",
         "_prefill_fn"),
    os.path.join("kubeflow_tpu", "serving", "disagg.py"):
        ("step", "_prefill_loop"),
}

_SPAN_CALLS = ("span", "record_span", "start_span")


def _py_files(root: str):
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", "tests")]
        for fn in filenames:
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


class _ObsVisitor(ast.NodeVisitor):
    """Collect (a) instrument-creation calls with a string-literal
    name, (b) span-minting calls, each with the enclosing function-name
    stack."""

    def __init__(self):
        self.stack: list[str] = []
        self.instruments: list[tuple[int, str, str]] = []
        self.span_calls: list[tuple[int, str, list[str]]] = []

    def _visit_func(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_Call(self, node: ast.Call):
        fn = node.func
        if isinstance(fn, ast.Attribute):
            if (fn.attr in _INSTRUMENT_METHODS and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                self.instruments.append(
                    (node.lineno, fn.attr, node.args[0].value))
            if fn.attr in _SPAN_CALLS:
                self.span_calls.append(
                    (node.lineno, fn.attr, list(self.stack)))
        self.generic_visit(node)


def check(pkg_root: str = PKG, repo_root: str = REPO) -> list[str]:
    findings: list[str] = []
    for path in sorted(_py_files(pkg_root)):
        rel = os.path.relpath(path, repo_root)
        with open(path, encoding="utf-8") as f:
            src = f.read()
        try:
            tree = ast.parse(src, filename=rel)
        except SyntaxError as e:
            findings.append(f"{rel}: unparseable ({e})")
            continue
        v = _ObsVisitor()
        v.visit(tree)
        if rel not in REGISTRY_MODULES:
            for lineno, method, name in v.instruments:
                findings.append(
                    f"{rel}:{lineno}: .{method}({name!r}, ...) mints a "
                    "metric outside the central registry modules — "
                    "define the instrument in obs/metrics.py (or "
                    "utils/metrics.py) and import it")
        hot_names = HOT_PATHS.get(rel)
        if hot_names:
            for lineno, call, stack in v.span_calls:
                if any(name in hot_names for name in stack):
                    findings.append(
                        f"{rel}:{lineno}: {call}(...) inside hot "
                        f"function {'/'.join(stack)} — decode/prefill "
                        "loops enter PhaseClock phases and record "
                        "through StepAggregator.note_step only; emit "
                        "the retrospective span at finish time "
                        "(_obs_finish)")
    return findings


def main() -> int:
    findings = check()
    for f in findings:
        print(f)
    if findings:
        print(f"check_observability: {len(findings)} finding(s)")
        return 1
    print("check_observability: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())

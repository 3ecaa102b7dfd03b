#!/usr/bin/env python
"""Kernel-path lint (ISSUE 15, CI satellite): an untestable-on-CPU
Pallas kernel must never land. With the kernel path ON BY DEFAULT on
TPU (flash-decode attention, fused dequant matmul), the only thing
standing between a kernel edit and silent production corruption is the
interpret-mode differential gauntlet — so its preconditions are
enforced statically, the check_dataplane.py pattern:

Rules (AST + text, no imports of the checked code), applied to every
module under `kubeflow_tpu/ops/` that calls `pallas_call`:

1. Every `pallas_call` call site passes an `interpret=` keyword — a
   kernel hard-wired to compiled Mosaic cannot run its byte-level
   differential tests in the CPU fast lane.
2. The module defines `FORCE_INTERPRET` — the seam the tests flip to
   route numerics through the interpreter (the ops/flash_pallas.py
   convention every kernel here follows).
3. The module is referenced by name from at least one `tests/test_*.py`
   — a kernel no parity test imports is, by construction, untested.
4. `tests/test_kernels_lower_tpu.py` declares, in `PALLAS_CALL_SITES`,
   exactly as many call sites for the module as its source has — the
   interpreter accepts block shapes Mosaic refuses, so every call site
   is also lowered for TPU (compiled, from the CPU fast lane) there.

And one rule for the whole compiled path (ISSUE 29), whatever calls
`pallas_call`:

5. No module under `kubeflow_tpu/ops`, `kubeflow_tpu/parallel`,
   `kubeflow_tpu/models` or `kubeflow_tpu/serving` reads the process
   environment (`os.environ`, `os.getenv`), `serving/storage.py` apart
   (paths and credentials: deployment settings). Which kernel, schedule
   or KV layout runs is the configuration's explicit value or a rule over
   what the code can observe (the target platform, the head layout, an
   active mesh), so a trace never raises the question of what was set on
   the machine that made it.

Run: `python scripts/check_kernels.py` — exit 0 clean, 1 with findings
(one per line). The fast lane runs it via tests/test_dataplane_lint.py.
"""

from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPS = os.path.join(REPO, "kubeflow_tpu", "ops")
TESTS = os.path.join(REPO, "tests")
LOWERING_TEST = "test_kernels_lower_tpu.py"
#: rule 5's scope, and the one module in it that reads deployment settings
ENV_FREE_PACKAGES = ("ops", "parallel", "models", "serving")
ENV_READ_ALLOWED = (os.path.join("serving", "storage.py"),)


class _PallasCallVisitor(ast.NodeVisitor):
    """Collect pallas_call call sites and whether each passes
    interpret=."""

    def __init__(self):
        self.calls: list[tuple[int, bool]] = []

    def visit_Call(self, node: ast.Call):
        fn = node.func
        name = (fn.id if isinstance(fn, ast.Name)
                else fn.attr if isinstance(fn, ast.Attribute) else None)
        if name == "pallas_call":
            has_interpret = any(kw.arg == "interpret"
                                for kw in node.keywords)
            self.calls.append((node.lineno, has_interpret))
        self.generic_visit(node)


def _test_references(tests_root: str) -> str:
    """Concatenated source of every tests/test_*.py but the TPU-lowering
    test, which proves no numbers (module-name reference check is
    textual: any import or attribute spelling counts)."""
    chunks = []
    if os.path.isdir(tests_root):
        for fn in sorted(os.listdir(tests_root)):
            if (fn.startswith("test_") and fn.endswith(".py")
                    and fn != LOWERING_TEST):
                with open(os.path.join(tests_root, fn),
                          encoding="utf-8") as f:
                    chunks.append(f.read())
    return "\n".join(chunks)


def _lowered_call_sites(tests_root: str) -> dict[str, int]:
    """The PALLAS_CALL_SITES literal of the TPU-lowering test ({} when
    the file or the table is missing — every kernel then has a finding)."""
    path = os.path.join(tests_root, LOWERING_TEST)
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "PALLAS_CALL_SITES"):
            return dict(ast.literal_eval(node.value))
    return {}


def _env_reads(tree: ast.AST) -> list[int]:
    """Line numbers where the module touches the process environment:
    `os.environ` / `os.getenv` (any use: a read is what the rule is
    about, and these packages have no reason to write it either), or
    either name imported from `os`."""
    lines = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute)
                and node.attr in ("environ", "getenv")
                and isinstance(node.value, ast.Name)
                and node.value.id == "os"):
            lines.append(node.lineno)
        elif isinstance(node, ast.ImportFrom) and node.module == "os":
            lines.extend(node.lineno for a in node.names
                         if a.name in ("environ", "getenv"))
    return lines


def check_env_free(pkg_root: str) -> list[str]:
    """Rule 5 over `<pkg_root>/{ops,parallel,models,serving}`."""
    findings: list[str] = []
    for package in ENV_FREE_PACKAGES:
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(pkg_root, package)):
            dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
            for fn in sorted(filenames):
                path = os.path.join(dirpath, fn)
                inside = os.path.relpath(path, pkg_root)
                if not fn.endswith(".py") or inside in ENV_READ_ALLOWED:
                    continue
                rel = os.path.join(os.path.basename(pkg_root), inside)
                with open(path, encoding="utf-8") as f:
                    src = f.read()
                if "environ" not in src and "getenv" not in src:
                    continue
                try:
                    tree = ast.parse(src, filename=rel)
                except SyntaxError as e:
                    findings.append(f"{rel}: unparseable ({e})")
                    continue
                findings.extend(
                    f"{rel}:{lineno}: reads the process environment — a "
                    "selection on the compiled path is the configuration's "
                    "explicit value or a rule over what the code can "
                    "observe, never a variable set on the machine"
                    for lineno in _env_reads(tree))
    return findings


def check(ops_root: str = OPS, tests_root: str = TESTS) -> list[str]:
    findings: list[str] = check_env_free(os.path.dirname(ops_root))
    test_src = _test_references(tests_root)
    lowered = _lowered_call_sites(tests_root)
    for fn in sorted(os.listdir(ops_root)):
        if not fn.endswith(".py"):
            continue
        path = os.path.join(ops_root, fn)
        rel = os.path.relpath(path, os.path.dirname(
            os.path.dirname(ops_root)))
        with open(path, encoding="utf-8") as f:
            src = f.read()
        if "pallas_call" not in src:
            continue
        try:
            tree = ast.parse(src, filename=rel)
        except SyntaxError as e:
            findings.append(f"{rel}: unparseable ({e})")
            continue
        v = _PallasCallVisitor()
        v.visit(tree)
        for lineno, has_interpret in v.calls:
            if not has_interpret:
                findings.append(
                    f"{rel}:{lineno}: pallas_call without an interpret= "
                    "keyword — the kernel cannot run its differential "
                    "tests on the CPU fast lane (thread an `interpret` "
                    "argument through, the ops/flash_pallas.py pattern)")
        if v.calls and "FORCE_INTERPRET" not in src:
            findings.append(
                f"{rel}: kernel module without a FORCE_INTERPRET seam — "
                "tests cannot route its numerics through the Pallas "
                "interpreter")
        module = fn[:-3]
        if v.calls and module not in test_src:
            findings.append(
                f"{rel}: kernel module not referenced by any "
                "tests/test_*.py — land it WITH its interpret-mode "
                "parity test")
        if v.calls and lowered.get(module) != len(v.calls):
            findings.append(
                f"{rel}: {len(v.calls)} pallas_call site(s) but "
                f"tests/{LOWERING_TEST} PALLAS_CALL_SITES declares "
                f"{lowered.get(module, 0)} — lower every call site for "
                "TPU there (interpret=False, lowering_platforms=('tpu',))")
    return findings


def main() -> int:
    findings = check()
    for f in findings:
        print(f)
    if findings:
        print(f"check_kernels: {len(findings)} finding(s)")
        return 1
    print("check_kernels: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())

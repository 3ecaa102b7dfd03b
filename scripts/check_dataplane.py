#!/usr/bin/env python
"""Dataplane lint (ISSUE 12, CI satellite): the unified-dataplane
invariant — every engine sits behind an `EngineSupervisor` — enforced
statically, so a future module cannot quietly construct or drive a bare
`LLMEngine` on the serving path and reopen the crash hole.

Rules (AST, no imports of the checked code):

1. Inside `kubeflow_tpu/` (tests excluded), `LLMEngine(...)` — and the
   disaggregated role engines `PrefillEngine(...)` / `DecodeEngine(...)`
   (ISSUE 13) — may only be constructed inside a function whose name
   marks it as a supervisor factory (`factory` in the name) — the
   closure handed to `EngineSupervisor`. Everything else must take a
   supervised engine from the outside.
2. The HTTP/gRPC frontends (`serving/server.py`, `serving/grpc_server.py`)
   must not reference any engine class at all — they speak to engines
   only through the `Model` abstraction, whose engine is the supervisor
   (or the disaggregated coordinator).
3. (ISSUE 19) `make_block_pool_buffers` — the single sanctioned
   construction site for paged KV block-pool device buffers — may only
   be called from inside `kubeflow_tpu/kvcache/`. Everyone else
   (PagedLLMEngine included) takes buffers from a `BlockPool`, so the
   pool's free-list/refcounts are the ONLY owner of KV memory.
4. Rule 1's scope is the library package: `benchmark/` and
   `chip_smoke.py` reach engines through `Platform` / `LLMModel` and are
   out of scope here by path.

Run: `python scripts/check_dataplane.py` — exit 0 clean, 1 with findings
(one per line). The fast lane runs it via tests/test_dataplane_lint.py.
"""

from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "kubeflow_tpu")

#: every class the factory rule and the engine-blind rule cover: the
#: bare engine, the disaggregated role engines (a rogue PrefillEngine
#: would be exactly the unsupervised crash hole rule 1 closes for
#: LLMEngine), and the tp×pp stage-sharded engine (ISSUE 14 — a
#: multichip engine crashing without a supervisor strands pp device
#: groups at once)
ENGINE_NAMES = ("LLMEngine", "PrefillEngine", "DecodeEngine",
                "StageShardedEngine", "PagedLLMEngine")

#: the single sanctioned construction site for paged KV block-pool
#: device buffers (ISSUE 19): only `kubeflow_tpu/kvcache/` may call it.
#: A module allocating pool buffers directly would create KV memory the
#: BlockPool's refcounts/free-list cannot see — the exact
#: double-ownership the paged design removes.
POOL_CTOR = "make_block_pool_buffers"
POOL_OWNER_DIR = os.path.join("kubeflow_tpu", "kvcache")

#: frontends that must stay engine-blind (rule 2)
ENGINE_BLIND = (
    os.path.join("kubeflow_tpu", "serving", "server.py"),
    os.path.join("kubeflow_tpu", "serving", "grpc_server.py"),
)


def _py_files(root: str):
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", "tests")]
        for fn in filenames:
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


class _EngineCallVisitor(ast.NodeVisitor):
    """Collect engine-class call sites (ENGINE_NAMES) with their
    enclosing function names (lexical nesting)."""

    def __init__(self):
        self.stack: list[str] = []
        self.calls: list[tuple[int, str, list[str]]] = []
        self.pool_calls: list[int] = []

    def _visit_func(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_Call(self, node: ast.Call):
        fn = node.func
        name = (fn.id if isinstance(fn, ast.Name)
                else fn.attr if isinstance(fn, ast.Attribute) else None)
        if name in ENGINE_NAMES:
            self.calls.append((node.lineno, name, list(self.stack)))
        if name == POOL_CTOR:
            self.pool_calls.append(node.lineno)
        self.generic_visit(node)


def check(pkg_root: str = PKG, repo_root: str = REPO) -> list[str]:
    findings: list[str] = []
    # the files DEFINING engine classes are allowed to mention them
    engine_defs = (
        os.path.join("kubeflow_tpu", "serving", "llm.py"),
        os.path.join("kubeflow_tpu", "serving", "multichip.py"),
        os.path.join("kubeflow_tpu", "serving", "paged.py"),
    )
    for path in sorted(_py_files(pkg_root)):
        rel = os.path.relpath(path, repo_root)
        with open(path, encoding="utf-8") as f:
            src = f.read()
        blind_hits = [n for n in ENGINE_NAMES if n in src] \
            if rel in ENGINE_BLIND else []
        for n in blind_hits:
            findings.append(
                f"{rel}: references {n} — frontends must speak "
                "through the Model abstraction (supervised engine)")
        try:
            tree = ast.parse(src, filename=rel)
        except SyntaxError as e:
            findings.append(f"{rel}: unparseable ({e})")
            continue
        v = _EngineCallVisitor()
        v.visit(tree)
        if rel not in engine_defs:
            for lineno, cls, stack in v.calls:
                if any("factory" in name for name in stack):
                    continue   # the sanctioned pattern: supervisor factory
                findings.append(
                    f"{rel}:{lineno}: bare {cls} construction outside a "
                    "supervisor factory — wrap it in an EngineSupervisor "
                    "(build it inside a *factory* function handed to one)")
        if not rel.startswith(POOL_OWNER_DIR + os.sep):
            for lineno in v.pool_calls:
                findings.append(
                    f"{rel}:{lineno}: {POOL_CTOR} called outside "
                    f"{POOL_OWNER_DIR}/ — only the kvcache package may "
                    "construct block-pool buffers; everything else takes "
                    "them from a BlockPool (kvcache/pool.py)")
    return findings


def main() -> int:
    findings = check()
    for f in findings:
        print(f)
    if findings:
        print(f"check_dataplane: {len(findings)} finding(s)")
        return 1
    print("check_dataplane: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())

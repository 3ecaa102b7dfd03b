"""Fast-lane dataplane lint (ISSUE 12 satellite): no non-test module may
construct a bare LLMEngine outside a supervisor factory, and the HTTP/
gRPC frontends must stay engine-blind. scripts/check_dataplane.py is the
CI entrypoint; these tests run it in-process so the fast lane fails the
moment someone reopens the crash hole."""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "check_dataplane", os.path.join(REPO, "scripts",
                                        "check_dataplane.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_repo_dataplane_is_clean():
    lint = _load_lint()
    findings = lint.check()
    assert findings == [], "\n".join(findings)


def test_lint_runs_as_a_script():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "check_dataplane.py")],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "check_dataplane: ok" in out.stdout


def test_lint_flags_bare_engine_construction(tmp_path):
    lint = _load_lint()
    pkg = tmp_path / "kubeflow_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "rogue.py").write_text(
        "from kubeflow_tpu.serving.llm import LLMEngine\n"
        "def serve(params, cfg):\n"
        "    eng = LLMEngine(params, cfg)\n"   # bare: no supervisor
        "    return eng.submit([1], 4)\n")
    findings = lint.check(pkg_root=str(tmp_path / "kubeflow_tpu"),
                          repo_root=str(tmp_path))
    assert len(findings) == 1
    assert "rogue.py:3" in findings[0]
    assert "supervisor factory" in findings[0]


def test_lint_allows_supervisor_factory(tmp_path):
    lint = _load_lint()
    pkg = tmp_path / "kubeflow_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "fine.py").write_text(
        "from kubeflow_tpu.serving.llm import LLMEngine\n"
        "from kubeflow_tpu.serving.agent import EngineSupervisor\n"
        "def supervised(params, cfg):\n"
        "    def engine_factory():\n"
        "        return LLMEngine(params, cfg)\n"
        "    return EngineSupervisor(engine_factory)\n")
    findings = lint.check(pkg_root=str(tmp_path / "kubeflow_tpu"),
                          repo_root=str(tmp_path))
    assert findings == []


def test_lint_flags_engine_aware_frontend(tmp_path):
    lint = _load_lint()
    pkg = tmp_path / "kubeflow_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "server.py").write_text(
        "from kubeflow_tpu.serving.llm import LLMEngine\n")
    findings = lint.check(pkg_root=str(tmp_path / "kubeflow_tpu"),
                          repo_root=str(tmp_path))
    assert any("frontends must speak" in f for f in findings)


def test_lint_flags_bare_role_engine_construction(tmp_path):
    """ISSUE 13 satellite: the disaggregated role engines are held to
    the same factory rule as LLMEngine — a bare PrefillEngine/
    DecodeEngine outside a supervisor factory reopens the crash hole."""
    lint = _load_lint()
    pkg = tmp_path / "kubeflow_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "rogue_roles.py").write_text(
        "from kubeflow_tpu.serving.llm import DecodeEngine, PrefillEngine\n"
        "def serve(params, cfg):\n"
        "    pre = PrefillEngine(params, cfg)\n"
        "    dec = DecodeEngine(params, cfg)\n"
        "    return pre, dec\n")
    findings = lint.check(pkg_root=str(tmp_path / "kubeflow_tpu"),
                          repo_root=str(tmp_path))
    assert len(findings) == 2
    assert any("PrefillEngine" in f for f in findings)
    assert any("DecodeEngine" in f for f in findings)
    assert all("supervisor factory" in f for f in findings)


def test_lint_allows_role_engines_in_supervisor_factories(tmp_path):
    lint = _load_lint()
    pkg = tmp_path / "kubeflow_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "fine_roles.py").write_text(
        "from kubeflow_tpu.serving.llm import DecodeEngine, PrefillEngine\n"
        "from kubeflow_tpu.serving.agent import EngineSupervisor\n"
        "def disagg(params, cfg):\n"
        "    def prefill_engine_factory():\n"
        "        return PrefillEngine(params, cfg)\n"
        "    def decode_engine_factory():\n"
        "        return DecodeEngine(params, cfg)\n"
        "    return (EngineSupervisor(prefill_engine_factory),\n"
        "            EngineSupervisor(decode_engine_factory))\n")
    findings = lint.check(pkg_root=str(tmp_path / "kubeflow_tpu"),
                          repo_root=str(tmp_path))
    assert findings == []


def test_lint_flags_role_engine_aware_frontend(tmp_path):
    lint = _load_lint()
    pkg = tmp_path / "kubeflow_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "server.py").write_text(
        "from kubeflow_tpu.serving.llm import PrefillEngine\n")
    findings = lint.check(pkg_root=str(tmp_path / "kubeflow_tpu"),
                          repo_root=str(tmp_path))
    assert any("PrefillEngine" in f and "frontends must speak" in f
               for f in findings)


def test_lint_flags_bare_stage_sharded_engine(tmp_path):
    """The tp×pp engine (ISSUE 14) is under the same factory-only rule:
    a bare StageShardedEngine outside a supervisor factory is exactly
    the unsupervised crash hole, times pp device groups."""
    lint = _load_lint()
    pkg = tmp_path / "kubeflow_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "rogue_pp.py").write_text(
        "from kubeflow_tpu.serving.multichip import StageShardedEngine\n"
        "def serve(params, cfg):\n"
        "    return StageShardedEngine(params, cfg, stage=2)\n")
    findings = lint.check(pkg_root=str(tmp_path / "kubeflow_tpu"),
                          repo_root=str(tmp_path))
    assert len(findings) == 1
    assert "StageShardedEngine" in findings[0]
    assert "supervisor factory" in findings[0]


def test_lint_allows_stage_sharded_factory(tmp_path):
    lint = _load_lint()
    pkg = tmp_path / "kubeflow_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "fine_pp.py").write_text(
        "from kubeflow_tpu.serving.multichip import StageShardedEngine\n"
        "from kubeflow_tpu.serving.agent import EngineSupervisor\n"
        "def supervised(params, cfg):\n"
        "    def engine_factory():\n"
        "        return StageShardedEngine(params, cfg, stage=2)\n"
        "    return EngineSupervisor(engine_factory)\n")
    findings = lint.check(pkg_root=str(tmp_path / "kubeflow_tpu"),
                          repo_root=str(tmp_path))
    assert findings == []


def test_lint_flags_stage_engine_aware_frontend(tmp_path):
    lint = _load_lint()
    pkg = tmp_path / "kubeflow_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "grpc_server.py").write_text(
        "from kubeflow_tpu.serving.multichip import StageShardedEngine\n")
    findings = lint.check(pkg_root=str(tmp_path / "kubeflow_tpu"),
                          repo_root=str(tmp_path))
    assert any("StageShardedEngine" in f for f in findings)


def test_lint_flags_bare_paged_engine(tmp_path):
    """ISSUE 19 satellite: the paged engine is under the same
    factory-only rule — a bare PagedLLMEngine outside a supervisor
    factory is the unsupervised crash hole plus a leaked block pool."""
    lint = _load_lint()
    pkg = tmp_path / "kubeflow_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "rogue_paged.py").write_text(
        "from kubeflow_tpu.serving.paged import PagedLLMEngine\n"
        "def serve(params, cfg):\n"
        "    return PagedLLMEngine(params, cfg)\n")
    findings = lint.check(pkg_root=str(tmp_path / "kubeflow_tpu"),
                          repo_root=str(tmp_path))
    assert len(findings) == 1
    assert "PagedLLMEngine" in findings[0]
    assert "supervisor factory" in findings[0]


def test_lint_allows_paged_engine_factory(tmp_path):
    lint = _load_lint()
    pkg = tmp_path / "kubeflow_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "fine_paged.py").write_text(
        "from kubeflow_tpu.serving.paged import PagedLLMEngine\n"
        "from kubeflow_tpu.serving.agent import EngineSupervisor\n"
        "def supervised(params, cfg):\n"
        "    def engine_factory():\n"
        "        return PagedLLMEngine(params, cfg)\n"
        "    return EngineSupervisor(engine_factory)\n")
    findings = lint.check(pkg_root=str(tmp_path / "kubeflow_tpu"),
                          repo_root=str(tmp_path))
    assert findings == []


def test_lint_flags_pool_buffer_construction_outside_kvcache(tmp_path):
    """ISSUE 19 satellite: make_block_pool_buffers outside kvcache/
    creates KV memory the BlockPool's refcounts cannot see — flagged
    anywhere in the package, supervisor factory or not."""
    lint = _load_lint()
    pkg = tmp_path / "kubeflow_tpu" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "rogue_pool.py").write_text(
        "from kubeflow_tpu.kvcache.pool import make_block_pool_buffers\n"
        "def engine_factory(cfg):\n"
        "    return make_block_pool_buffers(2, 8, 16, 2, 4, 'float32')\n")
    findings = lint.check(pkg_root=str(tmp_path / "kubeflow_tpu"),
                          repo_root=str(tmp_path))
    assert len(findings) == 1
    assert "rogue_pool.py:3" in findings[0]
    assert "only the kvcache package" in findings[0]


def test_lint_allows_pool_buffer_construction_inside_kvcache(tmp_path):
    lint = _load_lint()
    pkg = tmp_path / "kubeflow_tpu" / "kvcache"
    pkg.mkdir(parents=True)
    (pkg / "mypool.py").write_text(
        "def make_block_pool_buffers(*a, **k):\n"
        "    return {}\n"
        "def build():\n"
        "    return make_block_pool_buffers(2, 8, 16, 2, 4, 'float32')\n")
    findings = lint.check(pkg_root=str(tmp_path / "kubeflow_tpu"),
                          repo_root=str(tmp_path))
    assert findings == []


# -- kernel-path lint (ISSUE 15 satellite: scripts/check_kernels.py) ----------
# An untestable-on-CPU Pallas kernel must never land: every ops module
# calling pallas_call must pass interpret= at each call site, expose the
# FORCE_INTERPRET seam, be referenced from a parity test, and have each
# call site lowered for TPU in tests/test_kernels_lower_tpu.py.


def _load_kernel_lint():
    spec = importlib.util.spec_from_file_location(
        "check_kernels", os.path.join(REPO, "scripts",
                                      "check_kernels.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_repo_kernels_are_clean():
    lint = _load_kernel_lint()
    findings = lint.check()
    assert findings == [], "\n".join(findings)


def test_kernel_lint_runs_as_a_script():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "check_kernels.py")],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "check_kernels: ok" in out.stdout


def _kernel_tree(tmp_path, src, test_src="",
                 lowering_src="PALLAS_CALL_SITES = {'rogue_kernel': 1}\n"):
    ops = tmp_path / "kubeflow_tpu" / "ops"
    ops.mkdir(parents=True)
    (ops / "rogue_kernel.py").write_text(src)
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_rogue.py").write_text(test_src)
    (tests / "test_kernels_lower_tpu.py").write_text(lowering_src)
    return str(ops), str(tests)


def test_kernel_lint_flags_pallas_call_without_interpret(tmp_path):
    lint = _load_kernel_lint()
    ops, tests = _kernel_tree(
        tmp_path,
        "from jax.experimental import pallas as pl\n"
        "FORCE_INTERPRET = False\n"
        "def op(x):\n"
        "    return pl.pallas_call(lambda i, o: None, out_shape=x)(x)\n",
        "from kubeflow_tpu.ops import rogue_kernel\n")
    findings = lint.check(ops_root=ops, tests_root=tests)
    assert len(findings) == 1
    assert "without an interpret=" in findings[0]
    assert "rogue_kernel.py:4" in findings[0]


def test_kernel_lint_flags_missing_force_interpret_seam(tmp_path):
    lint = _load_kernel_lint()
    ops, tests = _kernel_tree(
        tmp_path,
        "from jax.experimental import pallas as pl\n"
        "def op(x, interpret=False):\n"
        "    return pl.pallas_call(lambda i, o: None, out_shape=x,\n"
        "                          interpret=interpret)(x)\n",
        "from kubeflow_tpu.ops import rogue_kernel\n")
    findings = lint.check(ops_root=ops, tests_root=tests)
    assert len(findings) == 1
    assert "FORCE_INTERPRET" in findings[0]


def test_kernel_lint_flags_untested_kernel_module(tmp_path):
    lint = _load_kernel_lint()
    ops, tests = _kernel_tree(
        tmp_path,
        "from jax.experimental import pallas as pl\n"
        "FORCE_INTERPRET = False\n"
        "def op(x, interpret=False):\n"
        "    return pl.pallas_call(lambda i, o: None, out_shape=x,\n"
        "                          interpret=interpret)(x)\n",
        "# no reference to the kernel module here\n")
    findings = lint.check(ops_root=ops, tests_root=tests)
    assert len(findings) == 1
    assert "not referenced" in findings[0]


@pytest.mark.parametrize("lowering_src", [
    "",                                              # no table at all
    "PALLAS_CALL_SITES = {'other_kernel': 1}\n",     # module missing
    "PALLAS_CALL_SITES = {'rogue_kernel': 2}\n",     # stale count
])
def test_kernel_lint_flags_call_site_not_lowered_for_tpu(tmp_path,
                                                        lowering_src):
    lint = _load_kernel_lint()
    ops, tests = _kernel_tree(
        tmp_path,
        "from jax.experimental import pallas as pl\n"
        "FORCE_INTERPRET = False\n"
        "def op(x, interpret=False):\n"
        "    return pl.pallas_call(lambda i, o: None, out_shape=x,\n"
        "                          interpret=interpret)(x)\n",
        "from kubeflow_tpu.ops import rogue_kernel\n",
        lowering_src=lowering_src)
    findings = lint.check(ops_root=ops, tests_root=tests)
    assert len(findings) == 1
    assert "PALLAS_CALL_SITES" in findings[0]


def test_kernel_lint_ignores_pallas_free_modules(tmp_path):
    lint = _load_kernel_lint()
    ops, tests = _kernel_tree(
        tmp_path, "def op(x):\n    return x\n")
    assert lint.check(ops_root=ops, tests_root=tests) == []


#: rule 5: (file inside kubeflow_tpu/, its source, the line it is flagged at
#: or None)
ENV_READ_CASES = [
    ("ops/rogue.py", "import os\nX = os.environ.get('A_SWITCH')\n", 2),
    ("parallel/rogue.py",
     "import os\n\ndef f():\n    return os.getenv('A_SWITCH', '')\n", 4),
    ("models/rogue.py", "from os import environ\n", 1),
    ("serving/deep/rogue.py", "import os\nX = 'A' in os.environ\n", 2),
    # deployment settings: the one module of the four packages that may
    ("serving/storage.py", "import os\nX = os.environ.get('HOME')\n", None),
    # outside the compiled path: not this rule's business
    ("runtime/compile_cache.py",
     "import os\nX = os.environ.get('JAX_COMPILATION_CACHE_DIR')\n", None),
    # `environ` of something that is not `os`, and a mention in a comment
    ("ops/fine.py", "# os.environ is not read here\nX = cfg.environ\n", None),
]


@pytest.mark.parametrize("inside,src,line", ENV_READ_CASES)
def test_kernel_lint_flags_environment_reads(tmp_path, inside, src, line):
    lint = _load_kernel_lint()
    ops, tests = _kernel_tree(tmp_path, "def op(x):\n    return x\n")
    path = tmp_path / "kubeflow_tpu" / inside
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(src)
    findings = lint.check(ops_root=ops, tests_root=tests)
    if line is None:
        assert findings == []
    else:
        assert len(findings) == 1
        assert findings[0].startswith(f"kubeflow_tpu/{inside}:{line}: ")
        assert "process environment" in findings[0]

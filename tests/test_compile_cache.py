"""The one compile-cache rule (runtime/compile_cache.py): placed from
outside by JAX_COMPILATION_CACHE_DIR, else one fixed git-ignored directory
in the checkout on a TPU backend, else JAX's default."""

import os
import re
import subprocess

import jax
import pytest

from kubeflow_tpu.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh(monkeypatch):
    """Each case starts unplaced and leaves jax's config as it found it."""
    monkeypatch.setattr(compile_cache, "_placed", False)
    prev = jax.config.jax_compilation_cache_dir
    yield prev
    if jax.config.jax_compilation_cache_dir != prev:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", prev)
        compilation_cache.reset_cache()


def test_env_set_leaves_config_alone(fresh, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # even on a TPU backend: the outside placement wins
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.ensure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == fresh


def test_unset_on_cpu_keeps_jax_default(fresh, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax.default_backend() == "cpu"
    assert compile_cache.ensure_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == fresh


def test_unset_on_tpu_uses_the_fixed_ignored_dir(fresh, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.ensure_compile_cache() == compile_cache.CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
    rel = os.path.relpath(compile_cache.CACHE_DIR, REPO)
    assert not rel.startswith(".."), "cache must live inside the checkout"
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert rel + "/" in f.read().split()


def test_one_call_site_and_no_moving_path():
    """The path is part of what a cache hit depends on: nothing under
    /tmp, no pid, no timestamp — and nobody else sets the directory."""
    with open(compile_cache.__file__) as f:
        src = f.read()
    assert not re.search(
        r"tempfile|/tmp|getpid|import time|datetime|uuid", src)
    targets = [p for p in ("kubeflow_tpu", "__graft_entry__.py",
                           "chip_smoke.py")
               if os.path.exists(os.path.join(REPO, p))]
    hits = subprocess.run(
        ["grep", "-rn", "--include=*.py", "jax_compilation_cache_dir",
         *targets], cwd=REPO, capture_output=True, text=True).stdout
    assert len(hits.strip().splitlines()) == 1, hits
    assert hits.startswith("kubeflow_tpu/runtime/compile_cache.py:")

"""The entry points' compile cache, and ``chip_smoke.py``'s refusal off the chip.

``enable_compile_cache`` leaves JAX's own reading of
``JAX_COMPILATION_CACHE_DIR`` alone when it is set, and otherwise points the
cache at ``<repo>/.jax_cache``, a path that stays the same from run to run.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.utils import compile_cache
from repro.utils.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_default_cache_dir_is_the_repo_root(cache_dir_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert DEFAULT_CACHE_DIR == REPO / ".jax_cache"
    assert enable_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")


def test_env_cache_dir_wins_and_nothing_is_set(cache_dir_config, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    assert enable_compile_cache() == str(tmp_path)
    assert calls == []
    assert jax.config.jax_compilation_cache_dir == before


def test_chip_smoke_refuses_to_run_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert proc.stdout == ""  # no result line, not even a failed one

"""The chip entry point and the compile-cache helper, on the CPU."""
from __future__ import annotations

import importlib.util
import os

import jax
import pytest

from repro import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)


def test_compile_cache_defaults_to_the_repo(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_leaves_the_variable_to_jax(monkeypatch, tmp_path,
                                                  restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # not re-set


def test_chip_smoke_refuses_the_cpu(capsys):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--four-chips"]) != 0
    out = capsys.readouterr()
    assert out.out == ""                       # no phase line, no result
    assert "no TPU" in out.err

"""chip_smoke.py's phases at CPU size, and the compile-cache helper.

On the chip ``python chip_smoke.py`` drives the paper models and granite-8b
serving at their published widths; here the same phase functions run in
Pallas interpret mode on small batches and ``reduced_config`` widths, so a
broken phase is found before a chip call is spent on it.
"""
from __future__ import annotations

import importlib.util
import os
import pathlib
import sys

import jax
import pytest

from repro.configs import reduced_config

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve their module
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules["chip_smoke"]


def test_paper_phase_at_cpu_size(chip_smoke, capsys):
    assert chip_smoke.paper_phase(seed=0, batches=(1, 3), svi_samples=4)
    out = capsys.readouterr().out
    assert out.count("[paper]") == 4 and "ok=False" not in out


def test_serving_phase_at_reduced_size(chip_smoke, capsys):
    assert chip_smoke.serving_phase(reduced_config("granite-8b"), seed=0)
    out = capsys.readouterr().out
    assert "[serve]" in out and "fallbacks=0" in out
    assert "escalations=0 " not in out


def test_main_refuses_without_a_tpu(chip_smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""  # no result line, no phase line


def test_compile_cache_helper(monkeypatch):
    from repro.launch import compile_cache

    key = "jax_compilation_cache_dir"
    before = getattr(jax.config, key)
    # Importing the helper changed nothing.
    assert before == os.environ.get(compile_cache.ENV)
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert getattr(jax.config, key) == before  # left to JAX
        monkeypatch.delenv(compile_cache.ENV)
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert getattr(jax.config, key) == path
        assert compile_cache.enable_compile_cache() == path  # fixed path
    finally:
        jax.config.update(key, before)

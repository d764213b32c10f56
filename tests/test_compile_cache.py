"""The entry points' persistent compilation cache: where it goes, and that
importing the helper turns nothing on."""
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_default_is_fixed_dir_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable_compile_cache() == str(REPO / ".jax_cache")
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_env_var_leaves_the_choice_to_jax(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == before


def test_env_var_dir_is_where_entries_go(tmp_path):
    code = ("import jax; "
            "from repro.launch.compile_cache import enable_compile_cache; "
            "print(enable_compile_cache()); "
            "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()")
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path), "JAX_PLATFORMS": "cpu",
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "PYTHONPATH": str(REPO / "src")}
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=120, check=True)
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing was cached in the env dir"


def test_import_enables_nothing():
    code = ("import jax, repro.launch.serve, repro.launch.train, "
            "repro.launch.coserve; "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], text=True,
                         capture_output=True, timeout=120, check=True,
                         env={"JAX_PLATFORMS": "cpu",
                              "PYTHONPATH": str(REPO / "src")})
    assert out.stdout.strip().splitlines()[-1] == "None"

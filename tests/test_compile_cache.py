"""Where the entry points put JAX's persistent compilation cache."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_default_is_fixed_path_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    # the same path on every call: no pid, time or temp name in it
    assert compile_cache.enable_compile_cache() == got


def test_environment_wins_and_is_left_alone(monkeypatch, restore_cache_dir,
                                            tmp_path):
    # JAX reads the variable at import; emulate that, then check the
    # helper neither overrides nor moves it
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_import_changes_nothing():
    """Importing the helper or the CLI module sets no cache directory;
    only an entry point's call does."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(CHECKOUT / "src"), env.get("PYTHONPATH", "")])
    code = ("import jax, repro.launch.compile_cache, repro.launch.train; "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "None"

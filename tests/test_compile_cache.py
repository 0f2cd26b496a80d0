"""The persistent compilation cache helper of the entry points."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import DEFAULT_DIR, ENV_VAR, use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Give the test the process-wide cache setting back as it found it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path / "cache"))
    assert use_compile_cache() == str(tmp_path / "cache")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cache")


def test_default_is_a_fixed_ignored_path_in_the_checkout(cache_config,
                                                        monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert use_compile_cache() == str(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    assert DEFAULT_DIR.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert f"{DEFAULT_DIR.name}/" in ignored


def test_importing_the_entry_points_sets_nothing():
    code = ("import jax\n"
            "import repro, repro.launch.rl_train, "
            "repro.launch.serve_policy, repro.launch.compile_cache\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "None"

"""The persistent compilation cache lands where JAX_COMPILATION_CACHE_DIR
says, and otherwise at one fixed directory of the checkout. Each case runs
in a fresh process: the cache directory is process-wide JAX state."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import pathlib, sys
from repro.launch import compile_cache
compile_cache.DEFAULT_CACHE_DIR = pathlib.Path(sys.argv[1])
print(compile_cache.enable_compile_cache())
import jax, jax.numpy as jnp
jax.jit(lambda x: x * 3.25 + 1)(jnp.ones(3)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_entries_written_only_to_the_chosen_dir(tmp_path, env_set):
    default, chosen = tmp_path / "default", tmp_path / "env"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(chosen)
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(default)],
                         env=env, cwd=ROOT, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    want, other = (chosen, default) if env_set else (default, chosen)
    assert out.stdout.strip().splitlines()[-1] == str(want)
    assert any(p.name.startswith("jit_") for p in want.iterdir())
    assert not other.exists()


def test_default_dir_is_fixed_gitignored_and_import_is_inert():
    before = jax.config.jax_compilation_cache_dir
    from repro.launch import compile_cache
    assert jax.config.jax_compilation_cache_dir == before
    assert compile_cache.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored

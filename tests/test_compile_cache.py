"""Where the persistent compile cache lands (``repro.launch.compile_cache``).

Each case compiles one small program in a fresh CPU process, with the
cache's size and time thresholds at zero so every compile is written,
and looks at which directories received entries.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]

# argv[1] stands in for <checkout>/.jax_cache, so the test never writes
# into the checkout.
_PROGRAM = """
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.launch import compile_cache

compile_cache.DEFAULT_DIR = Path(sys.argv[1])
print(compile_cache.enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def _entries(d: Path) -> list:
    return sorted(p.name for p in d.rglob("*") if p.is_file()) \
        if d.exists() else []


def _run(tmp_path: Path, env_dir: Path | None) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               HOME=str(tmp_path / "home"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _PROGRAM, str(tmp_path / "default")],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_default_dir_is_the_ignored_checkout_cache():
    assert compile_cache.DEFAULT_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env-set", "env-unset"])
def test_cache_entries_land_only_in_the_chosen_dir(tmp_path, from_env):
    env_dir = tmp_path / "from_env" if from_env else None
    chosen = env_dir if from_env else tmp_path / "default"
    other = tmp_path / "default" if from_env else tmp_path / "from_env"
    assert _run(tmp_path, env_dir) == str(chosen)
    assert _entries(chosen), "the compile wrote no cache entry"
    assert not _entries(other)
    assert not _entries(tmp_path / "home")  # nothing under ~/.cache

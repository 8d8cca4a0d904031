"""The entry points' persistent compilation cache: JAX_COMPILATION_CACHE_DIR
when it is set, else a fixed .jax_cache/ in the checkout that git ignores."""
import os
import subprocess
import sys
from pathlib import Path

from repro.launch.compile_cache import CHECKOUT

PROBE = ("import jax; from repro.launch.compile_cache import "
         "enable_compile_cache as e; p = e(); "
         "print(p); print(jax.config.jax_compilation_cache_dir)")


def _probe(env):
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, check=True,
                         capture_output=True, text=True).stdout.split()
    return out[0], out[1]


def _env(**kw):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(CHECKOUT / "src")
    env.update(kw)
    return env


def test_env_dir_wins_and_is_not_overridden(tmp_path):
    d = str(tmp_path / "cc")
    used, cfg = _probe(_env(JAX_COMPILATION_CACHE_DIR=d))
    assert used == d and cfg == d


def test_default_is_fixed_dir_in_checkout_and_ignored():
    used, cfg = _probe(_env())
    assert used == cfg == str(CHECKOUT / ".jax_cache")
    assert ".jax_cache/" in (CHECKOUT / ".gitignore").read_text().split()
    assert Path(used).parent == CHECKOUT

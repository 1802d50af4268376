"""The persistent compilation cache lands where ``enable_compile_cache``
says: ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
Each case runs in a fresh process, since JAX's cache configuration is
process-wide."""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

from repro.compile_cache import DEFAULT_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
path = enable_compile_cache()
if {compile}:
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.ones(8)).block_until_ready()
print(json.dumps({{"path": path,
                  "config": jax.config.jax_compilation_cache_dir,
                  "min_s": jax.config.jax_persistent_cache_min_compile_time_secs}}))
"""


def _probe(env_dir, compile_: bool):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")])
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _PROBE.format(compile=compile_)],
        capture_output=True, text=True, env=env, check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_cache_lands_in_env_dir(tmp_path):
    got = _probe(tmp_path, compile_=True)
    assert got["path"] == got["config"] == str(tmp_path)
    # a sub-second program is cached too
    assert got["min_s"] == 0.0
    assert any(f.endswith("-cache") for f in os.listdir(tmp_path))


def test_cache_defaults_to_checkout_dir():
    got = _probe(None, compile_=False)
    assert got["path"] == got["config"] == str(DEFAULT_DIR)
    assert DEFAULT_DIR == pathlib.Path(ROOT) / ".jax_cache"

"""Persistent compile cache: placed from outside, fixed otherwise, warm
across processes.

The contract (`util/compile_cache.py`): with `JAX_COMPILATION_CACHE_DIR`
set no code sets `jax_compilation_cache_dir` — before or after a mesh
change; unset, the directory is `<checkout>/.jax_cache` for every
process and every mesh size; and a SECOND process over the same
directory reports misses == 0 (everything loads from disk) and at
least one hit. Plus the start-up pin that rides the same children: a
server asked for a larger mesh than the host has does not start.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ENV8 = dict(JAX_PLATFORMS="cpu",
             XLA_FLAGS="--xla_force_host_platform_device_count=8")

_DIR_PROG = r"""
import json
import jax
import tidb_tpu
from tidb_tpu import devplane
before = jax.config.jax_compilation_cache_dir
devplane.enable_mesh(8)
during = jax.config.jax_compilation_cache_dir
devplane.disable_mesh()
print("DIRS " + json.dumps([before, during,
                            jax.config.jax_compilation_cache_dir]))
"""

_WARM_PROG = r"""
import json
import jax
import jax.numpy as jnp
from tidb_tpu.util import compile_cache

@jax.jit
def f(x):
    return (jnp.sin(x) @ jnp.cos(x.T)).sum()

f(jnp.arange(2048.0, dtype=jnp.float32).reshape(32, 64))
print("STATS " + json.dumps(compile_cache.stats()))
"""


def _child(prog: str, tag: str, **env):
    full = dict(os.environ, **_ENV8)
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env)
    proc = subprocess.run([sys.executable, "-c", prog],
                          capture_output=True, text=True, timeout=240,
                          env=full, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for line in proc.stdout.splitlines():
        if line.startswith(tag + " "):
            return json.loads(line[len(tag) + 1:])
    raise AssertionError(f"no {tag} line in: {proc.stdout!r}")


def test_env_var_places_the_cache_across_mesh_changes(tmp_path):
    want = str(tmp_path)
    assert _child(_DIR_PROG, "DIRS",
                  JAX_COMPILATION_CACHE_DIR=want) == [want] * 3


def test_default_dir_is_the_checkout_for_every_mesh_size():
    assert _child(_DIR_PROG, "DIRS") == \
        [os.path.join(REPO, ".jax_cache")] * 3


def test_warm_run_compile_cache_misses_zero(tmp_path):
    """Process 1 compiles into the directory; process 2 must load
    everything — misses == 0. The probe's program compiles in ms, so
    the children lower jax's 1s persistence floor through jax's own
    environment variable."""
    env = dict(JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    cold = _child(_WARM_PROG, "STATS", **env)
    assert cold["dir"] == str(tmp_path)
    assert cold["misses"] >= 1            # really compiled
    assert cold["entries"] >= 1           # really persisted
    warm = _child(_WARM_PROG, "STATS", **env)
    assert warm["dir"] == str(tmp_path)
    assert warm["misses"] == 0, warm
    assert warm["hits"] >= 1, warm


def test_server_refuses_a_mesh_larger_than_the_host():
    proc = subprocess.run(
        [sys.executable, "-m", "tidb_tpu", "--mesh", "16", "-P", "0",
         "--no-status"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, **_ENV8), cwd=REPO)
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert "16 devices requested but only 8 visible" in proc.stderr

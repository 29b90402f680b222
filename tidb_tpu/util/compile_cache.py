"""Persistent XLA compilation cache: enablement + hit/miss accounting.

First-compile of the big fused query programs costs tens of seconds;
the persistent cache turns every later process's compiles into disk
loads. One place owns the wiring so the package import, the server
entrypoint, chip_smoke.py and benchmark/run.py all agree on the directory and
the hit/miss counters (via jax.monitoring events).

Directory: where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it
itself and this module sets no directory in code — whoever runs the
process places the cache. Otherwise ``<checkout>/.jax_cache``, fixed:
the path never moves with the mesh size or the platform, because jax's
own cache key already covers platform, topology and compile options,
and a directory that moves never hits.
"""

from __future__ import annotations

import os
import threading

import jax
from jax import monitoring

__all__ = ["enable", "default_dir", "stats", "counters", "reset_counters"]

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

_lock = threading.Lock()
_counts = {"hits": 0, "misses": 0}
_listener_installed = False


def default_dir() -> str:
    """``<checkout>/.jax_cache`` (git-ignored): the directory used when
    nothing outside the process placed the cache."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def _on_event(event: str, **_kw) -> None:
    hit = event == "/jax/compilation_cache/cache_hits"
    if not hit and event != "/jax/compilation_cache/cache_misses":
        return
    with _lock:
        _counts["hits" if hit else "misses"] += 1
    # lazy import: metrics pulls in the package's runtime modules
    from tidb_tpu import metrics
    if hit:
        metrics.counter(metrics.COMPILE_CACHE_HITS)
    else:
        metrics.counter(metrics.COMPILE_CACHE_MISSES)


def enable() -> str:
    """Start counting persistent-cache hits/misses and, unless
    ``JAX_COMPILATION_CACHE_DIR`` placed the cache from outside, point
    jax at ``default_dir()``. -> the directory in force. Must run
    before the first compile; idempotent."""
    global _listener_installed
    if not os.environ.get(_ENV_DIR):
        jax.config.update("jax_compilation_cache_dir", default_dir())
    with _lock:
        if not _listener_installed:
            monitoring.register_event_listener(_on_event)
            _listener_installed = True
    return jax.config.jax_compilation_cache_dir


def stats() -> dict:
    """Snapshot for the server log / chip_smoke.py: the directory in
    force, how many compiled executables it currently holds (None
    before the first one is written), and this process's hit/miss
    counts."""
    cur = jax.config.jax_compilation_cache_dir
    try:
        entries = sum(1 for f in os.listdir(cur)
                      if not f.startswith("."))
    except FileNotFoundError:
        entries = None
    with _lock:
        return {"dir": cur, "entries": entries,
                "hits": _counts["hits"], "misses": _counts["misses"]}


def counters() -> dict:
    """Just the hit/miss counts — no directory listing. The profiler
    diffs these around a kernel's compile dispatch to attribute it
    hit|miss|cached; stats() costs a listdir and stays off hot paths."""
    with _lock:
        return dict(_counts)


def reset_counters() -> None:
    with _lock:
        _counts["hits"] = 0
        _counts["misses"] = 0

"""Where JAX's persistent compilation cache lives, and what it did.

One rule for every process of the system — driver, replicas, trainers,
tests, bench scripts: the cache directory is ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (JAX reads that variable itself, and then no
code here sets another), else ``<checkout>/.jax_cache``. The path is part
of the cache key's lookup, so it is never a temp name, a pid or a time: a
directory that moves never hits. The node supervisor exports the same
value to every worker it spawns (``core/node.py::_spawn_env``), so
replicas and trainers share one cache.

``compile_watch()`` counts the compiles of THIS process through
``jax.monitoring`` — how many programs were built, how long that took and
how many came out of the persistent cache — which is what the decode
engine's ``stats()`` and ``chip_smoke.py`` report.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Dict, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """The compile-cache directory: the environment's, untouched, when it
    names one; ``<checkout>/.jax_cache`` (git-ignored) otherwise."""
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


def configure() -> str:
    """Point this process (and, through the inherited environment, every
    process it starts) at :func:`cache_dir`. A no-op when the environment
    already names a directory. Call before the first compile."""
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        os.environ[ENV_VAR] = path
        if "jax" in sys.modules:  # jax read the (unset) variable at import
            sys.modules["jax"].config.update("jax_compilation_cache_dir",
                                             path)
    return path


class CompileWatch:
    """Process-wide compile counters fed by ``jax.monitoring`` events.
    ``compiles`` counts every backend compile request (a persistent-cache
    hit is still a request: steady state should show neither);
    ``compile_s`` is the time spent in them, retrieval included."""

    def __init__(self):
        from jax import monitoring

        self._lock = threading.Lock()
        self._compiles = 0
        self._compile_s = 0.0
        self._cache_hits = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self._cache_hits += 1

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self._compiles += 1
                self._compile_s += duration

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"compiles": self._compiles,
                    "compile_s": round(self._compile_s, 3),
                    "cache_hits": self._cache_hits}


_watch: Optional[CompileWatch] = None
_watch_lock = threading.Lock()


def compile_watch() -> CompileWatch:
    """The process's one :class:`CompileWatch` (jax.monitoring listeners
    are process-global and cannot be removed, so there is exactly one);
    counters start at the first call."""
    global _watch
    with _watch_lock:
        if _watch is None:
            _watch = CompileWatch()
        return _watch

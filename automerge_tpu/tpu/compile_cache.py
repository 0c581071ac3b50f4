"""The one place the persistent XLA compile cache is configured.

Every process that drives the device calls :func:`enable_compile_cache`
before its first compile: ``chip_smoke.py``, the ``bench.py`` children and
the mesh workers (``parallel/workers.py``). The directory is part of the
cache key, so it never moves:

- ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
  the variable itself; nothing else is set in code);
- otherwise ``<repo>/.jax_cache`` (listed in ``.gitignore``).

Every program is cached, however fast it compiled: a chip call starts cold,
and the second process of the same call should find all of them. The CPU
backend caches nothing: XLA:CPU entries are tied to the compiling host's
features, and loading them warns of SIGILL.
:func:`compile_cache_stats` reports the directory and the hits and misses
JAX's monitoring events counted in this process.
"""
from __future__ import annotations

import os

import jax
from jax import monitoring

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_HITS = "/jax/compilation_cache/cache_hits"
_counts = {_REQUESTS: 0, _HITS: 0}
_listening = False


def _count(event: str, **_kwargs) -> None:
    if event in _counts:
        _counts[event] += 1


def enable_compile_cache() -> str | None:
    """Turns the persistent cache on for this process (idempotent) and
    returns its directory; None on the CPU backend."""
    global _listening
    if jax.default_backend() == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _listening:
        monitoring.register_event_listener(_count)
        _listening = True
    return path


def compile_cache_stats() -> dict:
    """{dir, hits, misses} for this process: of the compiles that consulted
    the cache, how many it answered and how many it did not."""
    hits = _counts[_HITS]
    return {
        "dir": jax.config.jax_compilation_cache_dir,
        "hits": hits,
        "misses": _counts[_REQUESTS] - hits,
    }

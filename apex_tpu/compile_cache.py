"""Where apex_tpu keeps what it generates at run time, and the one place
that turns on JAX's persistent compilation cache.

Everything the package builds for itself — XLA executables, the native
host runtime's ``.so`` — lives under one
directory inside the checkout, :func:`cache_root` (git-ignored).  The
path is fixed on purpose: it is part of the compilation cache's key, so
a directory made from a temporary name, a pid or the time never hits.

The compile cache follows one rule, shared by ``chip_smoke.py``,
``bench.py`` and ``tests/conftest.py``: where ``JAX_COMPILATION_CACHE_DIR``
is set JAX already reads it and nothing is set in code; otherwise the
cache goes to ``<cache_root>/xla``.
"""
from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def cache_root() -> str:
    """``<checkout>/.apex_tpu_cache`` — next to the ``apex_tpu`` package
    directory."""
    pkg = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(pkg), ".apex_tpu_cache")


class CacheCounts:
    """Persistent-cache lookups seen since :func:`enable`: ``hits`` were
    read back from ``directory``, ``misses`` were compiled (and written
    there when they took long enough to be worth storing)."""

    def __init__(self, directory: str):
        self.directory = directory
        self.hits = 0
        self.misses = 0

    def _on_event(self, event: str, **_kw) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1


def enable() -> CacheCounts:
    """Turn the persistent compilation cache on (call before the first
    compile) and return the counter of its hits and misses."""
    import jax

    directory = os.environ.get(_ENV)
    if not directory:
        directory = os.path.join(cache_root(), "xla")
        jax.config.update("jax_compilation_cache_dir", directory)
    counts = CacheCounts(directory)
    jax.monitoring.register_event_listener(counts._on_event)
    return counts

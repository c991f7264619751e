"""JAX's persistent compilation cache at one fixed path in the checkout.

The path is part of the cache key, so it never moves; both sides of a
comparison run in checkouts of their own and share nothing. Every program is
written to it (no minimum compile time or entry size), so a second run in a
checkout compiles nothing. A ``JAX_COMPILATION_CACHE_DIR`` in the
environment is overridden: it may name a path outside the checkout.
"""
from __future__ import annotations

from .cell import ROOT

CACHE_DIR = ROOT / ".jax_cache"


def enable() -> str:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileCounter:
    """Programs JAX asked the backend for, and how many of them came from the
    persistent cache, read from jax.monitoring. JAX times every request,
    cache reads included, so compiles = requests - cache hits."""

    def __init__(self):
        import jax
        self.requests = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.requests += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, int]:
        return self.requests, self.cache_hits

    def report(self, who: str, since: tuple[int, int]) -> None:
        """Print the window's programs, cache hits and compiles (stderr)."""
        import sys
        req, hits = self.requests - since[0], self.cache_hits - since[1]
        print(f"[{who}] window: {req} programs lowered, {hits} from the "
              f"persistent cache, {req - hits} compiled", file=sys.stderr, flush=True)

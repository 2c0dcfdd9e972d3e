"""Backend-compile seconds, program count and persistent-cache hits from
JAX's own monitoring events (copied from ``chip_smoke.py`` ``CompileMeter``).
One listener per process: JAX has no way to unregister one."""

from __future__ import annotations

import threading


class CompileMeter:
    _instance = None

    def __init__(self) -> None:
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls) -> "CompileMeter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration
                self.programs += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        """(backend-compile seconds, programs, persistent-cache hits)."""
        with self._lock:
            return self.seconds, self.programs, self.cache_hits

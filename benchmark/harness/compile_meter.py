"""Counts every XLA compile in the process through jax's own monitoring
events, whichever module launched it (a copy of chip_smoke.py's
CompileMeter).  The harness snapshots it at the start and the end of the
measured window: the difference must be zero."""

import threading


class CompileMeter:
    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()

    def install(self) -> "CompileMeter":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_secs(self, event: str, secs: float, **kw) -> None:
        if event == self._BACKEND:
            with self._lock:
                self.compiles += 1
                self.seconds += secs

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.compiles, self.seconds, self.cache_hits

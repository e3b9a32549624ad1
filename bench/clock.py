"""The benchmark's own wall clock and compile counter, kept here so that
the yardstick cannot move with the program."""

from __future__ import annotations

import time


class WallClock:
    """Real time since construction, with the engine's clock interface:
    ``advance`` is a no-op (the work itself takes the time) and
    ``advance_to`` sleeps until the next arrival."""

    def __init__(self):
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def advance(self, dt: float):
        pass

    def advance_to(self, t: float):
        while (left := t - self.now()) > 0:
            time.sleep(min(0.001, left))


class CompileCounter:
    """XLA programs compiled, and programs loaded from the persistent
    compilation cache, in this process (JAX's monitoring events)."""

    def __init__(self):
        self.compiled = 0
        self.loaded = 0

    def install(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    @property
    def programs(self) -> int:
        return self.compiled + self.loaded

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

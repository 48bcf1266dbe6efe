"""JAX's compile events while a context is open (after ``chip_smoke.py``'s
``CompileLog``): seconds and counts by program name, and hits in the
persistent compilation cache."""

from __future__ import annotations

import collections

# JAX's duration event for one backend compile (a persistent-cache hit
# included: the event spans the cache lookup), and its count of those hits
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self):
        self.seconds = collections.Counter()
        self.count = collections.Counter()
        self.hits = 0

    def _on_duration(self, event, duration, fun_name="", **_):
        if event == COMPILE_EVENT:
            self.seconds[fun_name] += duration
            self.count[fun_name] += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def compiles(self) -> int:
        return sum(self.count.values())

    def __str__(self):
        names = ", ".join(f"{name} x{self.count[name]} {sec:.3f} s"
                          for name, sec in self.seconds.most_common())
        return (f"{self.compiles()} compile(s), "
                f"{sum(self.seconds.values()):.3f} s "
                f"[{names or 'nothing compiled'}], "
                f"{self.hits} persistent-cache hit(s)")

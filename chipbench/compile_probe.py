"""Backend compiles and persistent-cache loads, counted from JAX's own
monitoring events (a copy of the probe in ``chip_smoke.py``, kept here so
that a change to the program cannot change what it counts).

JAX times a persistent-cache load as a backend compile too; a compile
whose thread saw a cache hit between its start and its end was a load,
every other one is fresh and is counted under its function's name."""
from __future__ import annotations

import threading
from collections import Counter

COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


class CompileProbe:
    """One set of listeners per process: JAX has no way to remove one,
    so the first instance is the only one."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            import jax.monitoring as mon

            self = super().__new__(cls)
            self.compiles = self.hits = self.writes = 0
            self.fresh = Counter()
            self._lock = threading.Lock()
            self._local = threading.local()
            mon.register_scalar_listener(self._start)
            mon.register_event_duration_secs_listener(self._end)
            mon.register_event_listener(self._event)
            cls._instance = self
        return cls._instance

    def _start(self, event, value, **kw):
        if event == COMPILE:
            self._local.hit = False

    def _end(self, event, duration_secs, fun_name="?", **kw):
        if event == COMPILE:
            with self._lock:
                self.compiles += 1
                if not getattr(self._local, "hit", False):
                    self.fresh[fun_name] += 1

    def _event(self, event, **kw):
        with self._lock:
            if event == HIT:
                self.hits += 1
                self._local.hit = True
            elif event == MISS:
                self.writes += 1

    def snapshot(self):
        """(compiles, cache hits, cache writes, fresh compiles by name)."""
        with self._lock:
            return self.compiles, self.hits, self.writes, Counter(self.fresh)

    def since(self, snap):
        """The same four, counted after ``snap``."""
        now = self.snapshot()
        return (*(a - b for a, b in zip(now[:3], snap[:3])),
                now[3] - snap[3])

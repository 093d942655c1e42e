"""Count XLA executables built while a run is being measured.

Copied from the repository's ``chip_smoke.py`` so that what the benchmark
counts cannot move with the program. An executable is counted when it is
compiled or loaded from JAX's persistent cache; cache hits and misses are
counted apart, and compile walls are summed by the jitted function's name.
"""

from __future__ import annotations

import collections
import time


class CompileWatch:
    """Counts XLA executables built and persistent-cache hits and misses."""

    _BUILD = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.built = 0
        self.hits = 0
        self.misses = 0
        self.names = collections.Counter()
        self.seconds = collections.Counter()
        self.events = []        # (name, start, end) on time.perf_counter
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kwargs):
        if event == self._BUILD:
            name = kwargs.get("fun_name", "?")
            self.built += 1
            self.names[name] += 1
            self.seconds[name] += duration
            end = time.perf_counter()
            self.events.append((name, end - duration, end))

    def _on_event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.built, self.hits, self.misses, self.names.copy(),
                self.seconds.copy(), len(self.events))

    def spans(self, snap) -> dict:
        """Per function name, the wall from the first build's start to the
        last build's end since ``snap`` — builds run side by side."""
        out = {}
        for name, start, end in self.events[snap[5]:]:
            a, b = out.get(name, (start, end))
            out[name] = (min(a, start), max(b, end))
        return out

    def since(self, snap) -> dict:
        built, hits, misses, names, seconds, _ = snap
        return {"executables": self.built - built,
                "cache_hits": self.hits - hits,
                "cache_misses": self.misses - misses,
                "by_name": dict(self.names - names),
                "seconds_by_name": {k: round(v, 3) for k, v in
                                    (self.seconds - seconds).items()}}

"""Set-up, timed by phase, and the count of compiles that the
persistent cache did not serve."""
from __future__ import annotations

import os
import time

PHASES = ('init', 'build', 'weights', 'load', 'warm')


def process_start_wall(fallback):
    """Wall-clock time at which this process was created (Linux: field
    22 of /proc/self/stat, in clock ticks since boot), else `fallback`
    (the time run.py was first executed)."""
    try:
        with open('/proc/self/stat') as f:
            ticks = int(f.read().rsplit(')', 1)[1].split()[19])
        with open('/proc/uptime') as f:
            uptime = float(f.read().split()[0])
        started = time.time() - uptime + ticks / os.sysconf('SC_CLK_TCK')
        # 10 ms ticks: never later than the first line of run.py
        return min(started, fallback)
    except (OSError, ValueError, IndexError):
        return fallback


class Phases:
    """mark(name) closes the phase that ran since the last mark. The
    phases sum to total(), which is setup_s."""

    def __init__(self, wall_start):
        self._start = time.perf_counter() - (time.time() - wall_start)
        self._last = self._start
        self.seconds = {p: 0.0 for p in PHASES}
        self.detail = []                 # (what, seconds) inside phases
        self._noted = self._start

    def mark(self, name):
        now = time.perf_counter()
        self.seconds[name] += now - self._last
        self._last = self._noted = now

    def skip(self):
        """Leave what ran since the last mark out of every phase and of
        the total (the profiler's warm-up in a traced run, which reports
        the phases but never setup_s)."""
        now = time.perf_counter()
        self._start += now - self._last
        self._last = self._noted = now

    def note(self, what):
        """A finer split, printed for whoever steadies set-up next; no
        metric reads it."""
        now = time.perf_counter()
        self.detail.append((what, now - self._noted))
        self._noted = now

    def total(self):
        return self._last - self._start


class CompileMisses:
    """Backend compiles asked of the persistent cache minus those it
    served (jax/_src/compiler.py records both events; `cache_misses`
    counts writes only and is not this). Beside the count, the seconds
    JAX itself spent tracing, lowering, in the backend's compile call
    (which a cache hit answers) and reading the cache: printed with the
    set-up's detail, so that a set-up that changes level says where."""

    _ASKED = '/jax/compilation_cache/compile_requests_use_cache'
    _HIT = '/jax/compilation_cache/cache_hits'

    def __init__(self):
        import jax.monitoring
        self.asked = 0
        self.hits = 0
        self.seconds = {}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == self._ASKED:
            self.asked += 1
        elif event == self._HIT:
            self.hits += 1

    def _on_duration(self, event, duration, **_):
        if event.startswith(('/jax/core/compile/',
                             '/jax/compilation_cache/cache_retrieval')):
            what = 'jax_' + event.rsplit('/', 1)[1].replace('_duration', '')
            self.seconds[what] = self.seconds.get(what, 0.0) + duration

    @property
    def misses(self):
        return self.asked - self.hits

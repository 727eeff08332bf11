"""Seconds and counts of the programs JAX builds in this process.

One ``jax.monitoring`` listener, registered once per process by
:func:`install`, folds JAX's build events into :data:`BUILDS`:

  * ``trace_s``: Python tracing to a jaxpr and lowering to an MLIR module
    (``/jax/core/compile/jaxpr_trace_duration`` and
    ``jaxpr_to_mlir_module_duration``), which no compilation cache saves;
  * ``compile_s``: XLA and Mosaic compilation, or loading the executable
    from the persistent compilation cache
    (``/jax/core/compile/backend_compile_duration``, which spans both);
  * ``programs``: executables built, one per backend-compile event;
  * ``cache_hits``: of those, the ones loaded from the persistent cache
    (``/jax/compilation_cache/cache_hits``).

A jitted function traced while its caller is traced (a kernel's jitted
wrapper inside the program) fires an event inside the caller's event, so
the seconds are the length of the union of the events' intervals: nothing
is counted twice.

The listener only fires while a program is built, so it costs nothing on
a call that finds its program compiled. A caller that wants the builds of
one call reads :attr:`Builds.totals` before and after it: one attribute
read each, equal (the same tuple) when nothing was built.
"""
from __future__ import annotations

import threading
from typing import List, NamedTuple, Tuple

TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class BuildTotals(NamedTuple):
    programs: int = 0
    cache_hits: int = 0
    trace_s: float = 0.0
    compile_s: float = 0.0

    def __sub__(self, other: "BuildTotals") -> "BuildTotals":
        return BuildTotals(*(a - b for a, b in zip(self, other)))


class _Union:
    """Running length of the union of ``[start, end]`` intervals that
    arrive roughly in order of their end (nested events end first)."""

    __slots__ = ("total", "_ivs")

    def __init__(self):
        self.total = 0.0
        self._ivs: List[Tuple[float, float]] = []   # disjoint, by end

    def add(self, a: float, b: float):
        ivs, later = self._ivs, []
        while ivs and ivs[-1][1] > a:
            x, y = ivs.pop()
            if x > b:                    # wholly after [a, b]: keep it
                later.append((x, y))
                continue
            self.total -= y - x
            a, b = min(a, x), max(b, y)
        ivs.append((a, b))
        self.total += b - a
        ivs.extend(reversed(later))


class Builds:
    """Process-wide totals of JAX's program builds (see the module
    docstring); ``totals`` is replaced, never mutated, on every event."""

    def __init__(self):
        self.totals = BuildTotals()
        self._lock = threading.Lock()
        self._trace = _Union()
        self._compile = _Union()
        self._installed = False

    def _span(self, event: str, start: float, end: float, **_kw):
        if event in TRACE_EVENTS:
            kind = self._trace
        elif event == COMPILE_EVENT:
            kind = self._compile
        else:
            return
        with self._lock:
            kind.add(start, end)
            t = self.totals
            self.totals = BuildTotals(
                t.programs + (event == COMPILE_EVENT), t.cache_hits,
                self._trace.total, self._compile.total)

    def _event(self, event: str, **_kw):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.totals = self.totals._replace(
                    cache_hits=self.totals.cache_hits + 1)

    def install(self):
        """Register the listeners with ``jax.monitoring``, once."""
        with self._lock:
            if self._installed:
                return
            import jax.monitoring as mon
            mon.register_event_time_span_listener(self._span)
            mon.register_event_listener(self._event)
            self._installed = True


BUILDS = Builds()


def install() -> Builds:
    BUILDS.install()
    return BUILDS

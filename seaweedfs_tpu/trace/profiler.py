"""The tracer's boundaries on the profiler's clock (docs/TRACING.md).

Spans live on the host's clock and in this process's ring; a device
trace (`jax.profiler.start_trace`) lives on the profiler's. The two
helpers here put the same boundary on both:

  * `annotation(name)` — a `jax.profiler.TraceAnnotation` around a pool
    stage: a TraceMe event on the calling thread's `/host:CPU` line,
    free while no profiler runs. It touches JAX only where this process
    already imported it (daemons that need no JAX never do, PR 21) and
    is a no-op while the tracer is off (`tracer.enabled()`, its kill
    switch).
  * `Phases` — the back-to-back phases of one operation on ONE thread:
    each is a child span of the thread's current span and an annotation
    of the same name, and their seconds partition the operation's wall
    exactly, because neighbours share one clock sample. The seconds are
    kept whether tracing is on or not: report lines read them.
"""

from __future__ import annotations

import contextlib
import sys
import time

from seaweedfs_tpu.trace import tracer

_NO_ANNOTATION = contextlib.nullcontext()


def annotation(name: str):
    """Context manager naming the enclosed stretch of the calling
    thread in a running profiler trace; a shared no-op otherwise."""
    if not tracer.enabled():
        return _NO_ANNOTATION
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)  # None while jax is mid-import
    if profiler is None:
        return _NO_ANNOTATION
    return profiler.TraceAnnotation(name)


class Phases:
    """Serial phases of one operation, opened and closed on one thread.

    `to(name)` ends the open phase and starts `name` on the same
    `perf_counter` sample, so `seconds` sums to `close()`'s sample minus
    the first one with no residue. `at` hands in a sample already taken
    (by this thread a moment ago, or by a pool thread whose event ends
    the phase): the booked seconds and the span use it; the annotation,
    which cannot be backdated, ends when the call is made. A sample
    older than the open phase's own start counts as that start."""

    def __init__(self, first: str, t0: float | None = None):
        self.seconds: dict[str, float] = {}
        self._open(first, time.perf_counter() if t0 is None else t0)

    def _open(self, name: str, at: float) -> None:
        self._name, self._t0 = name, at
        self.seconds.setdefault(name, 0.0)
        self._span = tracer.span(name, t0=at)
        self._span.__enter__()
        self._annotation = annotation(name)
        self._annotation.__enter__()

    def _shut(self, at: float | None) -> float:
        at = time.perf_counter() if at is None else max(at, self._t0)
        self._annotation.__exit__(None, None, None)
        self._span.__exit__(None, None, None)
        if self._span:
            self._span.duration = at - self._t0  # the shared sample, not exit's own
        self.seconds[self._name] += at - self._t0
        return at

    def to(self, name: str, at: float | None = None) -> float:
        at = self._shut(at)
        self._open(name, at)
        return at

    def close(self, at: float | None = None) -> float:
        """End the last phase; returns the closing sample."""
        return self._shut(at)

"""Opt-in wall-clock profiling spans on the profiler trace's clock.

Disabled by default: :func:`span` and :func:`phases` check one
module-level bool and return a shared no-op object, and :func:`count`
does nothing, so the instrumented hot paths (the evaluator, the fused
``sojourn_eval`` ops, the workload-cache tiers in
:mod:`repro.core.policies`) pay well under a microsecond per span when
profiling is off.  Enable with :func:`enable` or the ``REPRO_PROFILE=1``
environment variable.

An enabled span records into the process-wide default
:class:`~repro.obs.metrics.MetricsRegistry` (or the one passed in):

* ``prof.<name>.seconds`` — inclusive wall time, one observation per
  call (histogram);
* ``prof.<name>.calls`` — the call count (counter);
* ``prof.<name>.self_s`` — the inclusive time minus that of the spans
  opened directly inside it on the same thread (histogram).  Nested
  spans (a cache lookup whose computation looks up other cached
  values) double-count in the inclusive sums; the self times do not.

:func:`count` adds to the counter ``prof.<name>`` when profiling is on.

An enabled span also opens ``jax.profiler.TraceAnnotation("prof.<name>")``,
so under a running profiler trace the span lies on the host plane's
``python`` line, on the same clock as the device's ``XLA Ops``.  Spans are timed
inside their annotation: the annotation covers the recorded time.

The ``sojourn_eval`` ops convert their answers to NumPy inside their
spans, which waits for the device, so an op span is end-to-end wall
time of the call.
"""

from __future__ import annotations

import os
import threading
import time

from repro.obs import metrics

__all__ = ["enabled", "enable", "count", "span", "phases"]

_ENABLED = os.environ.get("REPRO_PROFILE", "").strip().lower() not in (
    "", "0", "false", "off",
)
_local = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, imported on first use


def enabled() -> bool:
    return _ENABLED


def enable(on: bool = True) -> None:
    """Turn profiling spans on/off process-wide (overrides the env var)."""
    global _ENABLED
    _ENABLED = bool(on)


class _Off:
    """The shared no-op span and phase sequence of disabled profiling."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def to(self, phase: str) -> None:
        pass


_OFF = _Off()


def _open_spans() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("name", "registry", "annotation", "child", "t0")

    def __init__(self, name: str, registry: metrics.MetricsRegistry | None):
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        self.name = name
        self.registry = registry or metrics.get_registry()
        self.annotation = _annotation(f"prof.{name}")
        self.child = 0.0

    def __enter__(self):
        self.annotation.__enter__()
        _open_spans().append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self.t0
        stack = _open_spans()
        stack.pop()
        if stack:
            stack[-1].child += seconds
        self.annotation.__exit__(*exc)
        reg, name = self.registry, self.name
        reg.histogram(f"prof.{name}.seconds").observe(seconds)
        reg.histogram(f"prof.{name}.self_s").observe(seconds - self.child)
        reg.counter(f"prof.{name}.calls").inc()
        return None


def count(name: str, n: int = 1, registry: metrics.MetricsRegistry | None = None) -> None:
    """Add ``n`` to the counter ``prof.<name>`` when profiling is on."""
    if _ENABLED:
        (registry or metrics.get_registry()).counter(f"prof.{name}").inc(n)


def span(name: str, registry: metrics.MetricsRegistry | None = None):
    """Time a block as ``prof.<name>`` when profiling is on (see module doc)."""
    if not _ENABLED:
        return _OFF
    return _Span(name, registry)


class _Phases:
    __slots__ = ("prefix", "first", "phase", "open")

    def __init__(self, prefix, first):
        self.prefix, self.first = prefix, first
        self.phase = self.open = None

    def __enter__(self):
        self.to(self.first)
        return self

    def __exit__(self, *exc):
        if self.open is not None:
            self.open.__exit__(*exc)
            self.phase = self.open = None
        return None

    def to(self, phase: str) -> None:
        """Close the open phase and open ``phase``; nothing when it is open."""
        if phase == self.phase:
            return
        if self.open is not None:
            self.open.__exit__(None, None, None)
        self.phase = phase
        self.open = _Span(f"{self.prefix}.{phase}", None).__enter__()


def phases(prefix: str, first: str):
    """Consecutive spans ``<prefix>.<phase>`` that tile a block.

    The block starts in phase ``first``; ``.to(phase)`` ends the open
    phase and starts the next, so the phases leave no time of the block
    uncovered.  A no-op object when profiling is off.
    """
    if not _ENABLED:
        return _OFF
    return _Phases(prefix, first)

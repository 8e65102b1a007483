"""Lightweight per-query tracing shared by every serving mode.

Each stage of the read path wraps itself in a named *span*
(``with tracer.span("dispatch"): ...``); the measured wall time lands
in a bounded ring buffer per span name, and ``snapshot()`` reduces the
rings to rolling p50/p95/p99 histograms.  The snapshot is what
``stats()["latency"]`` returns everywhere — ``SuffixTable``,
``QueryScheduler``, ``TabletRouter`` — and what the ``metrics.jsonl``
feed exports, so one schema describes in-process, scheduled, and
multi-process serving alike (docs/observability.md).

Every span is also a profiler annotation named ``<component>.<name>``
(``table.dispatch``, ``sched.window``): while a ``jax.profiler`` trace
is active it lands on the host plane, on the same clock as the
device's operations, so an idle gap on the chip can be put down to the
span the host was in.  The annotation is made only while a profiler
session records (one ``TraceMe.is_enabled()`` check a span), so outside
a trace a span costs what it did before it had one.

Design constraints (the read path is the hot path):

* Recording a span is two ``time.monotonic_ns()`` calls, one float
  subtraction, one ring-slot store, and one integer increment — no
  locks, no allocation beyond the span object itself.  Slot writes and
  the index bump are each atomic under the GIL; a concurrent recorder
  can at worst overwrite one sample or under-count by one, which a
  rolling histogram tolerates by construction.
* ``Tracer(enabled=False)`` (or ``tracer.enabled = False`` at runtime)
  swaps ``span()`` for a shared no-op context, so a disabled tracer
  costs one attribute check per call site and emits nothing, neither
  to its histograms nor to the profiler.
* Buffers are preallocated numpy float64 rings (default 2048 samples
  per span) — memory is bounded no matter how long the process serves.

Span names are dotted free-form; the conventional set produced by the
repo's own call sites is documented in docs/observability.md.
"""

from __future__ import annotations

import time

import numpy as np
# the class behind jax.profiler.TraceAnnotation; taken from jaxlib so
# the numpy-only plane processes need not import jax for it
from jaxlib._profiler import TraceMe as _Annotation

__all__ = ["SpanHistogram", "Tracer"]

_DEFAULT_RING = 2048
# quantiles exported by every histogram snapshot, in feed order
_QUANTILES = (("p50_ms", 0.50), ("p95_ms", 0.95), ("p99_ms", 0.99))


class SpanHistogram:
    """Bounded ring of span durations (ms) reduced to rolling quantiles.

    The ring keeps the most recent ``size`` samples; ``total`` counts
    every sample ever recorded (so feeds can rate-convert) and
    ``sum_ms`` accumulates total time for mean/utilisation math.
    """

    __slots__ = ("_buf", "_size", "_n", "_sum_ms")

    def __init__(self, size: int = _DEFAULT_RING):
        if size <= 0:
            raise ValueError(f"ring size must be positive, got {size}")
        self._size = int(size)
        self._buf = np.zeros(self._size, np.float64)
        self._n = 0
        self._sum_ms = 0.0

    def record(self, ms: float) -> None:
        # lock-free: a slot store + int bump, each atomic under the GIL
        self._buf[self._n % self._size] = ms
        self._n += 1
        self._sum_ms += ms

    @property
    def count(self) -> int:
        return self._n

    def quantiles(self) -> dict:
        """Rolling p50/p95/p99 over the ring window (the sorted sample
        at index ``int(frac * n)``, clamped)."""
        n = min(self._n, self._size)
        if n == 0:
            out = {name: 0.0 for name, _ in _QUANTILES}
            out.update(n=0, total=0, sum_ms=0.0)
            return out
        data = np.sort(self._buf[:n])
        out = {name: round(float(data[min(n - 1, int(frac * n))]), 4)
               for name, frac in _QUANTILES}
        out.update(n=int(n), total=int(self._n),
                   sum_ms=round(float(self._sum_ms), 4))
        return out


class _Span:
    """One timed region.  Deliberately not ``@contextmanager`` — a tiny
    __enter__/__exit__ class is several times cheaper per call."""

    __slots__ = ("_tracer", "_name", "_t0")

    def __init__(self, tracer: "Tracer", name: str):
        self._tracer = tracer
        self._name = name

    def __enter__(self) -> "_Span":
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._tracer.record(self._name,
                            (time.monotonic_ns() - self._t0) / 1e6)
        return False


class _AnnotatedSpan(_Span):
    """A timed region inside its profiler annotation, for a span opened
    while a profiler session records."""

    __slots__ = ("_ann",)

    def __init__(self, tracer: "Tracer", name: str, label: str):
        super().__init__(tracer, name)
        self._ann = _Annotation(label)

    def __enter__(self) -> "_AnnotatedSpan":
        self._ann.__enter__()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        super().__exit__(exc_type, exc, tb)
        self._ann.__exit__(exc_type, exc, tb)
        return False


class _NullSpan:
    """Shared no-op context for disabled tracers."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Named span histograms for one component (``table``, ``sched``,
    ``router``, ``worker``).  ``span(name)`` times a region and marks
    it in the profiler as ``<component>.<name>``; ``record(name, ms)``
    logs an externally measured duration (e.g. a queue wait computed
    from a stored submit timestamp) to the histogram only, since a past
    interval cannot be put into a trace; ``snapshot()`` is the
    ``stats()["latency"]`` payload, keyed by the bare span names."""

    def __init__(self, component: str, *,
                 ring_size: int = _DEFAULT_RING, enabled: bool = True):
        self.component = str(component)
        self.enabled = bool(enabled)
        self._ring_size = int(ring_size)
        self._spans: dict[str, SpanHistogram] = {}
        self._labels: dict[str, str] = {}     # span name -> profiler name

    def span(self, name: str):
        if not self.enabled:
            return _NULL_SPAN
        if not _Annotation.is_enabled():
            return _Span(self, name)
        label = self._labels.get(name)
        if label is None:
            label = self._labels.setdefault(name,
                                            f"{self.component}.{name}")
        return _AnnotatedSpan(self, name, label)

    def record(self, name: str, ms: float) -> None:
        if not self.enabled:
            return
        hist = self._spans.get(name)
        if hist is None:
            # setdefault: two racing first-recorders converge on one ring
            hist = self._spans.setdefault(name,
                                          SpanHistogram(self._ring_size))
        hist.record(float(ms))

    def headline(self, name: str) -> dict:
        """``{p50_ms, p95_ms, p99_ms, n}`` of span ``name`` (zeros before
        its first sample): the top-level latency scalars of a plane
        feed row."""
        hist = self._spans.get(name) or SpanHistogram(1)
        q = hist.quantiles()
        return {k: q[k] for k in ("p50_ms", "p95_ms", "p99_ms", "n")}

    def snapshot(self) -> dict:
        """``{span_name: {p50_ms, p95_ms, p99_ms, n, total, sum_ms}}``,
        name-sorted so feed rows diff cleanly."""
        return {name: self._spans[name].quantiles()
                for name in sorted(self._spans)}

    def reset(self) -> None:
        self._spans.clear()

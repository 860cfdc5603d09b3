"""Span tracing + device profiling hooks.

Reference parity: the reference instruments everything with the `tracing`
crate's spans (SURVEY.md §5.1 — imports at engine.rs:6, tcp.rs:24,
store.rs:15) and leaves profiling to external tools. Here:

- :class:`Tracer` — a process-local span aggregator with the same
  pull-based-stats shape as the rest of the framework (§5.5): per-span
  count / total / max wall time, read via :meth:`Tracer.report`. Disabled
  by default; when disabled a span costs one attribute check. Set
  ``RABIA_TRACE=1`` in the environment to enable it process-wide (or
  flip ``tracer.enabled`` at runtime). The span aggregates fold into the
  observability registry's exposition — the engine attaches this tracer
  to its :class:`~rabia_tpu.obs.MetricsRegistry`, so ``/metrics``
  carries ``rabia_span_seconds{span=...}`` summaries and there is ONE
  ``report()`` shape, not two (docs/OBSERVABILITY.md).
- :func:`span` — ``with span("engine.tick.drain"): ...`` context manager
  against the module singleton.
- :func:`device_annotation` — the device lane's one span helper:
  ``with device_annotation("rabia.cycle.pack"): ...`` emits a
  ``jax.profiler.TraceAnnotation`` (a TraceMe event in the profiler's own
  ``.xplane.pb``, on the device trace's clock) and, when the tracer is
  enabled, records the same interval into the :class:`Tracer` under the
  same name. No-op when jax is absent and the tracer is off.

Span naming taxonomy (dotted, coarse→fine):
  engine.tick.{drain,open,kernel,apply,timeouts}
  engine.kernel.{start,route,step,outbox}
  wire.{serialize,deserialize}
  sm.apply
  rabia.cycle.{pack,book,wait,settle}, rabia.cycle.pack.{parse,alloc,gather},
    rabia.cycle.settle.download
  rabia.fetch.values (on a readback worker's thread, not the window's)
  rabia.devkv.{decide_apply,lookup_window,mixed_apply,read_probe}
    (reserved for the dispatch spans: the benchmark selects them by prefix)
  rabia.dispatch.{place,call}, rabia.jit.first_call
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field


@dataclass
class SpanStats:
    count: int = 0
    total_s: float = 0.0
    max_s: float = 0.0

    def add(self, dt: float) -> None:
        self.count += 1
        self.total_s += dt
        if dt > self.max_s:
            self.max_s = dt


@dataclass
class Tracer:
    """Process-local span aggregator (enable with ``tracer.enabled = True``)."""

    enabled: bool = False
    spans: dict = field(default_factory=dict)
    # the device lane's readback workers record spans too
    _lock: threading.Lock = field(
        default_factory=threading.Lock, repr=False, compare=False
    )

    def record(self, name: str, dt: float) -> None:
        with self._lock:
            st = self.spans.get(name)
            if st is None:
                st = self.spans[name] = SpanStats()
            st.add(dt)

    def report(self) -> dict:
        """{span: {count, total_s, avg_us, max_us}} sorted by total time."""
        out = {}
        with self._lock:
            items = list(self.spans.items())
        for name, st in sorted(items, key=lambda kv: -kv[1].total_s):
            out[name] = {
                "count": st.count,
                "total_s": round(st.total_s, 4),
                "avg_us": round(st.total_s / st.count * 1e6, 1) if st.count else 0,
                "max_us": round(st.max_s * 1e6, 1),
            }
        return out

    def reset(self) -> None:
        self.spans.clear()


tracer = Tracer()
# the documented enable path: RABIA_TRACE=1 turns span aggregation on for
# the whole process (tests/benches may still flip tracer.enabled directly)
if os.environ.get("RABIA_TRACE") == "1":
    tracer.enabled = True


class _NoopSpan:
    """Shared no-op context: a disabled span costs one attribute check,
    one call and no allocation."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return None

    def __exit__(self, *exc):
        tracer.record(self.name, time.perf_counter() - self.t0)
        return False


def span(name: str):
    """``with span("engine.tick.drain"): ...`` — aggregated when the
    tracer is enabled, near-free otherwise."""
    if not tracer.enabled:
        return _NOOP
    return _Span(name)


_annotation_cls = False  # unresolved; None when jax is absent


def _resolve_annotation_cls():
    global _annotation_cls
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        TraceAnnotation = None
    _annotation_cls = TraceAnnotation
    return TraceAnnotation


class _DeviceSpan:
    """A profiler annotation that also feeds the :class:`Tracer`. Like the
    annotation, whose event begins when it is made, it counts from its
    construction."""

    __slots__ = ("name", "ann", "t0")

    def __init__(self, name: str, ann):
        self.name = name
        self.ann = ann
        self.t0 = time.perf_counter()

    def __enter__(self):
        return self.ann

    def __exit__(self, *exc):
        if self.ann is not None:
            self.ann.__exit__(*exc)
        tracer.record(self.name, time.perf_counter() - self.t0)
        return False


def device_annotation(name: str, **stats):
    """``with device_annotation("rabia.cycle.pack", bytes=n): ...`` — the
    device lane's span: a TraceMe event in the JAX profiler's trace (the
    ``stats`` become the event's arguments in the trace viewer), and an
    aggregate under the same name in the :class:`Tracer` when that is
    enabled. With no profiler session and the tracer off it reads no
    clock and allocates only the annotation. The span begins where this
    is called (a ``TraceAnnotation``'s event starts at its construction,
    not at ``__enter__``): call it in the ``with`` statement itself.
    ``with device_annotation(name) as span:`` binds the profiler's
    annotation, or None where there is none: an argument known only
    inside the span is added by ``span.set_metadata(path=...)``."""
    cls = _annotation_cls
    if cls is False:
        cls = _resolve_annotation_cls()
    ann = cls(name, **stats) if cls is not None else None
    if tracer.enabled:
        return _DeviceSpan(name, ann)
    return ann if ann is not None else _NOOP

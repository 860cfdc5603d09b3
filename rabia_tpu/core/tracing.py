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
  ``.xplane.pb``, on the device trace's clock) while a profiler session
  is listening and, when the tracer is enabled, records the same interval
  into the :class:`Tracer` under the same name. With neither it costs one
  check (``TraceAnnotation.is_enabled()``) and returns the shared no-op:
  no annotation is built and no clock read, which is what lets
  ``submit_block`` (64 calls a window) and the readback workers carry
  spans.

Span naming taxonomy (dotted, coarse→fine):
  engine.tick.{drain,open,kernel,apply,timeouts}
  engine.kernel.{start,route,step,outbox}
  wire.{serialize,deserialize}
  sm.apply
  rabia.submit.{validate,route} (the client's call: two a ``submit_block``)
  rabia.cycle.{kinds,pack,book,wait,settle}, rabia.cycle.pack.{parse,alloc,gather},
    rabia.cycle.book.{versions,segment,handoff},
    rabia.cycle.settle.{download,blocks}
  rabia.fetch.{flags,meta,values} (on a readback worker's thread, not the
    window's)
  rabia.devkv.{decide_apply,lookup_window,mixed_apply,read_probe}
    (reserved for the dispatch spans: the benchmark selects them by prefix)
  rabia.dispatch.{place,call}, rabia.jit.first_call
  rabia.window.w<W>, rabia.governor.resize, rabia.ladder.build (the pipe)
  rabia.setup.{native,engine} (set-up: read under ``RABIA_TRACE=1``)
  rabia.sync.{dump,rebuild}
"""

from __future__ import annotations

import os
import sys
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
# TraceAnnotation.is_enabled, resolved with the class: is a profiler
# session listening? None where jax is absent or has no such method
# (every span is then built, as before there was a gate)
_listening = None


def _resolve_annotation_cls():
    global _annotation_cls, _listening
    if "jax" not in sys.modules:
        # nothing has imported jax yet, so no profiler session can be
        # listening, and a span is not what imports it (the native
        # loaders' spans are entered by processes that never touch jax):
        # unresolved until somebody has
        return None
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        TraceAnnotation = None
    _listening = getattr(TraceAnnotation, "is_enabled", None)
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
    enabled. With no profiler session listening and the tracer off it is
    one check and the shared no-op: no annotation is built, no clock
    read. The span begins where this is called (a ``TraceAnnotation``'s
    event starts at its construction, not at ``__enter__``): call it in
    the ``with`` statement itself.
    ``with device_annotation(name) as span:`` binds the profiler's
    annotation, or None where there is none (nothing listens, or jax is
    absent): an argument known only inside the span is added by
    ``span.set_metadata(path=...)`` where ``span`` is not None."""
    cls = _annotation_cls
    if cls is False:
        cls = _resolve_annotation_cls()
    if cls is None or (_listening is not None and not _listening()):
        return _DeviceSpan(name, None) if tracer.enabled else _NOOP
    ann = cls(name, **stats)
    return _DeviceSpan(name, ann) if tracer.enabled else ann

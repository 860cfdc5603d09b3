"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

One reducer. It finds the measured window by the benchmark's own span
(``chipbench.window``), clips everything to it, and returns:

- ``window_s``: the length of that span;
- ``busy_s``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane), averaged
  over the devices;
- ``spans``: for every benchmark (``chipbench.*``) and program
  (``rabia.*``) span on the window's thread, its durations in seconds;
- ``device_ops``: the ten device operations with most time, by name;
- ``idle_gaps``: the device's idle time (first device) split by what the
  window's thread was doing: inside a program dispatch span, inside
  ``run_cycle`` but outside a dispatch (pack, resolve, settle), inside the
  benchmark's submit or poll span, or outside every span.

Read with nothing but JAX (``jax.profiler.ProfileData``).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

import numpy as np

WINDOW_SPAN = "chipbench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
_SPAN_PREFIXES = ("chipbench.", "rabia.")


class TraceError(RuntimeError):
    """The trace lacks what the reducer needs (no window span, no device)."""


def find_xplane(trace_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _merge(starts: np.ndarray, ends: np.ndarray):
    """Union of intervals -> (starts, ends) of the merged, sorted runs."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.nonzero(new)[0]
    return s[first], np.maximum.reduceat(e, first)


def _busy_before(t: np.ndarray, ms: np.ndarray, me: np.ndarray) -> np.ndarray:
    """Total merged-busy time before each instant of ``t``."""
    if len(ms) == 0:
        return np.zeros(len(t))
    done = np.concatenate(([0.0], np.cumsum(me - ms)))
    i = np.searchsorted(ms, t, side="right") - 1
    inside = np.clip(t - ms[np.maximum(i, 0)], 0.0, (me - ms)[np.maximum(i, 0)])
    return np.where(i >= 0, done[np.maximum(i, 0)] + inside, 0.0)


def op_name(event_name: str) -> str:
    """The device plane names an op by its whole HLO line
    (``%while.55 = (s32[]...) while(...)``); keep the op's own name."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:64]


def _label(stack: list) -> str:
    if not stack:
        return "outside_spans"
    for name in reversed(stack):
        if name.startswith("rabia."):
            return "dispatch:" + name
    top = stack[-1]
    if top == "chipbench.run_cycle":
        return "run_cycle_outside_dispatch"
    return top


def _segments(spans: list, w0: float, w1: float) -> list:
    """Flatten properly nested spans into [(start, end, label)] covering the
    window, the innermost span naming each instant."""
    edges = []
    for name, a, b in spans:
        edges.append((a, 1, name))
        edges.append((b, 0, name))
    edges.sort(key=lambda x: (x[0], x[1]))
    out, stack, at = [], [], w0
    for t, opening, name in edges:
        t = min(max(t, w0), w1)
        if t > at:
            out.append((at, t, _label(stack)))
            at = t
        if opening:
            stack.append(name)
        elif name in stack:
            stack.remove(name)
    if w1 > at:
        out.append((at, w1, _label(stack)))
    return out


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window = None
    main_spans: list = []
    devices = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    ev = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                    devices.append((plane.name, ev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                named = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(_SPAN_PREFIXES)
                ]
                mine = [x for x in named if x[0] == WINDOW_SPAN]
                if mine:
                    window = mine[0]
                    main_spans = [x for x in named if x[0] != WINDOW_SPAN]
    if window is None:
        raise TraceError(f"no {WINDOW_SPAN} span in {path}")
    if not devices:
        raise TraceError(f"no {_OPS_LINE!r} line of a /device:TPU plane in {path}")
    w0, w1 = window[1], window[2]

    spans = defaultdict(list)
    inside = []
    for name, a, b in main_spans:
        if b <= w0 or a >= w1:
            continue
        inside.append((name, a, b))
        spans[name].append((min(b, w1) - max(a, w0)) * 1e-9)

    busy = []
    op_time = defaultdict(float)
    merged0 = None
    for _, ev in sorted(devices):
        if not ev:
            busy.append(0.0)
            continue
        s = np.array([x[1] for x in ev], np.float64)
        e = s + np.array([x[2] for x in ev], np.float64)
        s, e = np.clip(s, w0, w1), np.clip(e, w0, w1)
        ms, me = _merge(s, e)
        busy.append(float((me - ms).sum()) * 1e-9)
        if merged0 is None:
            merged0 = (ms, me)
            for (name, _, _), d in zip(ev, (e - s).tolist()):
                if d > 0:
                    op_time[op_name(name)] += d * 1e-9
    if merged0 is None:
        merged0 = (np.zeros(0), np.zeros(0))

    segs = _segments(inside, w0, w1)
    a = np.array([x[0] for x in segs], np.float64)
    b = np.array([x[1] for x in segs], np.float64)
    idle = (b - a) - (_busy_before(b, *merged0) - _busy_before(a, *merged0))
    gaps = defaultdict(float)
    for (_, _, label), d in zip(segs, idle.tolist()):
        gaps[label] += d * 1e-9

    def top(d: dict) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": float(np.mean(busy)),
        "n_devices": len(busy),
        "spans": dict(spans),
        "device_ops": top(op_time),
        "idle_gaps": top(gaps),
    }


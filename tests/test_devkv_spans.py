"""The device lane's spans and counters (core/tracing.device_annotation).

Every window of the device lane enters the same spans once, whatever its
kind: ``rabia.cycle.{kinds,pack,book,wait,settle}`` beside the dispatch span
``rabia.devkv.<program>``, with ``rabia.cycle.pack.*``,
``rabia.cycle.book.*``, ``rabia.cycle.settle.*`` and ``rabia.dispatch.*`` /
``rabia.jit.first_call`` nested inside; ``submit_block`` enters
``rabia.submit.{validate,route}`` once a call, and the readback workers
``rabia.fetch.{flags,meta,values}`` on their own threads. With the
tracer on (``RABIA_TRACE=1``) they aggregate into ``Tracer.report()`` and
``rabia_span_seconds``; with it off and no profiler session listening a
span is the shared no-op: no annotation is built, nothing is recorded and
nothing the lane computes changes. Runs on the virtual CPU mesh.
"""

from __future__ import annotations

import contextlib
import struct

import numpy as np
import pytest

from rabia_tpu.apps.kvstore import encode_set_bin
from rabia_tpu.apps.vector_kv import VectorShardedKV
from rabia_tpu.core.blocks import build_block
from rabia_tpu.core.tracing import tracer
from rabia_tpu.parallel import MeshEngine, make_mesh

N_SHARDS = 8
WINDOW = 4
N_WINDOWS = 3

PACK_PARTS = tuple(
    f"rabia.cycle.pack.{p}" for p in ("parse", "alloc", "gather")
)
CALLS = ("rabia.dispatch.call", "rabia.jit.first_call")
BOOK_PARTS = tuple(
    f"rabia.cycle.book.{p}" for p in ("versions", "segment", "handoff")
)
SUBMIT_PARTS = ("rabia.submit.validate", "rabia.submit.route")
PROGRAM = {
    "set": "rabia.devkv.decide_apply",
    "get": "rabia.devkv.lookup_window",
    "mixed": "rabia.devkv.mixed_apply",
}


@pytest.fixture
def traced():
    was = tracer.enabled
    tracer.reset()
    tracer.enabled = True
    try:
        yield tracer
    finally:
        tracer.enabled = was
        tracer.reset()


def _engine(**kw) -> MeshEngine:
    return MeshEngine(
        lambda: VectorShardedKV(N_SHARDS, capacity=1 << 12),
        n_shards=N_SHARDS,
        n_replicas=3,
        mesh=make_mesh(),
        window=kw.pop("window", WINDOW),
        device_store=True,
        **kw,
    )


def _get(key: str) -> bytes:
    return bytes([2]) + struct.pack("<H", len(key)) + key.encode()


def _block(kind: str, rng) -> object:
    """One full-width block: all SETs, all GETs, or a SET/GET interleaving
    per shard (which only the mixed program takes)."""
    cmds = []
    for s in range(N_SHARDS):
        key = f"k{s}_{int(rng.integers(0, 3))}"
        is_set = kind == "set" or (kind == "mixed" and s % 2 == 0)
        if is_set:
            cmds.append([encode_set_bin(key, "v" * int(rng.integers(1, 20)))])
        else:
            cmds.append([_get(key)])
    return build_block(list(range(N_SHARDS)), cmds)


def _window(eng: MeshEngine, kind: str, rng) -> list:
    """Submit one whole window of ``kind`` and dispatch it."""
    futs = [eng.submit_block(_block(kind, rng)) for _ in range(WINDOW)]
    before = eng.cycles
    eng.run_cycle()
    assert eng.cycles == before + 1
    return futs


def _total(name: str) -> float:
    st = tracer.spans.get(name)
    return st.total_s if st is not None else 0.0


def _count(report: dict, *names: str) -> int:
    return sum(report[n]["count"] for n in names if n in report)


class TestSpansPerWindow:
    def test_each_kind_enters_every_span_once_a_window(self, traced):
        eng = _engine()
        rng = np.random.default_rng(7)
        first_calls = 0
        for kind in ("set", "get", "mixed"):
            traced.reset()
            for _ in range(N_WINDOWS):
                _window(eng, kind, rng)
            eng.flush()
            assert eng.device_lane_active
            rep = traced.report()
            # a GET window derives no version and retains no segment
            book_parts = BOOK_PARTS[2:] if kind == "get" else BOOK_PARTS
            for name in ("rabia.cycle.kinds", "rabia.cycle.pack",
                         "rabia.cycle.book", *book_parts, "rabia.cycle.settle",
                         "rabia.cycle.settle.blocks", PROGRAM[kind],
                         *PACK_PARTS, "rabia.dispatch.place"):
                assert rep[name]["count"] == N_WINDOWS, (kind, name)
            assert not (set(BOOK_PARTS) - set(book_parts)) & set(rep), kind
            for name in SUBMIT_PARTS:  # once a submit_block call
                assert rep[name]["count"] == N_WINDOWS * WINDOW, (kind, name)
            # the workers' fetches: flags in every window, meta where it reads
            assert rep["rabia.fetch.flags"]["count"] == N_WINDOWS, kind
            fetched_meta = _count(rep, "rabia.fetch.meta")
            assert fetched_meta == (0 if kind == "set" else N_WINDOWS), kind
            assert _count(rep, *CALLS) == N_WINDOWS, kind
            # a SET window waits for its flags, the others for meta too
            waits = rep["rabia.cycle.wait"]["count"]
            assert waits == N_WINDOWS * (1 if kind == "set" else 2), kind
            # rabia.devkv.* is reserved: this kind's dispatch span, no other
            devkv = [n for n in rep if n.startswith("rabia.devkv.")]
            assert devkv == [PROGRAM[kind]], kind
            # nested spans lie inside their parents
            assert sum(map(_total, PACK_PARTS)) <= _total("rabia.cycle.pack")
            assert sum(map(_total, BOOK_PARTS)) <= _total("rabia.cycle.book")
            assert _total("rabia.cycle.settle.blocks") <= _total("rabia.cycle.settle")
            inside = _total("rabia.dispatch.place") + sum(map(_total, CALLS))
            assert inside <= _total(PROGRAM[kind])
            first_calls += _count(rep, "rabia.jit.first_call")
        # one first call per distinct program signature, ever
        snap = eng.metrics.snapshot()
        assert first_calls == len(eng._dev._fused_cache)
        assert snap["rabia_devkv_program_builds_total"] == first_calls
        eng.close()

    def test_read_probe_window_enters_the_same_spans(self, traced):
        eng = _engine(device_read_lane=True)
        rng = np.random.default_rng(11)
        _window(eng, "set", rng)
        eng.flush()
        traced.reset()
        for _ in range(WINDOW):
            eng.submit_block(_block("get", rng))
        eng.flush()
        rep = traced.report()
        for name in ("rabia.cycle.pack", "rabia.devkv.read_probe",
                     "rabia.cycle.book", "rabia.cycle.book.handoff",
                     "rabia.cycle.settle", "rabia.cycle.settle.blocks",
                     "rabia.dispatch.place", "rabia.fetch.meta"):
            assert rep[name]["count"] == 1, name
        assert rep["rabia.cycle.wait"]["count"] == 1  # meta; no flags
        # nothing was decided: no flags to fetch, no kind to choose a lane by
        assert "rabia.fetch.flags" not in rep and "rabia.cycle.kinds" not in rep
        for name in SUBMIT_PARTS:
            assert rep[name]["count"] == WINDOW, name
        eng.close()

    @pytest.mark.parametrize("kind", ["get", "mixed"])
    def test_value_download_is_named_inside_the_settle(self, traced, kind):
        """Reads of versions whose host segment was evicted make the settle
        download the window's value planes: the one blocking transfer in
        it, under a span of its own."""
        eng = _engine()
        eng._dev_vseg_cap = 1  # evict every segment but the newest
        rng = np.random.default_rng(23)
        for _ in range(2):
            _window(eng, "set", rng)
        eng.flush()
        traced.reset()
        _window(eng, kind, rng)
        eng.flush()
        rep = traced.report()
        assert rep["rabia.cycle.settle.download"]["count"] == 1
        assert _total("rabia.cycle.settle.download") <= _total("rabia.cycle.settle")
        eng.close()

    def test_prometheus_carries_every_span(self, traced):
        eng = _engine()
        for kind in ("set", "get", "mixed"):
            for _ in range(2):  # the same window twice: a build, then a call
                _window(eng, kind, np.random.default_rng(5))
        eng.flush()
        text = eng.metrics.render_prometheus()
        for name in (*SUBMIT_PARTS, "rabia.cycle.kinds", "rabia.cycle.pack",
                     *PACK_PARTS, "rabia.cycle.book", *BOOK_PARTS,
                     "rabia.cycle.wait", "rabia.cycle.settle",
                     "rabia.cycle.settle.blocks", "rabia.fetch.flags",
                     "rabia.fetch.meta", "rabia.setup.engine",
                     *PROGRAM.values(), "rabia.dispatch.place", *CALLS):
            assert f'rabia_span_seconds_count{{span="{name}"}}' in text, name
        assert "rabia_devkv_upload_bytes_total" in text
        assert "rabia_devkv_program_builds_total" in text
        eng.close()


def _profiled(tmp_path, work) -> list:
    """Run ``work()`` under a profiler session; ``(name, thread, start, end,
    stats)`` of every ``rabia.*`` event of its trace, a thread being one
    line of a host plane (the threads share a name)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    return [
        (e.name, (plane.name, i), e.start_ns,
         e.start_ns + e.duration_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        for i, line in enumerate(plane.lines)
        for e in line.events
        if e.name.startswith("rabia.")
    ]


def test_profiler_events_tile_the_dispatch(tmp_path):
    """The same spans as TraceMe events in a profiler trace (an annotation's
    event begins where it is made): inside one ``rabia.devkv.*`` event the
    place comes first and the call after it, without overlap, and the pack's
    parts follow one another inside the pack."""
    eng = _engine()
    rng = np.random.default_rng(19)
    _window(eng, "mixed", rng)  # the program's first call, untraced

    def work():
        _window(eng, "mixed", np.random.default_rng(19))
        eng.flush()

    events = {}
    for name, _, start, end, _ in _profiled(tmp_path, work):
        events.setdefault(name, []).append((start, end))
    eng.close()
    assert all("#" not in name for name in events)  # the stats stay apart
    one = {name: v[0] for name, v in events.items() if len(v) == 1}
    order = ["rabia.cycle.pack", "rabia.devkv.mixed_apply", "rabia.cycle.book"]
    for a, b in zip(order, order[1:]):
        assert one[a][1] <= one[b][0], (a, b)
    parts = [one[p] for p in PACK_PARTS]
    assert one["rabia.cycle.pack"][0] <= parts[0][0]
    assert parts[-1][1] <= one["rabia.cycle.pack"][1]
    for a, b in zip(parts, parts[1:]):
        assert a[1] <= b[0]
    devkv, place, call = (
        one[n] for n in ("rabia.devkv.mixed_apply", "rabia.dispatch.place",
                         "rabia.dispatch.call")
    )
    assert devkv[0] <= place[0] <= place[1] <= call[0] <= call[1] <= devkv[1]
    assert len(events["rabia.cycle.settle"]) == 2  # both windows settled


def test_profiler_children_lie_inside_parents_and_fetches_on_workers(tmp_path):
    """The spans of ISSUE 40 as TraceMe events: on the window's thread each
    child lies inside an event of its parent and the children of one parent
    follow one another without overlap (the benchmark's ``_segments`` pairs
    them by name), ``rabia.submit.*`` lie outside every window span, and
    ``rabia.fetch.flags`` / ``.meta`` lie on a thread other than
    ``rabia.cycle.book``'s, with the bytes they fetched."""
    eng = _engine()
    rng = np.random.default_rng(29)
    _window(eng, "mixed", rng)  # the program's first call, untraced
    eng.flush()

    def work():
        for _ in range(2):
            _window(eng, "mixed", rng)
        eng.flush()

    events = _profiled(tmp_path, work)
    eng.close()
    by_name: dict = {}
    for name, thread, a, b, stats in events:
        by_name.setdefault(name, []).append((thread, a, b, stats))
    (main,) = {t for t, *_ in by_name["rabia.cycle.book"]}
    children = {
        "rabia.cycle.book": BOOK_PARTS,
        "rabia.cycle.settle": ("rabia.cycle.settle.blocks",),
    }
    for parent, parts in children.items():
        assert len(by_name[parent]) == 2
        for _, p0, p1, _ in by_name[parent]:
            inside = sorted(
                (a, b) for part in parts for t, a, b, _ in by_name[part]
                if p0 <= a and b <= p1
            )
            assert len(inside) == len(parts), parent  # one of each, nested
            assert all(x[1] <= y[0] for x, y in zip(inside, inside[1:]))
        for part in parts:
            assert {t for t, *_ in by_name[part]} == {main}, part
            assert len(by_name[part]) == 2, part
    assert [st["blocks"] for *_, st in by_name["rabia.cycle.kinds"]] == [WINDOW] * 2
    assert [st["blocks"] for *_, st in by_name["rabia.cycle.settle.blocks"]] == [WINDOW] * 2
    assert [st["fetches"] for *_, st in by_name["rabia.cycle.book.handoff"]] == [2, 2]
    assert all(st["bytes"] > 0 for *_, st in by_name["rabia.cycle.book.segment"])
    # the client's call: two spans a call, one after the other, inside no
    # span of a window
    windows = [(a, b) for n in ("rabia.cycle.kinds", "rabia.cycle.pack",
                                "rabia.cycle.book", "rabia.cycle.settle")
               for _, a, b, _ in by_name[n]]
    val, route = (sorted(by_name[n], key=lambda e: e[1]) for n in SUBMIT_PARTS)
    assert len(val) == len(route) == 2 * WINDOW
    for (tv, a0, a1, sv), (tr, b0, b1, sr) in zip(val, route):
        assert tv == tr == main and a1 <= b0
        assert sv == {"n": N_SHARDS} and sr == {"lane": "full"}
        assert not any(w0 < b1 and a0 < w1 for w0, w1 in windows)
    # the workers' side of the handoff
    for name in ("rabia.fetch.flags", "rabia.fetch.meta"):
        fetches = by_name[name]
        assert len(fetches) == 2, name
        assert all(t != main for t, *_ in fetches), name
        assert all(st["bytes"] > 0 for *_, st in fetches), name
    # a fetch begins after the handoff that submitted it began
    first_handoff = min(a for _, a, _, _ in by_name["rabia.cycle.book.handoff"])
    assert all(a >= first_handoff for _, a, _, _ in by_name["rabia.fetch.flags"])


def test_submit_spans_say_which_lane_took_the_block(tmp_path, traced):
    """``rabia.submit.validate`` and ``rabia.submit.route`` once a call on
    each of the three lanes (``Tracer.report()`` counts), ``lane=`` on the
    route's event; a block the validation refuses enters no route."""
    from rabia_tpu.core.errors import ValidationError

    eng = _engine(device_read_lane=True)
    rng = np.random.default_rng(37)
    _window(eng, "set", rng)
    eng.flush()
    traced.reset()
    narrow = build_block([0, 3], [[encode_set_bin("a", "b")]] * 2)

    def work():
        eng.submit_block(_block("set", rng))  # full width, nothing queued
        eng.submit_block(_block("get", rng))  # skimmed onto the read lane
        eng.submit_block(narrow)  # two shards: one queue entry each
        eng.submit_block(_block("set", rng))  # full width behind a queue
        with pytest.raises(ValidationError):
            eng.submit_block(build_block([0, N_SHARDS], [[b"x"]] * 2))

    events = _profiled(tmp_path, work)
    rep = traced.report()
    assert rep["rabia.submit.validate"]["count"] == 5
    assert rep["rabia.submit.route"]["count"] == 4
    lanes = [st["lane"] for name, _, a, _, st in sorted(events, key=lambda e: e[2])
             if name == "rabia.submit.route"]
    assert lanes == ["full", "read", "queue", "queue"]
    sizes = [st["n"] for name, *_, st in events if name == "rabia.submit.validate"]
    assert sorted(sizes) == [2, 2, N_SHARDS, N_SHARDS, N_SHARDS]
    eng.flush()
    eng.close()


def test_native_load_is_a_setup_span_once_a_process(traced, monkeypatch):
    """``rabia.setup.native`` wraps a library's build and load, which run
    once a process: a later call returns the cached library, span-free."""
    from rabia_tpu.native import build

    if build.load_hostkernel() is None:
        pytest.skip("native host kernel unavailable")
    monkeypatch.setattr(build, "_HK_CACHED", None)  # as in a new process
    traced.reset()
    assert build.load_hostkernel() is not None
    assert build.load_hostkernel() is not None
    assert traced.report()["rabia.setup.native"]["count"] == 1


class TestDisabledSpanIsOneCheck:
    """With no profiler session listening and the tracer off
    ``device_annotation`` returns the shared no-op and builds nothing."""

    @pytest.fixture
    def counting(self, monkeypatch):
        """``TraceAnnotation`` with its constructions counted, in place of
        the class the helper resolved."""
        from jax.profiler import TraceAnnotation

        from rabia_tpu.core import tracing

        class Counting(TraceAnnotation):
            made = 0

            def __init__(self, *args, **kwargs):
                Counting.made += 1
                super().__init__(*args, **kwargs)

        tracing.device_annotation("resolve")  # the lazy import, before the patch
        monkeypatch.setattr(tracing, "_annotation_cls", Counting)
        monkeypatch.setattr(tracing, "_listening", Counting.is_enabled)
        return Counting

    def test_no_annotation_is_built_while_nothing_listens(self, counting):
        from rabia_tpu.core import tracing

        assert not tracer.enabled
        assert tracing.device_annotation("rabia.cycle.pack") is tracing._NOOP
        assert tracing.device_annotation("rabia.x", bytes=3) is tracing._NOOP
        with tracing.device_annotation("rabia.x") as span:
            assert span is None
        eng = _engine()
        rng = np.random.default_rng(41)
        for kind in ("set", "mixed", "get"):
            _window(eng, kind, rng)
        eng.flush()
        eng.sync_to_host()
        eng.close()
        assert counting.made == 0

    def test_tracer_alone_records_without_an_annotation(self, counting, traced):
        from rabia_tpu.core import tracing

        with tracing.device_annotation("rabia.x", bytes=3) as span:
            assert span is None  # what set_metadata's callers test for
        assert traced.report()["rabia.x"]["count"] == 1
        assert counting.made == 0

    def test_a_listening_session_gets_the_annotations(self, counting, tmp_path):
        eng = _engine()
        rng = np.random.default_rng(43)
        _window(eng, "mixed", rng)
        eng.flush()
        events = _profiled(tmp_path, lambda: (_window(eng, "mixed", rng), eng.flush()))
        eng.close()
        assert counting.made >= len(events) > 0
        assert {n for n, *_ in events} >= {"rabia.cycle.book.versions",
                                           "rabia.submit.route", "rabia.fetch.flags"}

    def test_without_is_enabled_every_span_is_built_as_before(self, counting, monkeypatch):
        from rabia_tpu.core import tracing

        monkeypatch.setattr(tracing, "_listening", None)
        with tracing.device_annotation("rabia.x", bytes=3) as span:
            assert isinstance(span, counting)
        assert counting.made == 1


@pytest.mark.parametrize("path", ["native", "numpy"])
def test_parse_event_and_counter_say_which_path_packed(
    tmp_path, monkeypatch, path
):
    """``rabia.cycle.pack.parse`` carries ``path=`` in the profiler's trace
    (an argument added once the scan has answered), the name stays bare,
    and ``devkv_pack_windows_total{path=}`` counts the same windows."""
    from rabia_tpu.native.build import load_hostkernel

    if load_hostkernel() is None:
        pytest.skip("native host kernel unavailable")
    if path == "numpy":
        monkeypatch.setenv("RABIA_PY_DEVPACK", "1")
    else:
        monkeypatch.delenv("RABIA_PY_DEVPACK", raising=False)
    eng = _engine()
    rng = np.random.default_rng(23)
    _window(eng, "mixed", rng)  # the program's first call, untraced

    def work():
        for kind in ("mixed", "set", "mixed"):
            _window(eng, kind, rng)
        eng.flush()

    events = _profiled(tmp_path, work)
    snap = eng.metrics.snapshot()
    eng.close()
    said = [
        stats.get("path") for name, *_, stats in events
        if name == "rabia.cycle.pack.parse"
    ]
    assert said == [path] * 3
    other = {"native": "numpy", "numpy": "native"}[path]
    assert snap[f'rabia_devkv_pack_windows_total{{path="{path}"}}'] == 4
    assert snap[f'rabia_devkv_pack_windows_total{{path="{other}"}}'] == 0


class TestPipeSpans:
    """The pipe's own events (PR 38): the rung marker at every dispatch,
    the governor's resizes, the ladder's builds."""

    def test_ungoverned_engine_marks_its_one_rung_and_nothing_else(self, traced):
        eng = _engine()
        rng = np.random.default_rng(3)
        for kind in ("set", "mixed", "mixed"):
            _window(eng, kind, rng)
        eng.flush()
        rep = traced.report()
        assert rep[f"rabia.window.w{WINDOW}"]["count"] == 3
        assert [n for n in rep if n.startswith("rabia.window.")] == [
            f"rabia.window.w{WINDOW}"]
        assert "rabia.governor.resize" not in rep and "rabia.ladder.build" not in rep
        assert not any(n.startswith("rabia.devkv.w") for n in rep)
        eng.close()

    def test_governed_engine_marks_each_rung_resize_and_ladder_build(self, traced):
        eng = _engine(latency_target_ms=60_000.0, min_window=2, max_window=WINDOW)
        rng = np.random.default_rng(4)
        _window(eng, "set", rng)  # builds the SET ladder: one sibling
        _window(eng, "mixed", rng)  # and the mixed one
        rep = traced.report()
        assert rep["rabia.ladder.build"]["count"] == 2
        assert rep["rabia.jit.first_call"]["count"] == 4  # two rungs x two kinds
        # each build lies inside the dispatch span that first needed the kind
        assert _total("rabia.ladder.build") <= (
            _total(PROGRAM["set"]) + _total(PROGRAM["mixed"]))
        eng.latency_target_ms = 1e-6  # nothing meets it: down a rung
        for _ in range(3):
            _window(eng, "mixed", rng)
        eng.flush()
        assert eng.window == 2 and eng.window_resizes == 1
        eng.latency_target_ms = 60_000.0
        _window(eng, "mixed", rng)  # a whole window of 4 blocks: two of rung 2
        eng.flush()
        rep = traced.report()
        assert rep["rabia.governor.resize"]["count"] == 1
        # a ladder build for each kind and widths first seen (the values'
        # widths are drawn), never one for a rung: two programs a build
        builds = rep["rabia.ladder.build"]["count"]
        assert rep["rabia.jit.first_call"]["count"] == 2 * builds
        assert len(eng._dev._fused_cache) == 2 * builds
        marks = {n: rep[n]["count"] for n in rep if n.startswith("rabia.window.")}
        assert marks == {f"rabia.window.w{w}": eng._dev_windows[w] for w in (2, WINDOW)}
        assert marks["rabia.window.w2"] >= 2 and marks[f"rabia.window.w{WINDOW}"] >= 4
        assert eng.device_lane_active
        eng.close()


class TestUploadBytes:
    @pytest.mark.parametrize("kind", ["set", "get", "mixed"])
    def test_counter_grows_by_the_placed_operands(self, kind):
        eng = _engine()
        rng = np.random.default_rng(13)
        blocks = [_block(kind, rng) for _ in range(WINDOW)]
        dev = eng._dev
        beside = eng.alive.nbytes + eng.S * 4  # alive mask + base slots
        if kind == "set":
            ops = dev.pack_window(blocks)
            want = beside + sum(a.nbytes for a in ops)
        elif kind == "get":
            # the key planes only: a lookup never uploads values
            want = beside + sum(a.nbytes for a in dev.pack_get_window(blocks))
        else:
            kinds, ops = dev.pack_mixed_window(blocks)
            want = beside + kinds.nbytes + sum(a.nbytes for a in ops)
        before = eng.metrics.snapshot()["rabia_devkv_upload_bytes_total"]
        for b in blocks:
            eng.submit_block(b)
        eng.run_cycle()
        after = eng.metrics.snapshot()["rabia_devkv_upload_bytes_total"]
        assert after - before == want
        eng.flush()
        eng.close()


def _content(sm: VectorShardedKV) -> dict:
    """``{(shard, key): (value, version)}`` of one host replica."""
    st = sm.store
    out = {}
    for slot in np.nonzero(st.state == 1)[0].tolist():
        key = st.key_lanes[slot].view(np.uint8)[: int(st.key_len[slot])]
        out[(int(st.shard_col[slot]), key.tobytes())] = (
            st._value_at(slot), int(st.version[slot]),
        )
    return out


def _replies_and_state(enabled: bool, profile_dir=None):
    """One stream's replies, final table and host stores, with the tracer
    on or off and, given a directory, under a profiler session."""
    was = tracer.enabled
    tracer.reset()
    tracer.enabled = enabled
    try:
        eng = _engine()
        rng = np.random.default_rng(21)
        futs = []

        def work():
            for kind in ("set", "mixed", "get", "set", "mixed"):
                futs.extend(_window(eng, kind, rng))
            eng.flush()

        if profile_dir is None:
            work()
        else:
            assert _profiled(profile_dir, work)
        assert eng.device_lane_active
        replies = [[bytes(g[0]) for g in f.result()] for f in futs]
        report = tracer.report()
        state = [np.asarray(a).tobytes() for a in eng._dev.state]
        eng.sync_to_host()
        stores = [_content(sm) for sm in eng.sms]
        eng.close()
        return replies, state, stores, report
    finally:
        tracer.enabled = was
        tracer.reset()


def test_tracer_off_records_nothing_and_changes_nothing(tmp_path):
    replies_on, state_on, stores_on, report_on = _replies_and_state(True)
    replies_off, state_off, stores_off, report_off = _replies_and_state(False)
    assert report_on and "rabia.cycle.pack" in report_on
    for name in (*SUBMIT_PARTS, "rabia.cycle.kinds", *BOOK_PARTS,
                 "rabia.cycle.settle.blocks", "rabia.fetch.flags",
                 "rabia.fetch.meta", "rabia.setup.engine"):
        assert name in report_on, name
    assert report_on["rabia.submit.route"]["count"] == 5 * WINDOW
    assert report_off == {}
    assert replies_on == replies_off
    assert state_on == state_off
    assert stores_on == stores_off
    # and with a profiler session listening (every span an annotation)
    replies, state, stores, report = _replies_and_state(False, tmp_path)
    assert report == {}
    assert (replies, state, stores) == (replies_off, state_off, stores_off)


class TestNamedScopes:
    SCOPES = ("consensus", "key_match", "apply_set", "get_gather", "flags")

    def _mixed_call(self, eng):
        """The mixed program of one window and its (host) arguments."""
        rng = np.random.default_rng(17)
        dev = eng._dev
        kinds, ops = dev.pack_mixed_window(
            [_block("mixed", rng) for _ in range(WINDOW)]
        )
        gidx = np.arange(WINDOW, dtype=np.int32)
        fn = dev._build_mixed(ops.kwin.shape[2], ops.vwin.shape[2], WINDOW)
        base = np.zeros(eng.S, np.int32)
        args = (dev.state, eng.alive, base, np.int32(WINDOW), kinds, gidx, ops)
        return fn, args

    def test_scopes_are_in_the_lowered_program_and_change_no_output(
        self, monkeypatch
    ):
        import jax

        eng = _engine()
        fn, args = self._mixed_call(eng)
        text = fn.lower(*args, W=WINDOW, max_phases=4).as_text(debug_info=True)
        for scope in self.SCOPES:
            assert scope in text, scope
        with_scopes = jax.tree.leaves(fn(*args, W=WINDOW, max_phases=4))

        monkeypatch.setattr(
            jax, "named_scope", lambda name: contextlib.nullcontext()
        )
        bare_fn, _ = self._mixed_call(eng)
        bare_text = bare_fn.lower(*args, W=WINDOW, max_phases=4).as_text(
            debug_info=True
        )
        assert "key_match" not in bare_text
        bare = jax.tree.leaves(bare_fn(*args, W=WINDOW, max_phases=4))
        assert len(bare) == len(with_scopes)
        for a, b in zip(with_scopes, bare):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        eng.close()


class TestPackBuffers:
    OUTCOMES = ("reused", "fresh")

    def test_alloc_span_and_counter_say_how_many_planes_were_reused(
        self, monkeypatch
    ):
        from rabia_tpu.apps import device_kv

        seen = []
        annotate = device_kv.device_annotation

        def recording(name, **stats):
            if name == "rabia.cycle.pack.alloc":
                seen.append(stats)
            return annotate(name, **stats)

        monkeypatch.setattr(device_kv, "device_annotation", recording)
        eng = _engine()
        eng._dev_vseg_cap = 1  # a window's segment leaves with the next
        rng = np.random.default_rng(31)
        for _ in range(10):
            _window(eng, "mixed", rng)  # its replies are dropped unread
        eng.flush()
        assert eng.device_lane_active
        # five planes a window: none of the first can be a reused buffer,
        # all of the last are, and the counter sums what the spans said
        assert seen[0] == {"reused": 0} and seen[-1] == {"reused": 5}
        snap = eng.metrics.snapshot()
        got = {
            o: snap[f'rabia_devkv_pack_buffers_total{{outcome="{o}"}}']
            for o in self.OUTCOMES
        }
        reused = sum(s["reused"] for s in seen)
        assert got == {"reused": reused, "fresh": 5 * len(seen) - reused}
        text = eng.metrics.render_prometheus()
        for o in self.OUTCOMES:
            assert f'rabia_devkv_pack_buffers_total{{outcome="{o}"}}' in text
        eng.close()

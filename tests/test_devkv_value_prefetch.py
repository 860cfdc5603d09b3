"""Where a GET-bearing device window's value planes reach the host.

A read whose version has left the host segments makes its window's settle
take the value planes from the device. The first such window downloads
them on the window's thread (``inline``); from then on a window starts the
fetch on a readback worker at dispatch and its settle only picks the
planes up (``prefetched``), until a window's reads all resolve on the host
again: that window drops its plane (``unused``) and turns prefetching off.
Whichever way the bytes came, the replies are the host store's, byte for
byte. All three window kinds with the fallback share the mechanism: the
mixed window, the lean GET window and the read-probe window. Runs on the
virtual CPU mesh.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from rabia_tpu.apps.kvstore import (
    KVOperation,
    KVOpType,
    encode_op_bin,
    encode_set_bin,
)
from rabia_tpu.apps.vector_kv import VectorShardedKV
from rabia_tpu.core.blocks import build_block
from rabia_tpu.parallel import MeshEngine, make_mesh, mesh_engine

N_SHARDS = 8
WINDOW = 4
KINDS = ("mixed", "get", "read")
OUTCOMES = ("prefetched", "inline", "unused")
SHARDS = list(range(N_SHARDS))
DOWNLOAD = "rabia.cycle.settle.download"
FLOOR = mesh_engine._PREFETCH_MIN_BYTES  # as shipped


def _engine(kind: str, device: bool = True, **kw) -> MeshEngine:
    return MeshEngine(
        lambda: VectorShardedKV(N_SHARDS, capacity=1 << 12),
        n_shards=N_SHARDS,
        n_replicas=3,
        mesh=make_mesh(),
        window=WINDOW,
        device_store=device,
        device_read_lane=device and kind == "read",
        **kw,
    )


def _get(key: str) -> bytes:
    return encode_op_bin(KVOperation(KVOpType.Get, key))


def _set_window(tag: str) -> list:
    """WINDOW full-width SET blocks of the key ``<tag>-<shard>``."""
    return [
        build_block(
            SHARDS, [[encode_set_bin(f"{tag}-{s}", f"{tag}{w}" * (1 + s))] for s in SHARDS]
        )
        for w in range(WINDOW)
    ]


def _read_window(kind: str, tag: str, new: tuple = ("w",)) -> list:
    """WINDOW blocks that read ``<tag>-<shard>``: on every shard, or (a
    mixed window) on the odd shards while the even ones SET a key drawn
    from ``new``."""
    out = []
    for w in range(WINDOW):
        cmds = []
        for s in SHARDS:
            if kind == "mixed" and s % 2 == 0:
                cmds.append([encode_set_bin(f"{new[w % len(new)]}-{s}", f"{tag}{w}")])
            else:
                cmds.append([_get(f"{tag}-{s}")])
        out.append(build_block(SHARDS, cmds))
    return out


def _run(eng: MeshEngine, blocks: list) -> list:
    futs = [eng.submit_block(b) for b in blocks]
    eng.flush(max_cycles=400)
    return futs


def _replies(futs: list) -> list:
    return [[list(map(bytes, g)) for g in f.result()] for f in futs]


def _load(eng: MeshEngine) -> None:
    """Two SET windows, after which the first one's segment is evicted
    (reads of ``a`` must download) and the second's stays for good (reads
    of ``b`` resolve on the host)."""
    if eng._dev is not None:
        eng._dev_vseg_cap = 1  # evict every segment but the newest
    _run(eng, _set_window("a"))
    _run(eng, _set_window("b"))
    if eng._dev is not None:
        assert bool((eng._dev_floor[:N_SHARDS] > 0).all())
        eng._dev_vseg_cap = 1 << 30


def _fetches(eng: MeshEngine) -> dict:
    snap = eng.metrics.snapshot()
    return {
        o: snap[f'rabia_devkv_value_fetch_total{{outcome="{o}"}}'] for o in OUTCOMES
    }


def _downloaded(eng: MeshEngine) -> int:
    return eng.metrics.snapshot()["rabia_devkv_value_download_bytes_total"]


@pytest.fixture(autouse=True)
def every_plane_is_worth_a_worker(monkeypatch):
    """The planes of these engines are a few KB, far under the size from
    which a window hands them to a worker (``FLOOR``): take the floor away."""
    monkeypatch.setattr(mesh_engine, "_PREFETCH_MIN_BYTES", 0)


@pytest.fixture
def spans(monkeypatch):
    """Every ``device_annotation`` the engine makes: (name, stats), in
    order, from whichever thread."""
    seen = []
    annotate = mesh_engine.device_annotation

    def recording(name, **stats):
        seen.append((name, stats))
        return annotate(name, **stats)

    monkeypatch.setattr(mesh_engine, "device_annotation", recording)
    return seen


# the windows of the scenario, in order: the tag read and the outcome
# counted (None: neither prefetched nor downloaded)
SCENARIO = (
    ("a", "inline"),  # the first falling-back window downloads as ever
    ("a", "prefetched"),  # ... and turned prefetching on
    ("b", "unused"),  # resolves on the host: the plane is dropped
    ("b", None),  # ... and prefetching is off again
    ("a", "inline"),
    ("a", "prefetched"),
)


@pytest.mark.parametrize("kind", KINDS)
class TestOutcomes:
    def test_replies_counters_and_spans_through_every_outcome(self, kind, spans):
        dev, host = _engine(kind), _engine(kind, device=False)
        _load(dev)
        _load(host)
        want = dict.fromkeys(OUTCOMES, 0)
        plane_bytes = None
        for i, (tag, outcome) in enumerate(SCENARIO):
            del spans[:]
            before = _downloaded(dev)
            got = _replies(_run(dev, _read_window(kind, tag)))
            assert dev.device_lane_active, i
            assert got == _replies(_run(host, _read_window(kind, tag))), (i, tag)
            if outcome is not None:
                want[outcome] += 1
            assert _fetches(dev) == want, (i, tag)
            # the download span: once in a window that used a downloaded
            # plane, by either way; never in one that resolved on the host
            entered = [st for name, st in spans if name == DOWNLOAD]
            used = outcome in ("inline", "prefetched")
            assert entered == ([{"outcome": outcome}] if used else []), (i, tag)
            # a worker fetched iff dispatch started a prefetch
            workers = [st for name, st in spans if name == "rabia.fetch.values"]
            assert len(workers) == (outcome in ("prefetched", "unused")), (i, tag)
            grew = _downloaded(dev) - before
            if used:
                plane_bytes = plane_bytes or grew
                assert grew == plane_bytes > 0, (i, tag)
                if workers:
                    assert workers[0] == {"bytes": plane_bytes}
            else:
                assert grew == 0, (i, tag)
        assert dev._dev_prefetch
        dev.close()
        host.close()

    def test_small_planes_are_downloaded_inline_every_time(
        self, kind, spans, monkeypatch
    ):
        """Under ``_PREFETCH_MIN_BYTES`` a hand-over costs more than the
        download: the window's thread takes the planes itself, window after
        window, and no worker is asked."""
        monkeypatch.setattr(mesh_engine, "_PREFETCH_MIN_BYTES", FLOOR)
        dev, host = _engine(kind), _engine(kind, device=False)
        _load(dev)
        _load(host)
        for i in range(3):
            got = _replies(_run(dev, _read_window(kind, "a")))
            assert got == _replies(_run(host, _read_window(kind, "a"))), i
        assert _fetches(dev) == {"prefetched": 0, "inline": 3, "unused": 0}
        assert [st for name, st in spans if name == DOWNLOAD] == [
            {"outcome": "inline"}
        ] * 3
        assert not [name for name, _ in spans if name == "rabia.fetch.values"]
        assert 0 < _downloaded(dev) < 3 * FLOOR
        dev.close()
        host.close()

    def test_prefetched_planes_are_whole_and_contiguous(self, kind, monkeypatch):
        """What the worker hands the settle is one C-contiguous array a
        plane, so a wave's row of it is a view: the reply copies nothing."""
        from rabia_tpu.apps import device_kv

        rows = []
        init = device_kv.GetFrameGroups.__init__

        def recording(self, shards, found, ver, vlen, val_words):
            rows.append(val_words)
            init(self, shards, found, ver, vlen, val_words)

        monkeypatch.setattr(device_kv.GetFrameGroups, "__init__", recording)
        dev = _engine(kind)
        _load(dev)
        _run(dev, _read_window(kind, "a"))
        del rows[:]
        futs = _run(dev, _read_window(kind, "a"))
        assert _fetches(dev)["prefetched"] == 1
        assert len(rows) == WINDOW
        for row, fut in zip(rows, futs):
            assert row.flags["C_CONTIGUOUS"] and row.base is not None
            gf = fut._results
            gf = getattr(gf, "_get", gf)
            assert np.shares_memory(gf.valb, row)
        dev.close()

    def test_pipelined_windows_prefetch_once_the_first_has_settled(self, kind):
        """With three windows in flight, those dispatched before the first
        falling-back window settled download inline; every later one is
        prefetched. Replies as the host's throughout."""
        dev, host = _engine(kind), _engine(kind, device=False)
        _load(dev)
        _load(host)
        n = 8
        futs = []
        for _ in range(n):
            futs += [dev.submit_block(b) for b in _read_window(kind, "a")]
            dev.run_cycle()
        dev.flush(max_cycles=400)
        assert dev.device_lane_active
        got = _fetches(dev)
        assert got["unused"] == 0 and got["inline"] + got["prefetched"] == n
        assert 1 <= got["inline"] <= dev._dev_inflight + 1
        want = []
        for _ in range(n):
            want += _run(host, _read_window(kind, "a"))
        assert _replies(futs) == _replies(want)
        dev.close()
        host.close()


def _content(sm: VectorShardedKV) -> dict:
    st = sm.store
    out = {}
    for slot in np.nonzero(st.state == 1)[0].tolist():
        key = st.key_lanes[slot].view(np.uint8)[: int(st.key_len[slot])]
        out[(int(st.shard_col[slot]), key.tobytes())] = (
            st._value_at(slot), int(st.version[slot]),
        )
    return out


@pytest.fixture
def slow_worker(monkeypatch):
    """The worker's fetch held back, so that a prefetch is surely still
    in flight when the engine rolls back or closes; ``done`` counts the
    fetches that ran to their end all the same."""
    fetch = mesh_engine._prefetch_values
    state = {"started": 0, "done": 0, "gate": threading.Event()}

    def held(planes):
        state["started"] += 1
        state["gate"].wait(5.0)
        out = fetch(planes)
        state["done"] += 1
        return out

    monkeypatch.setattr(mesh_engine, "_prefetch_values", held)
    return state


class TestRollbackAndShutdown:
    def test_dirty_window_with_a_prefetch_in_flight_rolls_back_and_demotes(
        self, slow_worker
    ):
        """A mixed window that prefetches and then reads back dirty (its
        SETs overflow the table): the pipe rolls back, the record goes with
        its future unread, the lane demotes, and the host path re-decides
        the same blocks: final state and replies as a host-only engine's."""
        kw = {"device_store_kw": {"per_shard_capacity": 4}}
        dev, host = _engine("mixed", **kw), _engine("mixed", device=False)
        _load(dev)
        _load(host)
        first = _read_window("mixed", "a")
        # keys a, b, w are there: three more make six in a table of four
        dirty = _read_window("mixed", "a", new=("x", "y", "z"))
        assert _replies(_run(dev, first)) == _replies(_run(host, first))
        assert _fetches(dev)["inline"] == 1
        before = _fetches(dev), _downloaded(dev)
        futs = [dev.submit_block(b) for b in dirty]
        dev.run_cycle()
        (rec,) = dev._dev_pipe
        assert rec["val_fut"] is not None and not rec["val_fut"].done()
        dev.flush(max_cycles=400)
        assert not dev.device_lane_active, "a dirty window must demote"
        assert not dev._dev_pipe and dev._dev_fetcher_pool is None
        # nothing of the rolled-back window was counted or used
        assert (_fetches(dev), _downloaded(dev)) == before
        assert _replies(futs) == _replies(_run(host, dirty))
        want = _content(host.sms[0])
        for sm in dev.sms:
            assert _content(sm) == want
        # the late completion touches nothing
        slow_worker["gate"].set()
        deadline = time.monotonic() + 10.0
        while slow_worker["done"] < slow_worker["started"]:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert slow_worker["started"] == 1
        assert (_fetches(dev), _downloaded(dev)) == before
        for sm in dev.sms:
            assert _content(sm) == want
        dev.close()
        host.close()

    @pytest.mark.parametrize("kind", KINDS)
    def test_close_with_a_prefetch_in_flight_settles_and_returns(
        self, kind, slow_worker
    ):
        dev, host = _engine(kind), _engine(kind, device=False)
        _load(dev)
        _load(host)
        blocks = _read_window(kind, "a")
        _run(dev, blocks)
        futs = [dev.submit_block(b) for b in blocks]
        # dispatch and no more: a whole cycle of the read lane, with no
        # write staged behind the probe window, settles it at once
        dev._dev_serve_reads() if kind == "read" else dev.run_cycle()
        (rec,) = dev._dev_pipe
        assert rec["val_fut"] is not None and not rec["val_fut"].done()
        threading.Timer(0.05, slow_worker["gate"].set).start()
        closer = threading.Thread(target=dev.close)
        closer.start()
        closer.join(30.0)
        assert not closer.is_alive(), "close() hung on the prefetch"
        assert dev._dev_fetcher_pool is None and not dev._dev_pipe
        assert _fetches(dev) == {"prefetched": 1, "inline": 1, "unused": 0}
        _run(host, blocks)
        assert _replies(futs) == _replies(_run(host, blocks))
        dev.close()  # idempotent
        host.close()


def test_tracer_counts_every_span_recorded_from_many_threads():
    """The readback workers record ``rabia.fetch.values`` while the window's
    thread records its own spans: no update of the aggregate is lost."""
    import sys

    from rabia_tpu.core.tracing import Tracer

    t = Tracer(enabled=True)
    n_threads, n_each = 16, 2000
    start = threading.Barrier(n_threads)

    def work(i: int) -> None:
        start.wait(10.0)
        for _ in range(n_each):
            t.record("shared", 1.0)
            t.record(f"own.{i}", 1.0)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60.0)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(was)
    rep = t.report()
    assert rep["shared"]["count"] == n_threads * n_each
    assert rep["shared"]["total_s"] == float(n_threads * n_each)
    assert all(rep[f"own.{i}"]["count"] == n_each for i in range(n_threads))

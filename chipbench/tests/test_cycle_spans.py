"""Tests of the readers of the program's per-window spans (CPU only).

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

- each of the seven readers on a hand-made ``ctx``, and ``None`` where the
  program has no such span (the parent commit, the older recorded trace);
- the reducer names an idle gap after the innermost ``rabia.cycle.*`` span;
- ``cycle_host_ms`` and ``dispatch_ms_per_window`` read the same value with
  and without the new spans beside the ``rabia.devkv.*`` ones;
- the program's ``devkv_upload_bytes_total`` over one row-packed window
  against the op-plane term of ``peaks.window_bytes``;
- the readers on a small trace recorded on the chip with the spans in it.
"""

from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import gen, peaks, run, spec, trace

REPO = spec.REPO_ROOT
DATA = Path(__file__).parent / "data"
NEW = (
    "pack_ms_per_window", "pack_ms_p95", "book_ms_per_window",
    "settle_host_ms_per_window", "readback_wait_ms_per_window",
    "upload_ms_per_window", "cycle_unattributed_ms",
)
MS = 1e-3


@pytest.fixture(scope="module")
def readers() -> dict:
    return spec.load_cell("kv-r3-s64.ycsb-a-sat", REPO).readers


def _ctx(spans: dict, windows: int) -> dict:
    return {"spans": spans, "windows": windows}


# two windows; every duration in seconds. run_cycle: 40 + 60 ms.
OLD_SPANS = {
    "chipbench.run_cycle": [40 * MS, 60 * MS],
    "chipbench.submit": [1 * MS] * 4,
    "rabia.devkv.mixed_apply": [5 * MS, 7 * MS],
}
NEW_SPANS = {
    "rabia.cycle.pack": [20 * MS, 30 * MS],
    "rabia.cycle.pack.parse": [4 * MS, 6 * MS],
    "rabia.cycle.pack.alloc": [3 * MS, 9 * MS],
    "rabia.cycle.pack.gather": [8 * MS, 8 * MS],
    "rabia.cycle.pack.dict": [5 * MS, 7 * MS],
    "rabia.dispatch.place": [3 * MS, 4 * MS],
    "rabia.dispatch.call": [2 * MS, 2.5 * MS],
    "rabia.cycle.book": [4 * MS, 6 * MS],
    "rabia.cycle.wait": [0.1 * MS, 0.3 * MS, 0.2 * MS, 0.4 * MS],
    "rabia.cycle.settle": [9 * MS, 11 * MS],
}


def test_readers_on_a_hand_made_ctx(readers):
    ctx = _ctx({**OLD_SPANS, **NEW_SPANS}, 2)
    got = {name: readers[name](ctx) for name in NEW}
    assert got["pack_ms_per_window"] == pytest.approx(25.0)  # median of 20, 30
    assert got["pack_ms_p95"] == pytest.approx(29.5)  # numpy's linear rule
    assert got["book_ms_per_window"] == pytest.approx(5.0)
    assert got["settle_host_ms_per_window"] == pytest.approx(10.0)
    assert got["readback_wait_ms_per_window"] == pytest.approx(0.5)  # 1.0 ms / 2
    assert got["upload_ms_per_window"] == pytest.approx(3.5)
    # 100 ms of run_cycle - pack 50 - book 10 - wait 1 - settle 20 - devkv 12
    # = 7 ms over 2 windows; pack.* and dispatch.* lie inside and are not
    # subtracted a second time (they sum to 50 + 11.5 ms: the result would
    # be negative)
    assert got["cycle_unattributed_ms"] == pytest.approx(3.5)


def test_readers_find_nothing_where_the_program_has_no_such_span(readers):
    ctx = _ctx(dict(OLD_SPANS), 2)
    assert {name: readers[name](ctx) for name in NEW} == dict.fromkeys(NEW)
    assert readers["cycle_unattributed_ms"](_ctx(dict(NEW_SPANS), 2)) is None
    no_windows = _ctx({**OLD_SPANS, **NEW_SPANS}, 0)
    assert readers["readback_wait_ms_per_window"](no_windows) is None
    assert readers["cycle_unattributed_ms"](no_windows) is None


def test_the_accepted_readers_do_not_see_the_new_spans(readers):
    before = _ctx(dict(OLD_SPANS), 2)
    after = _ctx({**OLD_SPANS, **NEW_SPANS}, 2)
    for name in ("cycle_host_ms", "dispatch_ms_per_window", "submit_us_per_block"):
        assert readers[name](before) == readers[name](after), name
    assert readers["cycle_host_ms"](after) == pytest.approx(44.0)
    assert readers["dispatch_ms_per_window"](after) == pytest.approx(6.0)
    assert not any(name.startswith("rabia.devkv.") for name in NEW_SPANS)


def test_an_idle_gap_is_named_after_the_innermost_program_span():
    segs = trace._segments(
        [("chipbench.run_cycle", 1, 20), ("rabia.cycle.pack", 2, 9),
         ("rabia.cycle.pack.parse", 2, 4), ("rabia.cycle.pack.alloc", 4, 5),
         ("rabia.devkv.mixed_apply", 9, 13), ("rabia.dispatch.place", 10, 12),
         ("rabia.cycle.book", 13, 15), ("rabia.cycle.wait", 15, 16),
         ("rabia.cycle.settle", 16, 19)], 0, 21,
    )
    assert segs == [
        (0, 1, "outside_spans"), (1, 2, "run_cycle_outside_dispatch"),
        (2, 4, "dispatch:rabia.cycle.pack.parse"),
        (4, 5, "dispatch:rabia.cycle.pack.alloc"),
        (5, 9, "dispatch:rabia.cycle.pack"),
        (9, 10, "dispatch:rabia.devkv.mixed_apply"),
        (10, 12, "dispatch:rabia.dispatch.place"),
        (12, 13, "dispatch:rabia.devkv.mixed_apply"),
        (13, 15, "dispatch:rabia.cycle.book"),
        (15, 16, "dispatch:rabia.cycle.wait"),
        (16, 19, "dispatch:rabia.cycle.settle"),
        (19, 20, "run_cycle_outside_dispatch"), (20, 21, "outside_spans"),
    ]


def test_upload_counter_against_the_op_plane_term_of_window_bytes():
    """One row-packed mixed window at the cells' widths (keys bucketed to 32
    B, values to 64 B): the program places exactly the 100 B an op that
    ``peaks.window_bytes`` counts for the op planes, and beside them one
    kind byte an op, the alive mask (a byte a shard a replica) and the base
    slots (4 B a shard), which ``window_bytes`` leaves out as not needed by
    every program that does the work."""
    config = {
        "n_shards": 8, "n_replicas": 3, "window": 64, "per_shard_capacity": 64,
        "key_bytes": 32, "value_bytes": 64,
    }
    traffic = {
        "readproportion": 0.5, "updateproportion": 0.5, "pool_windows": 1,
        "requestdistribution": "zipfian", "zipfian_constant": 0.99,
        "check_block_share": 1.0,
    }
    generator = gen.Generator(2**31 + 27, config, traffic)
    eng = run.build_engine(config)
    uploaded = eng.metrics.counter("devkv_upload_bytes_total")
    S, R, W = 8, 3, 64
    for wave in generator.pool_waves():
        eng.submit_block(generator.block(*generator.encode(wave)))
    before = uploaded.value()
    eng.run_cycle()
    got = uploaded.value() - before
    assert eng.cycles == 1 and eng.device_lane_active
    # over 32 distinct rows a shard: the upload is row-packed, not a dictionary
    assert [k[0] for k in eng._dev._fused_cache] == ["mix"]
    op_planes = W * S * (2 + 2 + 32 + 64)
    table, meta_out, flags = 2 * peaks.table_bytes(config), W * S * 8, 12
    assert op_planes == peaks.window_bytes(config) - table - meta_out - flags
    assert got - op_planes == W * S * 1 + S * R + S * 4
    eng.flush()
    eng.close()


def test_readers_on_a_trace_recorded_on_the_chip_with_the_spans(readers):
    """``data/tiny_spans_tpu_v5e.xplane.pb``: the tiny cell of
    ``test_chipbench.py`` (8 shards, window 4), traced for 33 ms on a TPU v5e
    with the program's spans in it (PR 27): six windows dispatched."""
    r = trace.reduce(str(DATA / "tiny_spans_tpu_v5e.xplane.pb"))
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.032855993, abs=1e-9)
    spans = r["spans"]
    once_a_window = (
        "rabia.cycle.pack", "rabia.cycle.pack.parse", "rabia.cycle.pack.alloc",
        "rabia.cycle.pack.gather", "rabia.cycle.pack.dict", "rabia.devkv.mixed_apply",
        "rabia.dispatch.place", "rabia.dispatch.call", "rabia.cycle.book",
        "rabia.cycle.settle",
    )
    assert {k: len(v) for k, v in spans.items() if k.startswith("rabia.")} == {
        **dict.fromkeys(once_a_window, 6), "rabia.cycle.wait": 12,
    }
    total = {k: sum(v) for k, v in spans.items()}
    # the event names come back bare, the stats (bytes=, what=) apart from them
    assert not any("#" in k or "=" in k for k in spans)
    # nested spans lie inside their parents, on the profiler's clock
    parts = sum(total[k] for k in once_a_window[1:5])
    assert parts <= total["rabia.cycle.pack"] and parts > 0.95 * total["rabia.cycle.pack"]
    inside = total["rabia.dispatch.place"] + total["rabia.dispatch.call"]
    assert inside <= total["rabia.devkv.mixed_apply"] and inside > 0.95 * total["rabia.devkv.mixed_apply"]
    # the idle gaps carry the innermost span's name; little is left unnamed
    gaps = dict(r["idle_gaps"])
    assert max(gaps, key=gaps.get) == "dispatch:rabia.dispatch.place"
    assert gaps["run_cycle_outside_dispatch"] < 0.06 * r["window_s"]
    ctx = {"trace": r, "spans": spans, "windows": 6, "blocks": 24}
    got = {name: readers[name](ctx) for name in NEW}
    assert got["pack_ms_per_window"] == pytest.approx(0.97589)
    assert got["pack_ms_p95"] == pytest.approx(1.49843)
    assert got["book_ms_per_window"] == pytest.approx(0.153995)
    assert got["settle_host_ms_per_window"] == pytest.approx(0.385735)
    assert got["readback_wait_ms_per_window"] == pytest.approx(4.298e-2 / 6)
    assert got["upload_ms_per_window"] == pytest.approx(2.1869855)
    top = sum(total[k] for k in (
        "rabia.cycle.pack", "rabia.cycle.book", "rabia.cycle.wait",
        "rabia.cycle.settle", "rabia.devkv.mixed_apply",
    ))
    assert got["cycle_unattributed_ms"] == pytest.approx(
        (total["chipbench.run_cycle"] - top) / 6 * 1e3
    )
    assert 0 < got["cycle_unattributed_ms"] < 0.15 * readers["cycle_host_ms"](ctx)
    # the accepted readers still read the dispatch spans, and only those
    assert readers["dispatch_ms_per_window"](ctx) == pytest.approx(3.235392)
    assert readers["cycle_host_ms"](ctx) == pytest.approx(
        (total["chipbench.run_cycle"] - total["rabia.devkv.mixed_apply"]) / 6 * 1e3
    )

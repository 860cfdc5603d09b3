"""The cells of PR 35: ``kv-r5-s4096.ycsb-a-sat`` and the configuration
``kv-r5-s4096-p512`` (the north-star deployment at 512 records a shard)
with its cell ``kv-r5-s4096-p512.ycsb-b-sat``.

- the new configuration's shape rehearsed at a small size: 512 records a
  shard (so key indices reach the stem's third hex digit), 8 shards,
  5 replicas, window 4, through ``chipbench.run.run_cell`` unchanged, under
  YCSB-B's and YCSB-A's proportions, held to ``kv_plain``;
- the generator at 512 records a shard;
- the program's ``devkv_table_bytes`` against the benchmark's hand count
  (``peaks.table_bytes``), at the rehearsal's size on a device table and at
  the configuration's own size from the planes' shapes alone;
- the data: the new file against ``kv-r5-s4096.json`` and its entry, both
  new cells through ``spec.load_cell``, and their reply samples.

PR 38 adds the configuration ``kv-r5-s4096-ab`` (the same shapes with the
window governed), the traffic ``ycsb-b-w1`` and the cell
``kv-r5-s4096-ab.ycsb-b-w1``: the data, a rehearsal over a small governed
configuration whose target forces a descent, and the two readers
(``window_waves_mean``, ``governor_resizes``) on a span table.

PR 40 adds seven readers of the spans inside ``submit_block``,
``rabia.cycle.book`` and ``rabia.cycle.settle`` and of ``rabia.cycle.kinds``:
the entries through ``spec.load_cell`` for every cell, each reader on a span
table with and without its span, and a rehearsal of ``kv-r3-s64``'s own
shapes under the CPU's profiler in which all seven read a number.

Nothing here touches a TPU.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import gen, peaks, run, spec, wire
from rabia_tpu.apps import device_kv

REPO = spec.REPO_ROOT
CONFIG = REPO / "chipbench/configs/kv-r5-s4096-p512.json"
BASE = REPO / "chipbench/configs/kv-r5-s4096.json"
NEW_CELLS = ("kv-r5-s4096.ycsb-a-sat", "kv-r5-s4096-p512.ycsb-b-sat")
AB_CONFIG = REPO / "chipbench/configs/kv-r5-s4096-ab.json"
AB_CELL = "kv-r5-s4096-ab.ycsb-b-w1"
SMALL = {"n_shards": 8, "window": 4, "records_at_capacity": 8 * 512}


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


# -- the data --------------------------------------------------------------------


def test_new_configuration_is_kv_r5_s4096_at_512_records_a_shard():
    new, old = _json(CONFIG), _json(BASE)
    assert new["name"] == "kv-r5-s4096-p512"
    for key in set(old) - {"name", "source", "deployment", "per_shard_capacity",
                           "records_at_capacity", "reduced", "assumed"}:
        assert new[key] == old[key], key  # the runner's keys, the guarantees
    assert set(new) == set(old)
    assert new["per_shard_capacity"] == 512
    assert new["records_at_capacity"] == 4096 * 512 == 2_097_152
    assert set(new["reduced"]) == set(old["reduced"])
    for key in ("replica_processes", "adaptive_batching"):
        assert new["reduced"][key] == old["reduced"][key], key
    assert new["reduced"]["records_per_shard"].startswith("512 records a shard")
    assert new["assumed"] == old["assumed"]
    entry = {c["name"]: c for c in spec.load_benchmark()["configs"]}[new["name"]]
    assert entry["source"] == new["source"] and len(entry["source"]) <= 200
    assert entry["source"].startswith(old["source"]) and entry["source"] != old["source"]
    assert entry["reduced"] == list(new["reduced"])
    assert entry["file"] == str(CONFIG.relative_to(REPO))


def test_both_new_cells_load_and_share_every_reader_and_traffic_file():
    bench = spec.load_benchmark()
    assert tuple(w["name"] for w in bench["workloads"][3:5]) == NEW_CELLS
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    pack, table = (spec.load_cell(name) for name in NEW_CELLS)
    assert pack.chips == table.chips == 1
    assert pack.config == _json(BASE) and table.config == _json(CONFIG)
    assert pack.traffic == _json(REPO / "chipbench/traffic/ycsb-a-sat.json")
    assert table.traffic == _json(REPO / "chipbench/traffic/ycsb-b-sat.json")
    for cell in (pack, table):
        assert len(cell.readers) == len(bench["per_layer"]) == 25
        assert cell.traffic["check_block_share"] == 1 / 512
        assert cell.traffic["in_flight_windows"] == 3
    # the two cells of a pair take the same traffic key for key
    assert table.traffic == spec.load_cell("kv-r5-s4096.ycsb-b-sat").traffic


def test_hand_count_of_the_larger_table_and_its_window():
    config = _json(CONFIG)
    table = 4096 * 512 * 109 + 4096 * 4  # per slot 109 B, as at 256 a shard
    assert peaks.table_bytes(config) == table == 228_605_952
    ops = 64 * 4096
    assert peaks.window_bytes(config) == 2 * table + ops * 108 + 12 == 485_523_468


@pytest.mark.parametrize("seed", (792490177, 1, 2350000001, 2**31 + 35, 3))
@pytest.mark.parametrize("cell", NEW_CELLS + (AB_CELL,))
def test_sample_is_not_empty_in_a_traced_window(cell, seed):
    """A traced run measures at most 4 s, which hold some 4,000 blocks of
    either cell after the load (256 or 512 waves) and the warm-up (some 700
    blocks; 64 windows of at most 64 in the governed cell): 8 expected
    picks at 1/512 (PERF.md, PR 29), 16 at the governed cell's 1/256."""
    c = spec.load_cell(cell)
    picked = gen.Generator(seed, c.config, c.traffic).sampler()
    first = c.config["per_shard_capacity"] + 800
    assert sum(picked(i) for i in range(first, first + 4000)) >= 1


# -- the generator at 512 records a shard ------------------------------------------


def test_generator_at_512_records_a_shard():
    config = dict(_json(CONFIG), n_shards=64, window=8)
    traffic = dict(_json(REPO / "chipbench/traffic/ycsb-b-sat.json"), pool_windows=80)
    g = gen.Generator(2**31 + 35, config, traffic)
    keys = {g.key_bytes(s, j) for s in range(g.S) for j in range(g.n_keys)}
    assert g.n_keys == 512 and len(keys) == 64 * 512  # distinct
    # the stem's third hex digit is in use: index 0x1ff of shard 0x3f
    assert g.key_bytes(63, 511)[:8] == b"003f1ff."
    assert g.key_bytes(0, 256)[:8] == b"0000100."
    assert len(g.load_waves()) == 512
    waves = g.pool_waves()
    kid = np.stack([w.kid for w in waves])  # [waves, S]
    kind = np.stack([w.kind for w in waves])
    assert kid.min() >= 0 and kid.max() == 511  # every op a record of its shard
    assert (kid >= 256).any()
    wave = waves[0]
    data, sizes = g.encode(wave)
    at = np.concatenate([[0], np.cumsum(sizes)])
    for s in (0, 17, 63):  # the op on the wire names that record's key
        klen = int(g.klen[s, wave.kid[s]])
        op = data[at[s] : at[s + 1]].tobytes()
        assert op[3 : 3 + klen] == g.key_bytes(s, int(wave.kid[s]))
    # YCSB's law over 512 ranks: the hottest record takes 1/H(512, 0.99) of
    # its shard's requests, the next 2^-0.99 of that
    top = 1 / (1 / np.arange(1, 513) ** 0.99).sum()
    assert top == pytest.approx(0.1426, abs=1e-3)
    counts = np.stack([np.bincount(kid[:, s], minlength=512) for s in range(g.S)])
    by_rank = -np.sort(-counts, axis=1) / len(waves)
    assert by_rank[:, 0].mean() == pytest.approx(top, rel=0.05)
    assert by_rank[:, 1].mean() == pytest.approx(top / 2**0.99, rel=0.08)
    assert len(set(counts.argmax(axis=1).tolist())) > 32  # scattered per shard
    assert (kind == wire.SET).mean() == pytest.approx(0.05, abs=0.005)


# -- the program's gauge against the benchmark's hand count ---------------------------


def test_table_bytes_of_the_planes_equal_the_hand_count_at_full_size():
    """``devkv_table_bytes`` is the bytes of ``DeviceKVTable.state``'s seven
    planes; at the configuration's own size they are counted from the
    shapes, with no device."""
    for path in (CONFIG, BASE, REPO / "chipbench/configs/kv-r5-s16384.json"):
        config = _json(path)
        planes = device_kv._state_planes(
            config["n_shards"], config["per_shard_capacity"],
            config["key_bytes"] // 4, config["value_bytes"] // 4,
        )
        assert len(planes) == 7
        assert device_kv._plane_bytes(planes) == peaks.table_bytes(config), path.name


# -- the configuration's shape, rehearsed ---------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    """The repo's ``BENCHMARK.json`` and ``chipbench`` (a link), with the
    new configuration cut to 8 shards x 512 records and window 4 (its file
    in a directory that is searched first), every block's replies compared,
    and a YCSB-A cell over it, which the benchmark itself does not hold."""
    root = tmp_path_factory.mktemp("root")
    (root / "chipbench").symlink_to(REPO / "chipbench", target_is_directory=True)
    for sub in ("configs", "traffic"):
        (root / "small" / sub).mkdir(parents=True)
    small = dict(_json(CONFIG), **SMALL)
    (root / "small/configs/kv-r5-s4096-p512.json").write_text(json.dumps(small))
    for name in ("ycsb-a-sat", "ycsb-b-sat"):
        traffic = _json(REPO / f"chipbench/traffic/{name}.json")
        (root / f"small/traffic/{name}.json").write_text(
            json.dumps(dict(traffic, check_block_share=1.0))
        )
    bench = spec.load_benchmark(REPO)
    bench["paths"] = ["small"] + bench["paths"]
    for c in bench["configs"]:
        if c["name"] == "kv-r5-s4096-p512":
            c["file"] = "small/configs/kv-r5-s4096-p512.json"
    bench["workloads"].append(
        {"name": "kv-r5-s4096-p512.ycsb-a-sat", "config": "kv-r5-s4096-p512",
         "traffic": "ycsb-a-sat", "chips": 1, "why": "throwaway"}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("traffic", ("ycsb-b-sat", "ycsb-a-sat"))
def test_rehearsal_at_512_records_a_shard_is_correct(root, traffic):
    seen = {}
    result = run.run_cell(
        f"kv-r5-s4096-p512.{traffic}", 2**31 + 35, 0.5, False, root=root,
        require_chip=False, engine_hook=lambda eng, runner: seen.update(eng=eng, run=runner),
    )
    eng, runner = seen["eng"], seen["run"]
    assert eng._dev.P == 512 and eng._dev.n_shards == 8
    assert result["correct"] is True and result["failed"] == 0
    checks = result["checks"]
    assert checks["replies_compared"]["value"] >= 8 * result["window"]["blocks_settled"] > 0
    for name in ("reply_mismatches", "replica_mismatches", "lane_faults",
                 "unsettled_blocks"):
        assert checks[name]["value"] == 0, name
    assert result["window"]["window_compiles"] == 0
    # loaded to capacity, and all five replica stores rebuilt to the reference
    # (replica_mismatches 0 compares every row of each with kv_plain)
    assert [len(sm.store) for sm in eng.sms] == [4096] * 5
    # the pool is 24 waves of 8 ops: 192 draws tell 5 % from 50 %, no more
    updates = np.mean([np.mean(w.kind == wire.SET) for w in runner.stream[512:]])
    assert updates == pytest.approx({"ycsb-b-sat": 0.05, "ycsb-a-sat": 0.5}[traffic], abs=0.12)
    assert max(int(w.kid.max()) for w in runner.stream[512:]) >= 256
    # the gauge, on a table that exists: 8 x 512 slots of 109 B and 8 counters
    small = dict(_json(CONFIG), **SMALL)
    snap = eng.metrics.snapshot()
    assert snap["rabia_devkv_table_bytes"] == peaks.table_bytes(small) == 446_496
    assert "rabia_devkv_table_bytes 446496" in eng.metrics.render_prometheus()


# -- PR 38: the governed configuration, its traffic and its cell -------------------------


def test_governed_configuration_is_p512_with_its_batching_on():
    new, old = _json(AB_CONFIG), _json(CONFIG)
    assert new["name"] == "kv-r5-s4096-ab"
    for key in ("reference", "n_shards", "n_replicas", "window", "per_shard_capacity",
                "key_bytes", "value_bytes", "records_at_capacity", "guarantees"):
        assert new[key] == old[key], key  # the shapes, the reference, the five guarantees
    assert set(new) == set(old) | {"engine"}
    assert new["engine"] == {"latency_target_ms": new["engine"]["latency_target_ms"],
                             "min_window": 8, "max_window": 64}
    assert 0 < new["engine"]["latency_target_ms"] < 60
    assert run.engine_options(new) == new["engine"]
    assert list(new["reduced"]) == ["replica_processes", "records_per_shard"]
    assert new["reduced"]["replica_processes"] == old["reduced"]["replica_processes"]
    assert set(new["assumed"]) == set(old["assumed"]) | set(new["engine"])
    entry = {c["name"]: c for c in spec.load_benchmark()["configs"]}[new["name"]]
    assert entry["source"] == new["source"] and len(entry["source"]) <= 200
    assert "adaptive batching on" in entry["source"]
    sources = [c["source"] for c in spec.load_benchmark()["configs"]]
    assert len(set(sources)) == len(sources)
    assert entry["reduced"] == list(new["reduced"])
    assert entry["file"] == str(AB_CONFIG.relative_to(REPO)) and len(entry["why"]) <= 200


def test_governed_cell_loads_with_one_window_outstanding():
    bench = spec.load_benchmark()
    assert [w["name"] for w in bench["workloads"]][-1] == AB_CELL
    assert len(bench["workloads"]) == 6 and len(bench["configs"]) == 5
    cell = spec.load_cell(AB_CELL)
    assert cell.chips == 1 and cell.config == _json(AB_CONFIG)
    sat = _json(REPO / "chipbench/traffic/ycsb-b-sat.json")
    differs = {"name", "what", "in_flight_windows", "warmup_windows",
               "check_block_share", "departs"}
    assert set(cell.traffic) == set(sat)
    for key in set(sat) - differs:
        assert cell.traffic[key] == sat[key], key  # the generator's and the pool's
    assert cell.traffic["loop"] == "closed" and cell.traffic["in_flight_windows"] == 1
    assert cell.traffic["warmup_windows"] == 64
    assert cell.traffic["check_block_share"] == 1 / 256
    for key in ("block_shape", "record_size"):
        assert cell.traffic["departs"][key] == sat["departs"][key]
    assert [m["name"] for m in bench["per_layer"][16:18]] == [
        "window_waves_mean", "governor_resizes"]
    for m in bench["per_layer"][16:18]:
        assert m["layer"] == "pipe" and "workloads" not in m
    assert set(cell.readers) == {m["name"] for m in bench["per_layer"]}
    # the engine it builds: the governor on, every rung known to the table
    small = dict(cell.config, n_shards=8, per_shard_capacity=8)
    eng = run.build_engine(small)
    try:
        assert eng.latency_target_ms == cell.config["engine"]["latency_target_ms"]
        assert eng._ladder() == eng._dev.rungs == (8, 16, 32, 64)
        assert eng.window == 64 and eng._dev_inflight == 1
    finally:
        eng.close()


@pytest.fixture(scope="module")
def governed_root(tmp_path_factory) -> Path:
    """The repo's benchmark with the governed configuration cut to 256
    shards x 16 records on the rungs 2, 4, 8 (its file in a directory that
    is searched first) and a target no cycle on a CPU meets, so that the
    governor descends in the load and stays down; the cell's traffic as it
    is, but an eighth of the blocks compared."""
    root = tmp_path_factory.mktemp("governed")
    (root / "chipbench").symlink_to(REPO / "chipbench", target_is_directory=True)
    for sub in ("configs", "traffic"):
        (root / "small" / sub).mkdir(parents=True)
    small = dict(
        _json(AB_CONFIG), n_shards=256, per_shard_capacity=16, window=8,
        records_at_capacity=256 * 16,
        engine={"latency_target_ms": 1e-3, "min_window": 2, "max_window": 8},
    )
    (root / "small/configs/kv-r5-s4096-ab.json").write_text(json.dumps(small))
    traffic = _json(REPO / "chipbench/traffic/ycsb-b-w1.json")
    (root / "small/traffic/ycsb-b-w1.json").write_text(
        json.dumps(dict(traffic, check_block_share=0.125))
    )
    bench = spec.load_benchmark(REPO)
    bench["paths"] = ["small"] + bench["paths"]
    for c in bench["configs"]:
        if c["name"] == "kv-r5-s4096-ab":
            c["file"] = "small/configs/kv-r5-s4096-ab.json"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_rehearsal_of_the_governed_cell_descends_and_is_correct(governed_root):
    seen = {}
    result = run.run_cell(
        AB_CELL, 2**31 + 38, 0.5, False, root=governed_root, require_chip=False,
        engine_hook=lambda eng, runner: seen.update(eng=eng, run=runner),
    )
    eng, runner = seen["eng"], seen["run"]
    assert result["correct"] is True and result["failed"] == 0
    checks = result["checks"]
    assert checks["replies_compared"]["value"] >= 256
    for name in ("reply_mismatches", "replica_mismatches", "lane_faults",
                 "unsettled_blocks"):
        assert checks[name]["value"] == 0, name
    # every rung's program was built in set-up: none by a measured window
    assert result["window"]["window_compiles"] == 0
    assert all(not measured for _, _, measured in runner.compiles)
    assert eng._dev.rungs == (2, 4, 8)
    sigs = set(eng._dev._fused_cache)
    assert {k[1] for k in sigs if k[0] == "mix"} == {2, 4, 8}
    assert all(k[4] == k[1] for k in sigs if k[0] == "mix")  # Gp = W
    # the governor came down, and the measured windows ran under the top rung
    assert eng.window == 2 and eng.window_resizes >= 1
    assert eng._dev_windows[2] >= result["window"]["windows"] > 0
    assert runner._target == 8  # one window of the configuration outstanding
    assert [len(sm.store) for sm in eng.sms] == [256 * 16] * 5


def test_readers_of_the_rung_and_the_resizes():
    cell = spec.load_cell(AB_CELL)
    mean, resizes = cell.readers["window_waves_mean"], cell.readers["governor_resizes"]
    # a program without the markers (the parent): nothing to read, no error
    parent = {"spans": {"rabia.cycle.pack": [0.01] * 5, "chipbench.run_cycle": [0.03] * 5}}
    assert mean(parent) is None and resizes(parent) is None
    assert mean({"spans": {}}) is None and resizes({"spans": {}}) is None
    # an ungoverned cell: every window at 64, no resize
    fixed = {"spans": {"rabia.window.w64": [1e-6] * 91, "rabia.cycle.pack": [0.012] * 91}}
    assert mean(fixed) == 64 and resizes(fixed) == 0
    # a governor that sat at 32, probed 64 twice and once went down to 16
    walked = {"spans": {
        "rabia.window.w32": [1e-6] * 150, "rabia.window.w64": [1e-6] * 16,
        "rabia.window.w16": [1e-6] * 8, "rabia.governor.resize": [2e-5] * 5,
        "rabia.window.wide": [1e-6],  # no rung in its name: not a marker
    }}
    assert mean(walked) == pytest.approx((150 * 32 + 16 * 64 + 8 * 16) / 174)
    assert resizes(walked) == 5


# -- PR 40: the readers of the window's inside ---------------------------------------------

# metric -> (its span, its unit, its layer, how it reduces the span's durations)
INSIDE = {
    "submit_validate_us_per_block": ("rabia.submit.validate", "us", "client surface", "mean"),
    "submit_route_us_per_block": ("rabia.submit.route", "us", "client surface", "mean"),
    "kinds_ms_per_window": ("rabia.cycle.kinds", "ms", "pack + resolve (host)", "per_window"),
    "book_versions_ms_per_window": (
        "rabia.cycle.book.versions", "ms", "pack + resolve (host)", "median"),
    "book_segment_ms_per_window": (
        "rabia.cycle.book.segment", "ms", "pack + resolve (host)", "median"),
    "book_handoff_ms_per_window": (
        "rabia.cycle.book.handoff", "ms", "readback + settle", "median"),
    "settle_blocks_ms_per_window": (
        "rabia.cycle.settle.blocks", "ms", "readback + settle", "median"),
}
CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_seven_entries_load_for_every_cell(cell):
    bench = spec.load_benchmark()
    assert len(CELLS) == 6
    assert [m["name"] for m in bench["per_layer"][-7:]] == list(INSIDE)
    layers = {m["layer"] for m in bench["per_layer"][:-7]}
    for m in bench["per_layer"][-7:]:
        _, unit, layer, _ = INSIDE[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": "program_span", "layer": layer, "moves": "committed_ops"}
        assert layer in layers  # a layer the benchmark already names
    loaded = spec.load_cell(cell)
    assert set(INSIDE) <= set(loaded.readers)
    assert set(loaded.readers) == {m["name"] for m in bench["per_layer"]}
    # a program without the spans, and a context without a traffic: nothing, no error
    parent = {"spans": {"rabia.cycle.book": [0.004] * 9, "chipbench.submit": [1e-4] * 576},
              "windows": 9}
    assert {name: loaded.readers[name](parent) for name in INSIDE} == dict.fromkeys(INSIDE)


@pytest.mark.parametrize("metric", INSIDE)
def test_reader_of_the_inside_with_and_without_its_span(metric):
    import statistics

    span, _, _, how = INSIDE[metric]
    read = spec.load_cell("kv-r3-s64.ycsb-a-sat").readers[metric]
    durations = [2e-3, 1e-3, 4e-3, 3e-3, 9e-3, 5e-3]  # s, six entries
    others = {name: [1.0] for name, *_ in INSIDE.values() if name != span}
    others.update({"rabia.cycle.book": [0.02] * 3, "rabia.devkv.mixed_apply": [0.01] * 3})
    assert read({"spans": others, "windows": 3}) is None
    assert read({"spans": {}, "windows": 3}) is None
    assert read({"spans": {**others, span: []}, "windows": 3}) is None
    got = read({"spans": {**others, span: durations}, "windows": 3})
    want = {
        "mean": sum(durations) / 6 * 1e6,
        "per_window": sum(durations) / 3 * 1e3,
        "median": statistics.median(durations) * 1e3,
    }[how]
    assert got == pytest.approx(want)
    if how == "per_window":  # no window dispatched: nothing to divide by
        assert read({"spans": {span: durations}, "windows": 0}) is None


def _window_thread_spans(path: str) -> dict:
    """``{name: [seconds]}`` of the ``chipbench.*`` and ``rabia.*`` events on
    the thread that holds ``chipbench.window``, clipped to it: what
    ``chipbench.trace.reduce`` hands the readers, from a trace without a
    device plane (the CPU's), which that reducer refuses."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            named = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events if e.name.startswith(("chipbench.", "rabia."))]
            window = [x for x in named if x[0] == "chipbench.window"]
            if not window:
                continue
            _, w0, w1 = window[0]
            spans: dict = {}
            for name, a, b in named:
                if name != "chipbench.window" and b > w0 and a < w1:
                    spans.setdefault(name, []).append((min(b, w1) - max(a, w0)) * 1e-9)
            return spans
    raise AssertionError("no chipbench.window in the trace")


def test_rehearsal_of_s64_shapes_reads_all_seven(tmp_path):
    """``kv-r3-s64.ycsb-a-sat`` as the runner drives it (its own
    configuration: 64 shards x 3 replicas x 64 records, window 64), a
    measured window under the CPU's profiler: every one of the seven
    reads a number, every accepted span reader still does, and each
    parent's children add up to no more than the parent."""
    import glob

    import jax

    cell = spec.load_cell("kv-r3-s64.ycsb-a-sat")
    generator = gen.Generator(2**31 + 40, cell.config, cell.traffic)
    eng = run.build_engine(cell.config)
    runner = run.Runner(eng, generator, cell.traffic, True)
    runner.load()
    runner.warm_up()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        runner.measure(0.5)
    finally:
        jax.profiler.stop_trace()
    runner.drain()
    assert eng.device_lane_active
    eng.close()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans = _window_thread_spans(path)
    windows = runner.windows_measured
    assert windows >= 2
    ctx = {"spans": spans, "windows": windows, "blocks": runner.submitted}
    got = {name: cell.readers[name](ctx) for name in INSIDE}
    assert all(v is not None and v > 0 for v in got.values()), got
    for name in ("submit_us_per_block", "book_ms_per_window", "settle_host_ms_per_window",
                 "pack_ms_per_window", "cycle_unattributed_ms", "cycle_host_ms",
                 "window_waves_mean", "dispatch_ms_per_window"):
        assert cell.readers[name](ctx) is not None, name
    assert cell.readers["window_waves_mean"](ctx) == 64
    # the workers' spans lie on other threads: the window's thread has none
    assert not any(name.startswith("rabia.fetch.") for name in spans)
    total = {k: sum(v) for k, v in spans.items()}
    n = {k: len(v) for k, v in spans.items()}
    book = [f"rabia.cycle.book.{p}" for p in ("versions", "segment", "handoff")]
    assert {n[k] for k in book} == {n["rabia.cycle.book"]}
    assert sum(total[k] for k in book) <= total["rabia.cycle.book"]
    assert n["rabia.cycle.settle.blocks"] == n["rabia.cycle.settle"]
    inside = total["rabia.cycle.settle.blocks"] + total.get("rabia.cycle.settle.download", 0.0)
    assert inside <= total["rabia.cycle.settle"]
    assert n["rabia.submit.validate"] == n["rabia.submit.route"] == n["chipbench.submit"]
    both = total["rabia.submit.validate"] + total["rabia.submit.route"]
    assert both <= total["chipbench.submit"]
    # the kinds scan lies in what cycle_unattributed_ms reads, and leaves it there
    assert got["kinds_ms_per_window"] <= cell.readers["cycle_unattributed_ms"](ctx)

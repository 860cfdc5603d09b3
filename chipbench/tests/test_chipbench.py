"""Tests of the benchmark itself (CPU only; nothing here touches a TPU or
describes a topology, at import or later).

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

- the runner rehearsed at a tiny geometry, both loop kinds, through a
  throwaway cell that is added by new files only;
- each planted fault of ``control.py`` comes out ``correct: false``;
- the plain reference and the wire format against the program's own
  ``KVStore`` and codec (a second witness, never used by a run);
- the trace reducer against a small trace recorded on the chip;
- the bytes function and ``window_hbm_share`` against hand counts;
- a configuration's ``engine`` object: passed on to ``MeshEngine``, refused
  where it restates a shape, names no option or holds no scalar;
- ``BENCHMARK.json``: names, units, bounds, and every file found by name.
"""

from __future__ import annotations

import functools
import json
import re
from pathlib import Path

import numpy as np
import pytest

from chipbench import control, gen, peaks, run, spec, trace, wire
from chipbench.reference.kv_plain import PlainKV

REPO = spec.REPO_ROOT
DATA = Path(__file__).parent / "data"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

TINY_CONFIG = {
    "name": "tiny", "reference": "kv_plain", "n_shards": 8, "n_replicas": 3,
    "window": 4, "per_shard_capacity": 8, "key_bytes": 32, "value_bytes": 64,
}
TINY_TRAFFIC = {
    "tiny-closed": {
        "loop": "closed", "in_flight_windows": 3,
        "readproportion": 0.6, "updateproportion": 0.4,
        "requestdistribution": "zipfian", "zipfian_constant": 0.99,
        "pool_windows": 5, "warmup_windows": 4, "check_block_share": 1.0,
    },
    "tiny-open": {
        "loop": "open", "rate_ops": 4000, "batch_blocks": 4,
        "readproportion": 0.5, "updateproportion": 0.5,
        "requestdistribution": "zipfian", "zipfian_constant": 0.99,
        "pool_windows": 5, "warmup_windows": 4, "check_block_share": 1.0,
    },
}
# throwaway configurations that state an engine: the shapes above, a file each
TINY_ENGINES = {
    "tiny-depth2": {"device_store_inflight": 2},
    "tiny-governed": {"latency_target_ms": 50.0, "min_window": 2, "max_window": 4},
}
THROWAWAY_METRIC = '''"""Throwaway: blocks submitted per window dispatched, where the loop is
closed (a reader with nothing to read elsewhere returns nothing)."""


def read(ctx):
    if ctx["traffic"]["loop"] != "closed" or not ctx["windows"]:
        return None
    return ctx["blocks"] / ctx["windows"]
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    """A checkout-shaped directory whose ``chipbench`` is the real one
    (a link: nothing in it is edited) and whose throwaway configuration,
    traffic mixes, metric and cells are new files in a second directory plus
    new entries in ``BENCHMARK.json``."""
    root = tmp_path_factory.mktemp("root")
    (root / "chipbench").symlink_to(REPO / "chipbench", target_is_directory=True)
    for sub in ("configs", "traffic", "metrics"):
        (root / "extra" / sub).mkdir(parents=True)
    (root / "extra/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    for name, traffic in TINY_TRAFFIC.items():
        (root / f"extra/traffic/{name}.json").write_text(json.dumps(traffic))
    (root / "extra/metrics/blocks_per_window.py").write_text(THROWAWAY_METRIC)
    for name, engine in TINY_ENGINES.items():
        (root / f"extra/configs/{name}.json").write_text(
            json.dumps(dict(TINY_CONFIG, name=name, engine=engine))
        )
    bench = spec.load_benchmark(REPO)
    bench["paths"] = bench["paths"] + ["extra"]
    cells = [("tiny", t) for t in TINY_TRAFFIC] + [(c, "tiny-closed") for c in TINY_ENGINES]
    for config in ("tiny", *TINY_ENGINES):
        bench["configs"].append(
            {"name": config, "source": "test", "file": f"extra/configs/{config}.json",
             "reduced": [], "why": "throwaway"}
        )
    for config, traffic in cells:
        bench["workloads"].append(
            {"name": f"{config}.{traffic}", "config": config, "traffic": traffic,
             "chips": 1, "why": "throwaway"}
        )
    bench["per_layer"].append(
        {"name": "blocks_per_window", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "pipe", "moves": "committed_ops",
         "workloads": ["tiny.tiny-closed"]}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


# -- the runner, rehearsed ----------------------------------------------------


@pytest.mark.parametrize("traffic", sorted(TINY_TRAFFIC))
def test_rehearsal_prints_the_contract_line(root, traffic):
    result = run.run_cell(
        f"tiny.{traffic}", 2**31 + 11, 0.4, False, root=root, require_chip=False
    )
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {
        "committed_ops", "commit_p50_ms", "commit_p95_ms", "setup_s"
    }
    for m in line["metrics"].values():
        assert m["value"] > 0 and UNIT.match(m["unit"])
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["checks"]["replies_compared"]["value"] > 0
    assert all(c["ok"] for c in line["checks"].values())


@pytest.mark.parametrize("fault", control.FAULTS)
def test_planted_fault_is_not_correct(root, fault):
    result = run.run_cell(
        "tiny.tiny-closed", 7, 0.4, False, root=root, require_chip=False,
        engine_hook=control.plant(fault),
    )
    assert result["correct"] is False
    failed = {k for k, c in result["checks"].items() if not c["ok"]}
    expect = {
        "dropped_window": {"replica_mismatches"},
        "half_batch": {"replica_mismatches"},
        "answer_altered": {"reply_mismatches"},
        "lagging_replica": {"replica_mismatches"},
    }[fault]
    assert expect <= failed


# -- a configuration states its engine -----------------------------------------


class _Built(Exception):
    """Raised by the recorder in ``MeshEngine``'s place: nothing is built."""


@pytest.mark.parametrize(
    "name", ["kv-r5-s4096", "kv-r3-s64", "kv-r5-s16384", "kv-r5-s4096-p512"]
)
def test_committed_configuration_builds_the_engine_it_built_before(monkeypatch, name):
    """No committed file states an ``engine``: ``MeshEngine`` gets the factory
    and the six keywords that ``build_engine`` passed before the key existed,
    and nothing else."""
    import rabia_tpu.parallel as parallel

    calls = []

    @functools.wraps(parallel.MeshEngine)
    def recorder(*args, **kw):
        calls.append((args, kw))
        raise _Built

    monkeypatch.setattr(parallel, "MeshEngine", recorder)
    config = json.loads((REPO / f"chipbench/configs/{name}.json").read_text())
    with pytest.raises(_Built):
        run.build_engine(config)
    ((args, kw),) = calls
    assert len(args) == 1 and callable(args[0])
    mesh = kw.pop("mesh")
    assert dict(mesh.shape) == dict(parallel.make_mesh().shape)
    assert kw == {
        "n_shards": config["n_shards"], "n_replicas": config["n_replicas"],
        "window": config["window"], "device_store": True,
        "device_store_kw": {
            "per_shard_capacity": config["per_shard_capacity"],
            "key_lanes": config["key_bytes"] // 8,
            "value_width": config["value_bytes"],
        },
    }


def test_engine_option_reaches_the_engine_and_the_run_is_correct(root, capfd):
    seen = []
    result = run.run_cell(
        "tiny-depth2.tiny-closed", 2**31 + 13, 0.4, False, root=root,
        require_chip=False, engine_hook=lambda eng, runner: seen.append(eng),
    )
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["lane_faults"]["value"] == 0
    assert seen[0]._dev_inflight == 2 and seen[0].latency_target_ms is None
    assert 'chipbench: engine options {"device_store_inflight": 2}' in capfd.readouterr().err


def test_engine_options_turn_the_governor_on(root):
    cell = spec.load_cell("tiny-governed.tiny-closed", root)
    eng = run.build_engine(cell.config)
    try:
        assert eng.latency_target_ms == 50.0
        assert (eng.min_window, eng.max_window, eng.window) == (2, 4, 4)
        assert eng._dev_inflight == 1  # the engine's own default under a target
    finally:
        eng.close()


@pytest.mark.parametrize(
    "key, value",
    [
        ("window", 8),  # reserved: the file's own ``window`` is the shape
        ("device_store", False),
        ("device_read_path", True),  # no such option
        ("device_store_inflight", [2]),  # not a scalar
        ("latency_target_ms", 0),  # the engine refuses the value
    ],
)
def test_engine_object_is_refused_by_key(key, value):
    config = dict(TINY_CONFIG, engine={key: value})
    with pytest.raises(spec.SpecError) as refused:
        run.build_engine(config)
    assert key in str(refused.value) and "tiny" in str(refused.value)


def test_a_spec_error_exits_with_its_text_and_no_result(capsys):
    rc = run.main(["--workload", "no-such.cell", "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "Traceback" not in out.err
    assert "unknown workload 'no-such.cell'" in out.err


def test_no_chip_no_result(capsys):
    rc = run.main(
        ["--workload", "kv-r3-s64.ycsb-a-sat", "--seed", "1", "--seconds", "1"]
    )
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "chip" in out.err


def test_throwaway_cell_is_files_and_entries_only(root):
    cell = spec.load_cell("tiny.tiny-closed", root)
    assert cell.config["n_shards"] == 8 and cell.traffic["loop"] == "closed"
    assert "blocks_per_window" in cell.readers  # found by its name alone
    ctx = {"blocks": 8, "windows": 2, "traffic": cell.traffic}
    assert cell.readers["blocks_per_window"](ctx) == 4
    other = spec.load_cell("tiny.tiny-open", root)
    assert other.readers["blocks_per_window"](dict(ctx, traffic=other.traffic)) is None
    assert len(other.readers) == len(spec.load_benchmark(REPO)["per_layer"]) + 1


# -- generator, wire format, reference ---------------------------------------


def test_generator_is_the_seed(root):
    cell = spec.load_cell("tiny.tiny-closed", root)
    a = gen.Generator(2**31 + 5, cell.config, cell.traffic)
    b = gen.Generator(2**31 + 5, cell.config, cell.traffic)
    c = gen.Generator(2**31 + 6, cell.config, cell.traffic)
    wa, wb, wc = a.pool_waves(), b.pool_waves(), c.pool_waves()
    blocks_a = [a.block(*a.encode(w)) for w in wa]
    blocks_b = [b.block(*b.encode(w)) for w in wb]
    assert [x.data for x in blocks_a] == [x.data for x in blocks_b]
    assert [x.id for x in blocks_a] == [x.id for x in blocks_b]
    assert [x.data for x in blocks_a] != [c.block(*c.encode(w)).data for w in wc]
    keys = {a.key_bytes(s, j) for s in range(a.S) for j in range(a.n_keys)}
    assert len(keys) == a.S * a.n_keys  # distinct, so an index names a key
    assert all(8 <= len(k) <= 32 for k in keys)
    # a submit gets a block of its own, never an earlier block's objects
    data, sizes = a.encode(wa[0])
    one, two = a.block(data, sizes), a.block(data, sizes)
    assert one.id != two.id and one.data == two.data and one.data is not two.data
    assert not np.shares_memory(one.cmd_sizes, two.cmd_sizes)
    assert not np.shares_memory(one.shards, two.shards)


def test_requests_follow_the_zipfian_law_within_each_shard():
    """YCSB's law, P(rank i) ~ 1/i^0.99 over a shard's records: the hottest
    of 64 records takes 1/H(64, 0.99) = 20.7 % of its shard's requests, the
    ranks are scattered differently in every shard, and the kinds follow the
    proportions."""
    config = dict(TINY_CONFIG, n_shards=64, per_shard_capacity=64, window=8)
    traffic = dict(TINY_TRAFFIC["tiny-closed"], pool_windows=40,
                   readproportion=0.95, updateproportion=0.05)
    g = gen.Generator(5, config, traffic)
    waves = g.pool_waves()
    kid = np.stack([w.kid for w in waves])  # [waves, S]
    kind = np.stack([w.kind for w in waves])
    top_share = 1 / (1 / np.arange(1, 65) ** 0.99).sum()
    assert top_share == pytest.approx(0.2070, abs=1e-3)
    hottest, share = [], []
    for s in range(g.S):
        counts = np.bincount(kid[:, s], minlength=64)
        hottest.append(int(counts.argmax()))
        share.append(counts.max() / len(waves))
    assert np.mean(share) == pytest.approx(top_share, rel=0.05)
    assert len(set(hottest)) > 32  # scrambled: no one index is hot everywhere
    assert (kind == wire.SET).mean() == pytest.approx(0.05, abs=0.005)
    assert set(np.unique(kind)) == {wire.SET, wire.GET}
    with pytest.raises(ValueError):
        gen.Generator(5, config, dict(traffic, requestdistribution="latest")).pool_waves()


def _program_frame(kind, res):
    """The device lane's framing of a ``KVStore`` result."""
    from rabia_tpu.apps.kvstore import KVResultKind, _result_bin

    if kind == wire.SET:
        return _result_bin(0, res.version)
    if kind == wire.GET:
        if res.kind == KVResultKind.NotFound:
            return _result_bin(1, 0)
        return _result_bin(0, res.version, res.value)
    raise AssertionError(kind)


def test_reference_and_wire_agree_with_the_programs_store_and_codec(root):
    from rabia_tpu.apps.kvstore import (
        KVOperation, KVStore, encode_op_bin, encode_set_bin,
    )

    cell = spec.load_cell("tiny.tiny-closed", root)
    g = gen.Generator(99, cell.config, cell.traffic)
    ref = PlainKV(g.S, g.n_keys, g.VW)
    stores = [KVStore() for _ in range(g.S)]
    seen = set()
    # the transaction phase first on an empty table (reads that find nothing),
    # then the load phase and the transaction phase again
    for wave in g.pool_waves()[:8] + g.load_waves() + g.pool_waves():
        frames = ref.apply_wave(wave.kind, wave.kid, wave.vlen, wave.val, range(g.S))
        ops = []
        for s in range(g.S):
            kind = int(wave.kind[s])
            key = g.key_bytes(s, int(wave.kid[s])).decode()
            val = wave.val[s, : wave.vlen[s]].tobytes().decode()
            if kind == wire.SET:
                res = stores[s].set(key, val)
                ops.append(encode_set_bin(key, val))
            else:
                res = stores[s].get(key)
                ops.append(encode_op_bin(KVOperation.get(key)))
            assert frames[s] == _program_frame(kind, res)
            seen.add((kind, frames[s][0]))
        assert g.block(*g.encode(wave)).data == b"".join(ops)
    assert seen == {(wire.SET, wire.OK), (wire.GET, wire.OK), (wire.GET, wire.NOT_FOUND)}
    for s, store in enumerate(stores):
        assert ref.shard_version[s] == store.version
        assert int(ref.present[s].sum()) == len(store)


# -- the yardstick's arithmetic -----------------------------------------------


def test_window_bytes_hand_count():
    config = json.loads((REPO / "chipbench/configs/kv-r5-s4096.json").read_text())
    # per slot: used 1 + key 32 + key length 4 + version 4 + value 64 + value
    # length 4 = 109 B; 4096 x 256 slots; a 4 B version counter per shard
    table = 4096 * 256 * 109 + 4096 * 4
    assert table == 114_311_168 == peaks.table_bytes(config)
    # per op in: 2 + 2 + 32 + 64 = 100 B; per op out: 8 B of meta; 12 B flags
    ops = 64 * 4096
    assert peaks.window_bytes(config) == 2 * table + ops * 100 + ops * 8 + 12
    assert peaks.window_bytes(config) == 256_933_900
    assert peaks.hbm_peak("TPU v5 lite") == 819e9
    with pytest.raises(KeyError):
        peaks.hbm_peak("TPU v9")


@pytest.mark.parametrize(
    "busy_s, windows, share",
    [
        # 1,335,820 B at 819 GB/s = 1.631 us, over 28.5305 us of device a window
        (171_183e-9, 6, 1_335_820 / 819e9 / 28.5305e-6 * 100),
        (171_183e-9, 0, None),  # no window dispatched in the trace
        (0.0, 6, None),  # no device op in the window
    ],
)
def test_window_hbm_share_is_one_chips_share(busy_s, windows, share):
    """A chip's bytes over a chip's peak and a chip's time: the same trace
    read as four chips' (``busy_s`` is their mean) gives a quarter."""
    read = spec.load_cell("kv-r3-s64.ycsb-a-sat", REPO).readers["window_hbm_share"]
    config = json.loads((REPO / "chipbench/configs/kv-r3-s64.json").read_text())
    # 2 x (64 x 64 slots x 109 B + 64 x 4 B) + 4,096 ops x (100 + 8) B + 12 B
    assert peaks.window_bytes(config) == 2 * 446_720 + 4096 * 108 + 12 == 1_335_820

    def on(n_devices):
        return read({"trace": {"busy_s": busy_s, "n_devices": n_devices},
                     "windows": windows, "config": config,
                     "device_kind": "TPU v5 lite"})

    if share is None:
        assert on(1) is None and on(4) is None
    else:
        assert on(1) == pytest.approx(share, rel=1e-12)
        assert on(4) == on(1) / 4


def test_interval_arithmetic():
    s, e = trace._merge(np.array([5.0, 0.0, 1.0, 9.0]), np.array([7.0, 2.0, 4.0, 10.0]))
    assert s.tolist() == [0.0, 5.0, 9.0] and e.tolist() == [4.0, 7.0, 10.0]
    before = trace._busy_before(np.array([-1.0, 0.0, 3.0, 4.5, 6.0, 20.0]), s, e)
    assert before.tolist() == [0.0, 0.0, 3.0, 4.0, 5.0, 7.0]
    segs = trace._segments(
        [("chipbench.run_cycle", 2, 8), ("rabia.devkv.mixed_apply", 3, 5),
         ("chipbench.poll", 8, 9)], 0, 10,
    )
    assert segs == [
        (0, 2, "outside_spans"), (2, 3, "run_cycle_outside_dispatch"),
        (3, 5, "dispatch:rabia.devkv.mixed_apply"),
        (5, 8, "run_cycle_outside_dispatch"), (8, 9, "chipbench.poll"),
        (9, 10, "outside_spans"),
    ]
    assert trace.op_name("%while.55 = (s32[]{:T(128)}) while(...)") == "while.55"


def test_reducer_on_a_trace_recorded_on_the_chip():
    """``data/tiny_tpu_v5e.xplane.pb``: the tiny cell above, traced for
    33 ms on a TPU v5e (PR 26). The expected numbers were taken from the raw
    events by other means: the window span read directly, the busy time by
    painting every ``XLA Ops`` event onto a 1 ns timeline (171,183 ns set),
    the six windows counted on the ``XLA Modules`` line."""
    r = trace.reduce(str(DATA / "tiny_tpu_v5e.xplane.pb"))
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.033070319, abs=1e-9)
    assert r["busy_s"] == pytest.approx(171_183e-9, abs=1e-9)
    assert {k: len(v) for k, v in r["spans"].items()} == {
        "chipbench.run_cycle": 12, "chipbench.poll": 12,
        "chipbench.submit": 24, "rabia.devkv.mixed_apply": 6,
    }
    assert sum(r["spans"]["rabia.devkv.mixed_apply"]) == pytest.approx(0.020176878)
    assert r["device_ops"][0][0] == "while.56"
    assert r["device_ops"][0][1] == pytest.approx(99.693e-6)
    assert all(len(name) <= 64 for name, _ in r["device_ops"])
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"], abs=1e-9)
    assert max(gaps, key=gaps.get) == "dispatch:rabia.devkv.mixed_apply"
    # the readers on it, as the run feeds them
    ctx = {"trace": r, "spans": r["spans"], "windows": 6, "blocks": 24,
           "counters": {"settle_sum_s": 0.21, "settle_count": 6, "window_compiles": 0},
           "config": TINY_CONFIG, "device_kind": "TPU v5 lite"}
    cell = spec.load_cell("kv-r3-s64.ycsb-a-sat", REPO)
    got = {name: read(ctx) for name, read in cell.readers.items()}
    assert got["device_ms_per_window"] == pytest.approx(0.0285305)
    assert got["dispatch_ms_per_window"] == pytest.approx(3.362813)
    assert got["cycle_host_ms"] == pytest.approx((0.03035708 - 0.020176878) / 6 * 1e3)
    assert got["submit_us_per_block"] == pytest.approx(99.99833)
    assert got["settle_ms"] == pytest.approx(35.0) and got["window_compiles"] == 0
    least_s = peaks.window_bytes(TINY_CONFIG) / 819e9
    assert got["window_hbm_share"] == pytest.approx(least_s / 28.5305e-6 * 100)
    assert 0 < got["window_hbm_share"] < 100


def test_reducer_refuses_a_trace_without_a_device(tmp_path):
    with pytest.raises(trace.TraceError):
        trace.find_xplane(str(tmp_path))


# -- BENCHMARK.json --------------------------------------------------------------


def test_benchmark_json_names_units_and_files():
    bench = spec.load_benchmark(REPO)
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= bench["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e and "bound" not in m
    for c in bench["configs"]:
        held = json.loads((REPO / c["file"]).read_text())
        assert held["name"] == c["name"] and set(c["reduced"]) == set(held["reduced"])
        run.engine_options(held)  # an ``engine`` object, if the file states one
        assert all(NAME.match(k) for k in c["reduced"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200
        cell = spec.load_cell(w["name"], REPO)  # config, traffic, readers found
        assert {m["name"] for m in cell.end_to_end} == set(e2e)
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}
        assert cell.traffic["loop"] == "closed"  # no open-loop cell yet
        assert cell.traffic["source"].startswith("YCSB core workload")

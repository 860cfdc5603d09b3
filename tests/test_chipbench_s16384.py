"""The four-chip cell ``kv-r5-s16384.ycsb-b-sat-c64`` (PR 30): its data
files, its reply sample, its two readers, and a rehearsal of the cell at a
tiny size over four of the virtual CPU devices.

The rehearsal runs ``chipbench.run.run_cell`` unchanged on a checkout-shaped
directory whose ``chipbench`` is the real one and whose configuration is a
small copy of the cell's own (same widths, same guarantees, fewer shards);
the check against ``kv_plain`` and ``control.py``'s planted faults are the
benchmark's. Nothing here touches a TPU.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import pytest

from chipbench import control, gen, run, spec

REPO = spec.REPO_ROOT
CELL = "kv-r5-s16384.ycsb-b-sat-c64"
CONFIG = REPO / "chipbench/configs/kv-r5-s16384.json"
TRAFFIC = REPO / "chipbench/traffic/ycsb-b-sat-c64.json"
SEEDS = (792490177, 1, 2, 3, 2280000001)  # the first emptied PR 29's sample


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


# -- the data files ------------------------------------------------------------


def test_cell_loads_with_four_chips_and_every_reader():
    cell = spec.load_cell(CELL)
    assert cell.chips == 4
    assert cell.config["n_shards"] == 16384 and cell.config["n_replicas"] == 5
    assert cell.traffic["check_block_share"] == 1 / 64
    assert {"settle_download_ms_per_window", "pack_gather_ms_p95"} <= set(cell.readers)
    bench = spec.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}["kv-r5-s16384"]
    assert entry["source"] == cell.config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == list(cell.config["reduced"])
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [CELL]


def test_configuration_keeps_the_widths_and_guarantees_of_kv_r5_s4096():
    new, old = _json(CONFIG), _json(REPO / "chipbench/configs/kv-r5-s4096.json")
    for key in ("reference", "n_replicas", "window", "key_bytes", "value_bytes",
                "records_at_capacity", "guarantees"):
        assert new[key] == old[key], key
    assert new["n_shards"] * new["per_shard_capacity"] == new["records_at_capacity"]
    assert set(new["reduced"]) == {
        "replica_processes", "records_per_shard", "adaptive_batching",
        "transport", "key_skew",
    }
    for key in ("replica_processes", "adaptive_batching"):
        assert new["reduced"][key] == old["reduced"][key], key
    assert set(new["assumed"]) == set(old["assumed"])
    assert "4 chips" in new["mapping"] and "shard axis" in new["mapping"]


def test_traffic_is_ycsb_b_sat_with_a_denser_sample():
    new, old = _json(TRAFFIC), _json(REPO / "chipbench/traffic/ycsb-b-sat.json")
    assert set(new) == set(old)
    for key in set(old) - {"name", "what", "check_block_share", "departs"}:
        assert new[key] == old[key], key
    assert new["name"] == "ycsb-b-sat-c64"
    assert new["check_block_share"] == 0.015625 == 8 * old["check_block_share"]
    added = set(new["departs"]) - set(old["departs"])
    assert added == {"check_block_share"}
    for key, text in old["departs"].items():
        assert new["departs"][key] == text, key


@pytest.mark.parametrize("seed", SEEDS)
def test_sample_is_never_empty_in_a_traced_window(seed):
    """A traced run measures about the stream's blocks 700-1,700 (64 load
    waves, warm-up, under 1,000 blocks in 4 s). At 1/512 seed 792490177
    picks none of them (ledger, PR 29); at the cell's 1/64 every seed
    picks at least 3 of the first 300."""
    g = gen.Generator(seed, _json(CONFIG), _json(TRAFFIC))
    picked = g.sampler()
    assert sum(picked(i) for i in range(700, 1000)) >= 3
    if seed == SEEDS[0]:
        sparse = gen.Generator(
            seed, _json(CONFIG), dict(_json(TRAFFIC), check_block_share=1 / 512)
        ).sampler()
        assert [i for i in range(2000) if sparse(i)][:3] == [38, 1756, 1942]


def test_the_two_new_readers_read_their_spans_or_nothing():
    cell = spec.load_cell(CELL)
    ctx = {
        "windows": 4,
        "spans": {
            "rabia.cycle.settle.download": [0.010, 0.012, 0.011, 0.013],
            "rabia.cycle.pack.gather": [0.02] * 19 + [0.06],
        },
    }
    assert cell.readers["settle_download_ms_per_window"](ctx) == pytest.approx(11.5)
    assert cell.readers["pack_gather_ms_p95"](ctx) == pytest.approx(22.0)
    for name in ("settle_download_ms_per_window", "pack_gather_ms_p95"):
        assert cell.readers[name]({"windows": 4, "spans": {}}) is None


# -- the cell, rehearsed over four devices -----------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    """The repo's ``BENCHMARK.json`` and ``chipbench`` (a link), with the
    cell's configuration cut to 16 shards x 8 records (its file moved to a
    directory that is searched first) and its sample widened to a quarter
    of the few blocks a rehearsal settles."""
    root = tmp_path_factory.mktemp("root")
    (root / "chipbench").symlink_to(REPO / "chipbench", target_is_directory=True)
    for sub in ("configs", "traffic"):
        (root / "small" / sub).mkdir(parents=True)
    small = dict(_json(CONFIG), n_shards=16, per_shard_capacity=8, window=4,
                 records_at_capacity=128)
    (root / "small/configs/kv-r5-s16384.json").write_text(json.dumps(small))
    (root / "small/traffic/ycsb-b-sat-c64.json").write_text(
        json.dumps(dict(_json(TRAFFIC), check_block_share=0.25))
    )
    bench = spec.load_benchmark(REPO)
    bench["paths"] = ["small"] + bench["paths"]
    for c in bench["configs"]:
        if c["name"] == "kv-r5-s16384":
            c["file"] = "small/configs/kv-r5-s16384.json"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def four_devices(monkeypatch):
    devs = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: devs)
    return devs


def test_rehearsal_over_four_devices_is_correct(root, four_devices):
    seen = {}
    result = run.run_cell(
        CELL, SEEDS[0], 0.5, False, root=root, require_chip=False,
        engine_hook=lambda eng, runner: seen.update(eng=eng),
    )
    assert result["device"]["count"] == 4
    dev = seen["eng"]._dev
    assert dev.n_devices == 4 and dev.n_shards == 16
    assert len(dev.state[0].sharding.device_set) == 4  # the table is split
    assert result["correct"] is True and result["failed"] == 0
    checks = result["checks"]
    assert checks["replies_compared"]["value"] >= 3 * 16
    for name in ("reply_mismatches", "replica_mismatches", "lane_faults",
                 "unsettled_blocks"):
        assert checks[name]["value"] == 0, name
    assert result["window"]["window_compiles"] == 0
    # all five replica stores were rebuilt from the table by the final sync
    assert [len(sm.store) for sm in seen["eng"].sms] == [128] * 5
    assert seen["eng"].metrics.snapshot()["rabia_devkv_sync_rows_total"] == 128


@pytest.mark.parametrize("fault", control.FAULTS)
def test_planted_fault_reads_false_over_four_devices(root, four_devices, fault):
    result = run.run_cell(
        CELL, 7, 0.4, False, root=root, require_chip=False,
        engine_hook=control.plant(fault),
    )
    assert result["correct"] is False
    failed = {k for k, c in result["checks"].items() if not c["ok"]}
    expect = "reply_mismatches" if fault == "answer_altered" else "replica_mismatches"
    assert expect in failed

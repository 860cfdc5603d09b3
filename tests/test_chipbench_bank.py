"""The ``bank`` family's tests (``chipbench/tests/test_bank_family.py``),
collected here as they are so that tier-1 runs them, and the bank cell's
spans rehearsed under the CPU's profiler: a tiny ``bank-r7-s1024`` with its
crash, driven by the runner, in which the cell's new readers read a number
and the accepted readers read what ``BENCHMARK.json`` says they read there.
CPU only.
"""

import glob
import json

from chipbench import run, spec
from chipbench.tests.test_bank_family import *  # noqa: F401,F403
from chipbench.tests.test_bank_family import CELL, CONFIG, TINY, TRAFFIC, _json
from tests.test_chipbench_cells import _window_thread_spans


def test_configuration_and_entries():
    """``chipbench/tests/test_bank_family.py``'s test of the same name, with
    the place of the bank cell as it stands since a later cell was appended
    after it: right after ``kv-r3-s64.ycsb-b-sat``, and not necessarily
    last."""
    config = _json(CONFIG)
    bench = spec.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}["bank-r7-s1024"]
    assert entry["file"] == str(CONFIG.relative_to(spec.REPO_ROOT))
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == list(config["reduced"]) == ["replica_processes", "transport"]
    assert "no state transfer" in config["reduced"]["replica_processes"]
    assert (config["n_shards"], config["n_replicas"], config["window"]) == (1024, 7, 64)
    assert config["per_shard_capacity"] * config["n_shards"] == config[
        "records_at_capacity"] == 1_048_576
    assert (config["key_bytes"], config["value_bytes"]) == (32, 8)
    assert config["family"] == "bank" and config["reference"] == "bank_plain"
    assert {"initial_balances", "smallbank_mapping"} <= set(config["assumed"])
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["chips"] == cells["kv-r3-s64.ycsb-b-sat"]["chips"] == 1
    names = [w["name"] for w in bench["workloads"]]
    at = names.index(CELL)
    assert names[at - 1 : at + 1] == ["kv-r3-s64.ycsb-b-sat", CELL] and at >= 7
    cell = spec.load_cell(CELL)
    assert cell.family.__name__ == "chipbench_family_bank"
    assert cell.faults == ((0.25, "crash", (0, 1, 2)), (0.75, "heal", (0, 1, 2)))
    layer = {m["name"]: m for m in bench["per_layer"]}
    for name in ("bank_hbm_share", "bank_settle_ms_per_window", "degraded_cycle_ms"):
        assert layer[name]["workloads"] == [CELL]
        assert name in cell.readers


def test_rehearsal_of_the_bank_cell_reads_its_spans(tmp_path):
    import jax

    cell = spec.load_cell(CELL)
    config = dict(json.loads(CONFIG.read_text()), **TINY)
    traffic = dict(json.loads(TRAFFIC.read_text()), pool_windows=5, warmup_windows=4)
    generator = cell.family.Generator(2**31 + 43, config, traffic)
    eng = run.build_engine(config, cell.family)
    runner = run.Runner(eng, generator, traffic, True, cell.faults)
    runner.load()
    runner.warm_up()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        runner.measure(0.6)
    finally:
        jax.profiler.stop_trace()
    runner.strike("after")
    runner.drain()
    assert eng.device_lane_active and runner.down_range == (0, 3)
    eng.close()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans = _window_thread_spans(path)
    ctx = {"spans": spans, "windows": runner.windows_measured, "blocks": runner.submitted,
           "config": config, "traffic": traffic,
           "counters": {"settle_sum_s": 1.0, "settle_count": 1, "window_compiles": 0}}
    assert ctx["windows"] >= 2
    for name in ("bank_settle_ms_per_window", "degraded_cycle_ms", "dispatch_ms_per_window",
                 "settle_host_ms_per_window", "pack_ms_per_window", "book_ms_per_window",
                 "settle_blocks_ms_per_window", "kinds_ms_per_window", "cycle_host_ms"):
        assert cell.readers[name](ctx) > 0, name
    assert [k for k in spans if k.startswith("rabia.devkv.")] == ["rabia.devkv.bank_apply"]
    # the accepted readers that list their cells find nothing here, as listed
    bench = spec.load_benchmark()
    for m in bench["per_layer"]:
        if "workloads" in m and CELL not in m["workloads"] and m["source"] == "program_span":
            assert cell.readers[m["name"]](ctx) is None, m["name"]
    degraded = spans["rabia.cycle.degraded"]
    assert len(degraded) < len(spans["chipbench.run_cycle"])  # the middle half only

"""The configuration ``kv-r5-s4096-loss5`` (the north-star deployment under
upstream's lossy network), its op family ``kvnet``, its reference
``kvnet_plain`` and its cell ``kv-r5-s4096-loss5.ycsb-b-sat``:

- the data: the file keeps ``kv-r5-s4096.json``'s shapes to the letter and
  states its network, cuts and departure; the entries; the two readers on
  span tables;
- a tiny copy of the configuration (8 shards, window 4) through
  ``run.run_cell`` unchanged: correct, the decision check comparing slots,
  null slots and second phases decided on the device, no demotion;
- the check has teeth: a program that decides with full views, or with a
  coin keyed differently, is not correct;
- the cell's spans under the CPU's profiler: both new readers read a number
  and every accepted reader that lists no cells does too;
- a program whose engine takes no ``net_loss`` ends the run with
  ``SpecError``.

Nothing here touches a TPU.
"""

from __future__ import annotations

import glob
import inspect
import json
import statistics
from pathlib import Path

import numpy as np
import pytest

from chipbench import run, spec
from chipbench.families import kvnet
from tests.test_chipbench_cells import _window_thread_spans

REPO = spec.REPO_ROOT
CONFIG = REPO / "chipbench/configs/kv-r5-s4096-loss5.json"
BASE = REPO / "chipbench/configs/kv-r5-s4096.json"
TRAFFIC = REPO / "chipbench/traffic/ycsb-b-sat.json"
CELL = "kv-r5-s4096-loss5.ycsb-b-sat"
METRICS = ("consensus_phases_mean", "slot_book_ms_per_window")
TINY = {"name": "tiny-net", "n_shards": 8, "window": 4, "per_shard_capacity": 32,
        "records_at_capacity": 256}


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


# -- the data ------------------------------------------------------------------------


def test_configuration_keeps_the_shapes_of_kv_r5_s4096_and_states_its_network():
    new, old = _json(CONFIG), _json(BASE)
    for key in ("n_shards", "n_replicas", "window", "per_shard_capacity", "key_bytes",
                "value_bytes", "records_at_capacity"):
        assert new[key] == old[key], key
    assert (new["family"], new["reference"]) == ("kvnet", "kvnet_plain")
    assert new["engine"]["net_loss"] == 0.05 and isinstance(new["engine"]["seed"], int)
    assert list(new["reduced"]) == ["replica_processes", "records_per_shard",
                                    "adaptive_batching", "transport"]
    assert new["guarantees"][:5] == old["guarantees"]
    assert "weak-MVC" in new["guarantees"][5]
    assert {"seed", "arrival_order", "origin", "coin_p1", "stale_proposal"} <= set(
        new["assumed"])
    run.engine_options(new)  # every option is one MeshEngine takes
    bench = spec.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}[new["name"]]
    assert entry["source"] == new["source"] and len(entry["source"]) <= 200
    assert "lossy_network" in entry["source"] and "0.05" in entry["source"]
    assert entry["reduced"] == list(new["reduced"])
    sources = [c["source"] for c in bench["configs"]]
    assert len(set(sources)) == len(sources)


def test_entries_of_the_cell_and_its_readers():
    bench = spec.load_benchmark()
    assert bench["configs"][-1]["name"] == "kv-r5-s4096-loss5"
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "kv-r5-s4096-loss5", "traffic": "ycsb-b-sat", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
    layer = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(METRICS)
    assert layer["consensus_phases_mean"]["layer"] == "device window"
    assert layer["slot_book_ms_per_window"]["layer"] == "pack + resolve (host)"
    for name in METRICS:
        assert layer[name]["workloads"] == [CELL] and layer[name]["source"] == "program_span"
    cell = spec.load_cell(CELL)
    assert cell.family.__name__ == "chipbench_family_kvnet" and cell.faults == ()
    assert cell.traffic["check_block_share"] == 1 / 512 and cell.chips == 1
    assert set(METRICS) <= set(cell.readers)


def test_readers_on_span_tables():
    readers = {name: spec._load_reader(REPO / f"chipbench/metrics/{name}.py")
               for name in METRICS}
    spans = {"rabia.consensus.p7": [0.0] * 3, "rabia.consensus.p9": [0.0],
             "rabia.consensus.pX": [0.0], "rabia.cycle.book.slots": [0.001, 0.004, 0.002]}
    ctx = {"spans": spans, "windows": 4}
    assert readers["consensus_phases_mean"](ctx) == pytest.approx((7 * 3 + 9) / 4)
    assert readers["slot_book_ms_per_window"](ctx) == pytest.approx(2.0)
    parent = {"spans": {"rabia.cycle.book": [0.004] * 3, "rabia.window.w64": [0.0]},
              "windows": 3}
    assert {name: readers[name](parent) for name in METRICS} == dict.fromkeys(METRICS)


def test_validate_refuses_a_fault_schedule():
    traffic = _json(TRAFFIC)
    kvnet.validate(traffic)
    with pytest.raises(spec.SpecError, match="fault"):
        kvnet.validate(dict(traffic, faults=[{"at": 0.5, "crash": [0]},
                                             {"at": "after", "heal": [0]}]))


# -- the cell, rehearsed ----------------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    """A checkout-shaped directory: the real ``chipbench`` (a link) and a tiny
    copy of the configuration with its traffic, every block compared."""
    root = tmp_path_factory.mktemp("netroot")
    (root / "chipbench").symlink_to(REPO / "chipbench", target_is_directory=True)
    for sub in ("configs", "traffic"):
        (root / "extra" / sub).mkdir(parents=True)
    config = dict(_json(CONFIG), **TINY)
    (root / "extra/configs/tiny-net.json").write_text(json.dumps(config))
    traffic = dict(_json(TRAFFIC), name="tiny-b", check_block_share=1.0,
                   pool_windows=5, warmup_windows=4)
    (root / "extra/traffic/tiny-b.json").write_text(json.dumps(traffic))
    bench = spec.load_benchmark(REPO)
    bench["paths"] = bench["paths"] + ["extra"]
    bench["configs"].append({"name": "tiny-net", "source": "test",
                             "file": "extra/configs/tiny-net.json",
                             "reduced": list(config["reduced"]), "why": "throwaway"})
    bench["workloads"].append({"name": "tiny-net.tiny-b", "config": "tiny-net",
                               "traffic": "tiny-b", "chips": 1, "why": "throwaway"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_tiny_cell_is_correct_with_nulls_and_phases_decided_on_the_device(root):
    seen = {}
    result = run.run_cell("tiny-net.tiny-b", 2**31 + 45, 0.5, False, root=root,
                          require_chip=False,
                          engine_hook=lambda eng, runner: seen.update(eng=eng))
    eng = seen["eng"]
    assert result["correct"] is True and result["failed"] == 0
    checks = {k: c["value"] for k, c in result["checks"].items()}
    assert checks["replicas_compared"] == 5 and checks["replies_compared"] > 0
    assert checks["reply_mismatches"] == checks["replica_mismatches"] == 0
    assert checks["lane_faults"] == 0 and result["window"]["window_compiles"] == 0
    assert eng.device_lane_active and eng.net_loss == 0.05
    assert all(k[0] == "mixnet" for k in eng._dev._fused_cache)
    # nulls and second phases, decided on the device, booked on the host
    assert eng.decided_v0 > 0 and eng._net_phases[1:].sum() > 0
    assert eng._net_phases.sum() == eng.decided_v1 + eng.decided_v0
    assert int(eng.next_slot.sum()) == eng.decided_v1 + eng.decided_v0
    assert 0.05 < eng.decided_v0 / (eng.decided_v1 + eng.decided_v0) < 0.25
    snap = eng.metrics.snapshot()
    assert snap['rabia_mesh_slots_total{outcome="null"}'] == eng.decided_v0
    assert snap['rabia_mesh_slots_total{outcome="decided"}'] == eng.decided_v1
    assert snap["rabia_phases_to_decide_count"] == eng.decided_v1 + eng.decided_v0
    # the decision check itself compared slots of every shard (8 < 64)
    ref = kvnet.reference(dict(_json(CONFIG), **TINY), _Gen())
    ref.waves = eng.decided_v1 // 8
    compared, wrong, first = kvnet.decision_mismatches(eng, ref, _Gen())
    assert compared >= 8 and wrong == 0, first


class _Gen:
    """What the decision check reads of a generator."""

    S, n_keys, VW, seed = 8, 32, 64, 2**31 + 45


def _full_views(monkeypatch):
    from rabia_tpu.parallel import mesh

    real = mesh._net_views
    monkeypatch.setattr(mesh, "_net_views", lambda base, e, alive, q, loss, xp=None:
                        real(base, e, alive, alive.shape[0] + 1, loss))


def _coin_keyed_otherwise(monkeypatch):
    from rabia_tpu.parallel import mesh

    real = mesh._coin_bits
    monkeypatch.setattr(mesh, "_coin_bits", lambda seed, shard, slot, phase, p1, xp=None:
                        real(seed, shard, slot, phase + 1, p1))


@pytest.mark.parametrize("fault", (_full_views, _coin_keyed_otherwise))
def test_the_decision_check_has_teeth(root, monkeypatch, fault):
    fault(monkeypatch)
    result = run.run_cell("tiny-net.tiny-b", 2**31 + 47, 0.4, False, root=root,
                          require_chip=False)
    assert result["correct"] is False
    failed = {k for k, c in result["checks"].items() if not c["ok"]}
    assert failed == {"replica_mismatches"}  # the answers are the network's to keep


def test_rehearsal_of_the_cell_reads_its_spans(root, tmp_path):
    import jax

    cell = spec.load_cell("tiny-net.tiny-b", root)
    generator = cell.family.Generator(2**31 + 49, cell.config, cell.traffic)
    eng = run.build_engine(cell.config, cell.family)
    runner = run.Runner(eng, generator, cell.traffic, True, cell.faults)
    runner.load()
    runner.warm_up()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        runner.measure(0.6)
    finally:
        jax.profiler.stop_trace()
    runner.drain()
    eng.close()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans = _window_thread_spans(path)
    ctx = {"spans": spans, "windows": runner.windows_measured, "blocks": runner.submitted,
           "config": cell.config, "traffic": cell.traffic,
           "counters": {"settle_sum_s": 1.0, "settle_count": 1, "window_compiles": 0}}
    assert ctx["windows"] >= 2
    assert cell.readers["consensus_phases_mean"](ctx) >= 1
    assert cell.readers["slot_book_ms_per_window"](ctx) > 0
    markers = [k for k in spans if k.startswith("rabia.consensus.p")]
    assert markers and not any(k.startswith("rabia.devkv.") and k != "rabia.devkv.mixed_apply"
                               for k in spans)
    # every accepted span reader that lists no cells reads here too
    bench = spec.load_benchmark()
    for m in bench["per_layer"]:
        if "workloads" not in m and m["source"] == "program_span":
            assert cell.readers[m["name"]](ctx) is not None, m["name"]
    assert statistics.median(spans["rabia.cycle.book.slots"]) > 0


def test_an_engine_without_net_loss_ends_the_run_with_spec_error(root, monkeypatch):
    import rabia_tpu.parallel as parallel

    real = parallel.MeshEngine

    class Lossless(real):  # the engine's parameters as they were before net_loss
        def __init__(self, sm_factory, n_shards, n_replicas, mesh=None, *, window=16,
                     max_phases=4, coin_p1=0.5, seed=0, max_decision_history=4096,
                     device_store=False, device_store_kw=None, device_store_repromote=64,
                     device_store_inflight=None, device_read_lane=False,
                     latency_target_ms=None, min_window=1, max_window=256):
            raise AssertionError("never built")

    assert "net_loss" in inspect.signature(real).parameters
    monkeypatch.setattr(parallel, "MeshEngine", Lossless)
    with pytest.raises(spec.SpecError, match="net_loss"):
        run.run_cell("tiny-net.tiny-b", 2**31 + 51, 0.2, False, root=root,
                     require_chip=False)


def test_sampled_shards_come_from_the_seed():
    class G:
        S, seed = 4096, 2**31 + 45

    a, b = kvnet.sampled_shards(G), kvnet.sampled_shards(G)
    assert len(a) == kvnet.DECISION_SHARDS == 64 and (a == b).all()
    assert len(set(a.tolist())) == 64 and (np.diff(a) > 0).all()
    G.seed += 1
    assert (kvnet.sampled_shards(G) != a).any()

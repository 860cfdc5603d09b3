"""chip_smoke.py's legs at a tiny size on the CPU.

The on-chip smoke itself only runs through the chip tool (it refuses to
start without a TPU); these tests run the SAME leg functions on the
virtual CPU devices with the Pallas kernel in interpret mode, so the
reference comparison, the lane-never-demoted proof, the replica
equality check and the multi-device placement guard cannot rot between
chip runs. Also: the entry's no-TPU refusal and the compile-cache
helper's two branches.
"""

from __future__ import annotations

import hashlib
import re
from pathlib import Path

import jax
import pytest

import chip_smoke
from rabia_tpu.core import compile_cache
from rabia_tpu.parallel import make_mesh

REPO = Path(__file__).resolve().parent.parent
TINY = dict(n_shards=16, n_replicas=3, window=8, slots=8)


class TestEngineLeg:
    def test_matches_reference_on_one_device_and_on_a_four_device_mesh(self):
        """Every phase checked against the reference with the lane never
        demoted; then the >= 4-device branch on the virtual mesh: table
        and window operands split over the shard axis (placement check +
        the device-to-device transfer guard inside the leg), responses
        identical to the 1-device run's."""
        one = chip_smoke.engine_leg(
            11, mesh=make_mesh(jax.devices()[:1]), **TINY
        )
        assert [p["phase"] for p in one["phases"]] == [
            "load", "overwrite", "get", "get-warm", "mixed", "mixed-warm",
            "get-evicted", "read-lane-load", "read-lane", "read-lane-warm",
        ]
        assert one["devices"] == 1
        assert re.fullmatch(r"[0-9a-f]{64}", one["digest"])
        four = chip_smoke.engine_leg(
            11, mesh=make_mesh(jax.devices()[:4]), **TINY
        )
        assert four["placement"] == {"devices": 4, "rows_per_device": 4}
        assert four["digest"] == one["digest"]

    def test_a_wrong_response_is_fatal(self, monkeypatch):
        """The comparison has teeth: a reference that disagrees on one
        version fails the leg (nothing catches and continues)."""
        from rabia_tpu.apps import kvstore

        real_set = kvstore.KVStore.set
        calls = {"n": 0}

        def skewed(self, key, value):
            res = real_set(self, key, value)
            calls["n"] += 1
            if calls["n"] == 40:
                self._version += 1
            return res

        monkeypatch.setattr(kvstore.KVStore, "set", skewed)
        with pytest.raises(chip_smoke.SmokeFailure, match="reference"):
            chip_smoke.engine_leg(
                7, mesh=make_mesh(jax.devices()[:1]), **TINY
            )

    def test_a_demotion_is_fatal(self):
        """Work outside the lane's envelope (a key over the table's
        width) demotes to the host stores; the leg must refuse it."""
        wl = chip_smoke.Workload(3, 16, 8)
        wl.keys[5][2] = "k" * (chip_smoke.KEY_BYTES + 1)
        from rabia_tpu.apps.kvstore import KVStore
        from rabia_tpu.apps.vector_kv import VectorShardedKV
        from rabia_tpu.parallel import MeshEngine

        eng = MeshEngine(
            lambda: VectorShardedKV(16, capacity=1 << 10),
            n_shards=16, n_replicas=3, window=8, device_store=True,
            mesh=make_mesh(jax.devices()[:1]),
            device_store_kw={"per_shard_capacity": 8},
        )
        with pytest.raises(chip_smoke.SmokeFailure, match="demoted"):
            chip_smoke._run_phase(
                eng, "load", wl.load_waves(),
                [KVStore() for _ in range(16)], hashlib.sha256(), None,
            )


class TestKernelLeg:
    def test_four_kernels_agree_in_interpret_mode(self):
        obs = chip_smoke.kernel_leg(
            5, S=128, R=5, t_scan=64, t_i8=128, t_packed=256,
            t_ragged=77, interpret=True,
        )
        assert set(obs) == {"i8-depth", "ragged", "packed-depth"}


class TestEntry:
    def test_exits_nonzero_without_a_tpu_and_prints_no_result(self, capsys):
        assert jax.devices()[0].platform == "cpu"
        assert chip_smoke.main([]) != 0
        cap = capsys.readouterr()
        assert cap.out == ""
        assert "no TPU" in cap.err


class TestCompileCachePlacement:
    @pytest.fixture
    def updates(self, monkeypatch):
        calls: dict = {}
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: calls.__setitem__(k, v)
        )
        return calls

    def test_env_placed_dir_is_left_alone(self, monkeypatch, updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert compile_cache.place_compile_cache() == "/placed/outside"
        assert "jax_compilation_cache_dir" not in updates
        # short programs are cached in both branches
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
        assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1

    def test_unset_uses_the_fixed_dir_in_the_checkout(
        self, monkeypatch, updates
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        placed = compile_cache.place_compile_cache()
        assert placed == str(REPO / ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == placed
        assert compile_cache.place_compile_cache() == placed  # never moves
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()

    def test_no_other_code_path_sets_a_cache_dir(self):
        writers = [
            str(p.relative_to(REPO))
            for p in REPO.rglob("*.py")
            if ".jax_cache" not in p.parts
            and "build" not in p.parts
            and p.parent.name != "tests"
            and "jax_compilation_cache_dir" in p.read_text(errors="ignore")
        ]
        assert writers == ["rabia_tpu/core/compile_cache.py"]

"""Device-plane read-index lane: correctness gates for off-consensus GETs.

The round-17 lane lets full-width GET blocks skim out of the consensus
stream at submit time and serve from consensus-free ``lookup_only``
probe windows — zero slots, zero collectives. The price of skipping
consensus is paid with a write BARRIER: a probe read only becomes
eligible once every full-width write block staged before it has
dispatched, so read-your-writes holds; a probe read may legally observe
writes submitted AFTER it that dispatched before its probe window ran
(invocation/response concurrency — both orders are linearizable).

Gates here:

- probe results conform to the consensus GET window (flushed stream:
  byte-identical frames, lane on vs off);
- zero consensus slots consumed by probe-served GETs;
- read-your-writes through the barrier while SET windows are still
  in flight (GET racing a pipelined SET window);
- monotone versions under interleaving (no time travel);
- value-segment eviction falls back to the slot/download path and
  counts it;
- demotion mid-probe flushes parked reads to the host path (correct
  answers, stats coherent) and the lane re-engages after repromote;
- the jaxpr collective census: ``lookup_only`` traces collective-free
  while the consensus window does not (benchmarks/ici_model.py).
"""

from __future__ import annotations

import numpy as np
import pytest

from rabia_tpu.apps.kvstore import (
    KVOperation,
    KVOpType,
    decode_result_bin,
    encode_op_bin,
    encode_set_bin,
)
from rabia_tpu.apps.vector_kv import VectorShardedKV
from rabia_tpu.core.blocks import build_block
from rabia_tpu.parallel import MeshEngine, make_mesh

N_SHARDS = 8


def make_engine(read_lane: bool, **kw) -> MeshEngine:
    return MeshEngine(
        lambda: VectorShardedKV(N_SHARDS, capacity=1 << 12),
        n_shards=N_SHARDS,
        n_replicas=3,
        mesh=make_mesh(),
        window=4,
        device_store=True,
        device_read_lane=read_lane,
        **kw,
    )


def set_block(tag: str, val: str):
    shards = list(range(N_SHARDS))
    return build_block(
        shards, [[encode_set_bin(f"{tag}-{s}", val)] for s in shards]
    )


def get_block(tag: str):
    shards = list(range(N_SHARDS))
    return build_block(
        shards,
        [
            [encode_op_bin(KVOperation(KVOpType.Get, f"{tag}-{s}"))]
            for s in shards
        ],
    )


def get_frames(bfut) -> list[bytes]:
    """Per-shard first-response frames of a settled block future."""
    return [bytes(r[0]) for r in bfut.result()]


@pytest.mark.parametrize("read_lane", [False, True])
class TestReadLaneConformance:
    def test_flushed_stream_byte_identical(self, read_lane):
        """With a flush between operations the probe path must frame
        byte-identically to the consensus GET window (and to the host
        store): found/version/value and the miss shape."""
        eng = make_engine(read_lane)
        try:
            eng.submit_block(set_block("a", "v1"))
            eng.flush(max_cycles=200)
            hit = eng.submit_block(get_block("a"))
            miss = eng.submit_block(get_block("nope"))
            eng.flush(max_cycles=200)
            eng.sync_to_host()  # device table is authoritative; pull it down
            for s, frame in enumerate(get_frames(hit)):
                res = decode_result_bin(frame)
                assert res.value == "v1", (s, frame)
                host = eng.sms[0].store.get(s, f"a-{s}".encode())
                assert host is not None
                assert res.version == host[1]
            for frame in get_frames(miss):
                assert decode_result_bin(frame).value is None
        finally:
            eng.close()

    def test_zero_slots_for_probe_reads(self, read_lane):
        """Lane on: GET blocks consume ZERO consensus slots (decided_v1
        frozen); lane off: every GET costs a slot."""
        eng = make_engine(read_lane)
        try:
            eng.submit_block(set_block("z", "v"))
            eng.flush(max_cycles=200)
            before = eng.decided_v1
            for _ in range(3):
                eng.submit_block(get_block("z"))
            eng.flush(max_cycles=200)
            slots = eng.decided_v1 - before
            rl = eng.read_lane_stats()
            if read_lane:
                assert slots == 0
                assert rl["probe"] == 3 * N_SHARDS
                assert rl["probe_windows"] >= 1
            else:
                assert slots == 3 * N_SHARDS
                assert rl["probe"] == 0
        finally:
            eng.close()

    def test_get_racing_inflight_set_window(self, read_lane):
        """Read-your-writes through the barrier: GETs submitted AFTER a
        SET block (no flush in between — the SET window is still in
        flight, possibly pipelined) must observe that SET or a later
        one, never the pre-SET value."""
        eng = make_engine(read_lane)
        try:
            eng.submit_block(set_block("r", "old"))
            eng.flush(max_cycles=200)
            futs = []
            for gen in range(4):
                eng.submit_block(set_block("r", f"new{gen}"))
                futs.append((gen, eng.submit_block(get_block("r"))))
            eng.flush(max_cycles=400)
            for gen, fut in futs:
                for frame in get_frames(fut):
                    res = decode_result_bin(frame)
                    # barrier: the write staged before this GET has
                    # dispatched before its probe runs — "old" (or any
                    # EARLIER generation) is a read-your-writes hole
                    assert res.value in {
                        f"new{g}" for g in range(gen, 4)
                    }, (gen, res)
        finally:
            eng.close()

    def test_interleaved_versions_monotone(self, read_lane):
        """Versions observed by a GET stream interleaved with SETs never
        go backwards (no time travel), and each is a version the host
        mirror actually assigned."""
        eng = make_engine(read_lane)
        try:
            futs = []
            for gen in range(6):
                eng.submit_block(set_block("m", f"g{gen}"))
                futs.append(eng.submit_block(get_block("m")))
            eng.flush(max_cycles=400)
            eng.sync_to_host()
            final = {
                s: eng.sms[0].store.get(s, f"m-{s}".encode())[1]
                for s in range(N_SHARDS)
            }
            last = [0] * N_SHARDS
            for gen, fut in enumerate(futs):
                for s, frame in enumerate(get_frames(fut)):
                    res = decode_result_bin(frame)
                    assert res.value is not None, (gen, s)
                    ver = res.version
                    assert last[s] <= ver <= final[s], (gen, s, ver)
                    last[s] = ver
        finally:
            eng.close()


class TestReadLaneEdges:
    def test_eviction_fallback_counts_and_serves(self):
        """Probe-found values whose segment was evicted resolve through
        the value-download fallback: correct bytes, and the fallback
        counter records the event."""
        eng = make_engine(True)
        try:
            eng.submit_block(set_block("e", "keepme"))
            eng.flush(max_cycles=200)
            # force the eviction edge the way _dev_evict_segments does:
            # drop every retained value segment (raising the floor) and
            # empty the seed index, so the resolvability check fails and
            # the window must download its value planes
            while eng._dev_vseg:
                old = eng._dev_vseg.popleft()
                eng._dev_vseg_bytes -= old.nbytes
                np.maximum(eng._dev_floor, old.end, out=eng._dev_floor)
            eng._dev_seed_keys = eng._dev_seed_keys[:0]
            fut = eng.submit_block(get_block("e"))
            eng.flush(max_cycles=200)
            for frame in get_frames(fut):
                assert decode_result_bin(frame).value == "keepme"
            rl = eng.read_lane_stats()
            assert rl["fallback"] >= N_SHARDS
            assert rl["probe"] == N_SHARDS  # still served off-consensus
        finally:
            eng.close()

    def test_demotion_mid_probe_flushes_parked_reads(self):
        """Parked probe reads survive a forced demotion: they re-enter
        the consensus stream at the host path and answer correctly;
        the lane re-engages after the repromote horizon with working
        barriers."""
        eng = make_engine(True, device_store_repromote=4)
        try:
            eng.submit_block(set_block("d", "v0"))
            eng.flush(max_cycles=200)
            # park reads behind a staged (undispatched) write, then
            # demote before any probe window runs
            eng.submit_block(set_block("d", "v1"))
            parked = eng.submit_block(get_block("d"))
            eng._demote_device_store()
            assert not eng._dev_active
            eng.flush(max_cycles=200)
            for frame in get_frames(parked):
                # staged write dispatched before the flushed read: the
                # host path must serve v1 (read-your-writes preserved
                # across the demotion)
                assert decode_result_bin(frame).value == "v1"
            rl = eng.read_lane_stats()
            assert rl["probe"] == 0  # never probe-served
            # climb back: clean full-width windows re-promote the lane
            for i in range(8):
                eng.submit_block(set_block("d", f"v{i + 2}"))
                eng.flush(max_cycles=200)
            assert eng._dev_active
            fut = eng.submit_block(get_block("d"))
            eng.flush(max_cycles=200)
            for frame in get_frames(fut):
                assert decode_result_bin(frame).value == "v9"
            assert eng.read_lane_stats()["probe"] == N_SHARDS
        finally:
            eng.close()

    def test_probe_reads_survive_replica_crash(self):
        """A minority crash does not wedge or corrupt the probe path:
        reads keep serving off-consensus against the device table."""
        eng = make_engine(True)
        try:
            eng.submit_block(set_block("c", "alive"))
            eng.flush(max_cycles=200)
            eng.crash_replica(2)
            eng.submit_block(set_block("c", "alive2"))
            fut = eng.submit_block(get_block("c"))
            eng.flush(max_cycles=400)
            for frame in get_frames(fut):
                assert decode_result_bin(frame).value == "alive2"
            assert eng.read_lane_stats()["probe"] == N_SHARDS
            eng.heal_replica(2)
        finally:
            eng.close()


class TestCollectiveCensus:
    def test_probe_window_traces_collective_free(self):
        """The jaxpr census (benchmarks/ici_model.py): the consensus GET
        window carries replica-axis all_gathers; ``lookup_only`` must
        carry NONE — the structural fact the multi-chip scaling model
        stands on."""
        from benchmarks.ici_model import census

        c = census(n_shards=8, n_replicas=3, W=4, max_phases=4)
        assert c["probe_is_collective_free"], c["programs"]
        assert c["programs"]["probe_window_lookup_only"] == {}
        assert (
            c["programs"]["consensus_get_window"].get("all_gather", 0) >= 2
        )
        assert c["executed_per_window"]["consensus_get_window"] == 2 * 4 * 4
        assert c["executed_per_window"]["probe_window_lookup_only"] == 0

    def test_census_raises_when_it_cannot_recurse(self, monkeypatch):
        """A census that finds no sub-jaxprs must fail, not report zero
        collectives (the jax 0.9 regression: Jaxpr/ClosedJaxpr left
        ``jax.core``, the walker recursed into nothing, and every
        program read as collective-free)."""
        import jax.numpy as jnp
        from jax import lax

        from benchmarks import ici_model

        def scanned(x):
            return lax.scan(lambda c, _: (c + 1, ()), x, None, length=3)[0]

        assert ici_model.count_collectives(scanned, jnp.zeros(2)) == {}
        monkeypatch.setattr(ici_model, "ClosedJaxpr", type("Gone", (), {}))
        monkeypatch.setattr(ici_model, "Jaxpr", type("Gone", (), {}))
        with pytest.raises(TypeError, match="cannot recurse"):
            ici_model.count_collectives(scanned, jnp.zeros(2))

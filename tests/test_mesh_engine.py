"""MeshEngine: the full SMR stack on the device plane (SURVEY.md §5.8),
engine-level conformance-gated against the transport engine (§7.4.6).

The gate: the same submission schedule through (a) a 3-replica
RabiaEngine cluster over in-memory transports and (b) a MeshEngine with
MeshPhaseKernel as its consensus core must produce bit-identical decided
values per (shard, slot), the same per-shard applied command sequence,
and byte-identical replica state snapshots.
"""

from __future__ import annotations

import numpy as np
import pytest

from rabia_tpu.core.errors import RabiaError
from rabia_tpu.core.state_machine import InMemoryStateMachine
from rabia_tpu.core.types import V1
from rabia_tpu.parallel import MeshEngine, make_mesh


def _mesh():
    return make_mesh(shard_axis_size=2, replica_axis_size=4)


class TestMeshEngineBasics:
    def test_commit_settle_replicate(self):
        eng = MeshEngine(
            InMemoryStateMachine, n_shards=4, n_replicas=4, mesh=_mesh(),
            window=4,
        )
        futs = [
            eng.submit([f"SET k{i} v{i}"], shard=i % 4) for i in range(10)
        ]
        assert eng.flush() == 10
        assert all(f.result() == [b"OK"] for f in futs)
        # replica-state equality IS the replication test
        snaps = [sm.create_snapshot().data for sm in eng.sms]
        assert all(s == snaps[0] for s in snaps)
        assert eng.sms[0].get("k7") == "v7"
        assert eng.decided_v1 == 10

    def test_decision_log_values(self):
        eng = MeshEngine(
            InMemoryStateMachine, n_shards=2, n_replicas=4, mesh=_mesh(),
            window=2,
        )
        eng.submit(["SET a 1"], 0)
        eng.submit(["SET b 2"], 0)
        eng.submit(["SET c 3"], 1)
        eng.flush()
        d0 = eng.decisions_for(0)
        assert sorted(d0) == [0, 1]
        assert all(v == V1 for v, _ in d0.values())
        assert [c.data for c in d0[0][1].commands] == [b"SET a 1"]

    def test_minority_crash_commits_majority_crash_stalls(self):
        eng = MeshEngine(
            InMemoryStateMachine, n_shards=2, n_replicas=4, mesh=_mesh(),
            window=2,
        )
        eng.crash_replica(3)
        f = eng.submit(["SET x 1"], 0)
        eng.flush()
        assert f.result() == [b"OK"]
        # crash a second replica: 2/4 live < quorum(3) -> stall, then heal
        eng.crash_replica(2)
        assert not eng.has_quorum
        g = eng.submit(["SET y 2"], 1)
        with pytest.raises(RabiaError):
            eng.flush(max_cycles=3)
        assert not g.done()
        eng.heal_replica(2)
        eng.flush()
        assert g.result() == [b"OK"]
        # crashed replica 3's SM missed nothing: colocated apply covers all
        # replicas (state divergence modeling is the transport plane's job)

    def test_apply_failure_fails_future_not_engine(self):
        class Exploding(InMemoryStateMachine):
            def apply_command(self, command):
                if b"BOOM" in command.data:
                    raise RuntimeError("boom")
                return super().apply_command(command)

        eng = MeshEngine(
            Exploding, n_shards=1, n_replicas=4, mesh=_mesh(), window=2
        )
        bad = eng.submit(["BOOM"], 0)
        good = eng.submit(["SET a 1"], 0)
        eng.flush()
        with pytest.raises(RabiaError):
            bad.result()
        assert good.result() == [b"OK"]


    def test_vector_bulk_apply_matches_scalar_path(self):
        # same submissions through the bulk (apply_block) and scalar
        # (apply_batch) paths of the same SM type must land in identical
        # store state and identical responses
        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.apps.vector_kv import VectorShardedKV

        def run(force_scalar):
            eng = MeshEngine(
                lambda: VectorShardedKV(4, capacity=1 << 10),
                n_shards=4, n_replicas=4, mesh=_mesh(), window=4,
            )
            assert eng._vector  # VectorShardedKV implements apply_block
            if force_scalar:
                eng._vector = False
            futs = [
                eng.submit([encode_set_bin(f"k{i}", f"v{i}")], shard=i % 4)
                for i in range(12)
            ]
            eng.flush()
            return eng, [f.result() for f in futs]

        bulk_eng, bulk_res = run(force_scalar=False)
        scalar_eng, scalar_res = run(force_scalar=True)
        assert bulk_res == scalar_res
        # logical state equality (snapshot BYTES may differ: the open-
        # addressing table layout depends on insertion interleaving, which
        # legitimately differs between the bulk and scalar paths)
        for i in range(12):
            b = bulk_eng.sms[0].store.get(i % 4, f"k{i}".encode())
            s = scalar_eng.sms[0].store.get(i % 4, f"k{i}".encode())
            assert b is not None and s is not None
            assert b[0] == s[0] == f"v{i}".encode()
            assert b[1] == s[1]  # per-shard version counters agree
        # every replica of the bulk engine holds the same values/versions
        # (snapshot bytes embed wall-clock entry timestamps, so logical
        # comparison is the right replication check for this store)
        for i in range(12):
            vals = {
                sm.store.get(i % 4, f"k{i}".encode()) for sm in bulk_eng.sms
            }
            assert len(vals) == 1

    def test_block_lane_commits_with_zero_repacking(self):
        # submitted PayloadBlocks apply directly (no rebuild); results
        # match the scalar path on the same columnar store
        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.apps.vector_kv import VectorShardedKV
        from rabia_tpu.core.blocks import build_block

        S = 4
        eng = MeshEngine(
            lambda: VectorShardedKV(S, capacity=1 << 10),
            n_shards=S, n_replicas=4, mesh=_mesh(), window=2,
        )
        blk1 = build_block(
            list(range(S)),
            [[encode_set_bin(f"a{s}", f"x{s}")] for s in range(S)],
        )
        blk2 = build_block(
            [0, 2],
            [[encode_set_bin("b0", "y0"), encode_set_bin("b0b", "y0b")],
             [encode_set_bin("b2", "y2")]],
        )
        f1 = eng.submit_block(blk1)
        f2 = eng.submit_block(blk2)
        assert eng.flush() == S + 2
        r1, r2 = f1.result(), f2.result()
        assert len(r1) == S and all(len(e) == 1 for e in r1)
        assert len(r2[0]) == 2 and len(r2[1]) == 1
        for s in range(S):
            assert eng.sms[0].store.get(s, f"a{s}".encode())[0] == f"x{s}".encode()
        assert eng.sms[2].store.get(0, b"b0b")[0] == b"y0b"
        # mixed lanes in one window: scalar + block entries coexist
        g = eng.submit([encode_set_bin("c", "z")], shard=1)
        f3 = eng.submit_block(build_block([0], [[encode_set_bin("d", "w")]]))
        eng.flush()
        assert len(g.result()) == 1 and len(f3.result()) == 1

    def test_block_lane_decision_log_materializes(self):
        # block-lane commits must be recoverable from the decision log
        # (V1 with None is reserved for null slots)
        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.apps.vector_kv import VectorShardedKV
        from rabia_tpu.core.blocks import build_block

        eng = MeshEngine(
            lambda: VectorShardedKV(2, capacity=1 << 10),
            n_shards=2, n_replicas=4, mesh=_mesh(), window=2,
        )
        op = encode_set_bin("k", "v")
        eng.submit_block(build_block([0, 1], [[op], [op]]))
        eng.flush()
        v, batch = eng.decisions_for(0)[0]
        assert v == V1 and batch is not None
        assert [c.data for c in batch.commands] == [op]

    def test_deterministic_apply_failure_is_not_divergence(self):
        # all replicas rejecting a batch identically is an app error, not
        # replica divergence — on BOTH apply paths
        class Rejecting(InMemoryStateMachine):
            def apply_command(self, command):
                raise RuntimeError("nope")

            def apply_block(self, block, idxs, want_responses=True):
                raise RuntimeError("nope")

        from rabia_tpu.core.errors import RabiaError

        for vector in (False, True):
            eng = MeshEngine(
                Rejecting, n_shards=1, n_replicas=4, mesh=_mesh(), window=2
            )
            eng._vector = vector
            f = eng.submit(["X"], 0)
            eng.flush()
            with pytest.raises(RabiaError):
                f.result()
            assert eng.divergences == 0, f"vector={vector}"

    def test_fullwidth_fast_lane_survives_quorum_loss(self):
        # the vectorized full-width lane must demote cleanly when a wave
        # can't decide (quorum lost), park, and commit after heal
        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.apps.vector_kv import VectorShardedKV
        from rabia_tpu.core.blocks import build_block

        S = 2
        eng = MeshEngine(
            lambda: VectorShardedKV(S, capacity=1 << 10),
            n_shards=S, n_replicas=4, mesh=_mesh(), window=4,
        )
        mk = lambda i: build_block(
            [0, 1],
            [[encode_set_bin(f"a{i}", f"x{i}")],
             [encode_set_bin(f"b{i}", f"y{i}")]],
        )
        # minority crash: fast lane still decides V1 everywhere
        eng.crash_replica(3)
        f0 = eng.submit_block(mk(0))
        assert eng.flush() == S
        assert f0.done()
        # majority crash: waves go ABSENT -> demote -> park
        eng.crash_replica(2)
        futs = [eng.submit_block(mk(i)) for i in range(1, 4)]
        with pytest.raises(RabiaError):
            eng.flush(max_cycles=3)
        assert not any(f.done() for f in futs)
        eng.heal_replica(2)
        eng.flush()
        assert all(f.done() for f in futs)
        for i in range(4):
            got = eng.sms[0].store.get(0, f"a{i}".encode())
            assert got is not None and got[0] == f"x{i}".encode()
        # slot ordering preserved across the demotion
        log = eng.decisions_for(0)
        assert sorted(log) == [0, 1, 2, 3]

    def test_replica0_only_failure_counts_divergence_on_bulk_path(self):
        # replica 0 rejects, followers apply: their state mutated alone —
        # genuine divergence, must be counted on the bulk path too
        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.apps.vector_kv import VectorShardedKV
        from rabia_tpu.core.blocks import build_block
        from rabia_tpu.core.errors import RabiaError

        made = []

        def factory():
            class MaybeReject(VectorShardedKV):
                def apply_block(self, block, idxs, want_responses=True):
                    if made and self is made[0]:
                        raise RuntimeError("replica 0 only")
                    return super().apply_block(block, idxs, want_responses)

            sm = MaybeReject(2, capacity=1 << 10)
            made.append(sm)
            return sm

        eng = MeshEngine(factory, n_shards=2, n_replicas=4, mesh=_mesh(),
                         window=2)
        op = encode_set_bin("k", "v")
        f = eng.submit_block(build_block([0, 1], [[op], [op]]))
        eng.flush()
        assert eng.divergences == 3  # every follower diverged from replica 0
        assert all(isinstance(r, RabiaError) for r in f.result())

    def test_duplicate_shard_block_rejected(self):
        from rabia_tpu.core.blocks import PayloadBlock
        import uuid

        eng = MeshEngine(
            InMemoryStateMachine, n_shards=2, n_replicas=4, mesh=_mesh(),
            window=2,
        )
        blk = PayloadBlock(
            uuid.uuid4(),
            np.array([0, 0]),
            np.array([-1, -1]),
            np.array([1, 1]),
            np.array([1, 1]),
            b"XY",
        )
        from rabia_tpu.core.errors import ValidationError

        with pytest.raises(ValidationError, match="unique"):
            eng.submit_block(blk)

    def test_block_lane_scalar_sm_materializes(self):
        # a non-vector SM still commits block submissions (per-batch
        # materialization fallback)
        from rabia_tpu.core.blocks import build_block

        eng = MeshEngine(
            InMemoryStateMachine, n_shards=2, n_replicas=4, mesh=_mesh(),
            window=2,
        )
        f = eng.submit_block(
            build_block([0, 1], [[b"SET m 1"], [b"SET n 2"]])
        )
        eng.flush()
        assert f.result() == [[b"OK"], [b"OK"]]
        assert all(sm.get("m") == "1" and sm.get("n") == "2" for sm in eng.sms)

    def test_empty_batch_on_vector_path_does_not_poison_wave(self):
        # regression: an empty batch (legal no-op commit) cannot ride a
        # PayloadBlock; it must fall back to scalar apply without
        # orphaning the rest of the wave
        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.apps.vector_kv import VectorShardedKV

        eng = MeshEngine(
            lambda: VectorShardedKV(2, capacity=1 << 10),
            n_shards=2, n_replicas=4, mesh=_mesh(), window=2,
        )
        empty = eng.submit([], shard=0)
        full = eng.submit([encode_set_bin("k", "v")], shard=1)
        eng.flush()
        assert empty.result() == []
        assert len(full.result()) == 1
        assert eng.sms[0].store.get(1, b"k") is not None
        assert eng.divergences == 0

    def test_checkpoint_restore_resumes_slots(self):
        eng = MeshEngine(
            InMemoryStateMachine, n_shards=2, n_replicas=4, mesh=_mesh(),
            window=2,
        )
        for i in range(4):
            eng.submit([f"SET a{i} v{i}"], shard=i % 2)
        eng.flush()
        ckpt = eng.checkpoint()

        fresh = MeshEngine(
            InMemoryStateMachine, n_shards=2, n_replicas=4, mesh=_mesh(),
            window=2,
        )
        fresh.restore(ckpt)
        assert list(fresh.next_slot) == list(eng.next_slot)
        assert all(sm.get("a3") == "v3" for sm in fresh.sms)
        # resumed engine keeps committing at the next slot numbers
        f = fresh.submit(["SET after restore"], 0)
        fresh.flush()
        assert f.result() == [b"OK"]
        assert 2 in fresh.decisions_for(0)  # slots 0,1 were pre-checkpoint

    def test_decision_log_trims_to_history_cap(self):
        eng = MeshEngine(
            InMemoryStateMachine, n_shards=1, n_replicas=4, mesh=_mesh(),
            window=2, max_decision_history=3,
        )
        for i in range(9):
            eng.submit([f"SET k{i} v"], 0)
        eng.flush()
        d = eng.decisions_for(0)
        assert len(d) == 3
        assert sorted(d) == [6, 7, 8]  # oldest trimmed

    def test_replica_divergence_detected(self):
        # a non-deterministic SM (outcome differs per replica) must be
        # surfaced, not silently absorbed by replica 0's response
        made = []

        def factory():
            class Tagged(InMemoryStateMachine):
                def apply_command(self, command):
                    if len(made) > 1 and self is made[1]:
                        return b"DIVERGED"
                    return super().apply_command(command)

            sm = Tagged()
            made.append(sm)
            return sm

        eng = MeshEngine(factory, n_shards=1, n_replicas=4, mesh=_mesh(),
                         window=2)
        f = eng.submit(["SET a 1"], 0)
        eng.flush()
        assert f.result() == [b"OK"]  # replica 0's outcome
        assert eng.divergences == 1


class TestMeshEngineConformance:
    @pytest.mark.asyncio
    async def test_decisions_match_transport_engine(self):
        """Engine-level §7.4.6 gate: same schedule, same decisions, same
        applied sequence, byte-identical state — device plane vs transport
        plane. The gate itself lives in rabia_tpu.testing.conformance and
        is ALSO driven with random schedules by
        scripts/fuzz_conformance.py --planes (shared code: the fixed and
        randomized checks cannot drift)."""
        from rabia_tpu.testing.conformance import run_schedule_on_both_planes

        n_shards, waves = 2, 4
        schedule = [
            {s: [f"SET w{w}s{s} val{w}"] for s in range(n_shards)}
            for w in range(waves)
        ]
        await run_schedule_on_both_planes(
            schedule, n_shards=n_shards, n_replicas=3, tag="fixed-gate"
        )


class TestMultiApplyFailureGranularity:
    """A deterministic app failure in one wave of a multi-block apply
    group must fail ONLY that wave's future — earlier and later waves
    keep their real responses (per-wave granularity, like the
    sequential per-block path)."""

    class _StubVectorSM:
        """Vector-SM shape whose apply_block raises on 'poison' blocks."""

        def apply_batch(self, batch):
            return [b"OK"] * len(batch.commands)

        def apply_block(self, block, idxs, want_responses=True):
            if block.commands_for(0)[0].startswith(b"POISON"):
                raise RuntimeError("boom")
            if not want_responses:
                return None
            return [[b"OK"] for _ in np.asarray(idxs)]

        def apply_block_multi(self, blocks, idxs_list, want_responses=True):
            out = []
            for b, i in zip(blocks, idxs_list):
                try:
                    out.append(self.apply_block(b, i, want_responses))
                except Exception as e:
                    out.append(e)
            return out

        def create_snapshot(self):
            from rabia_tpu.core.state_machine import Snapshot

            return Snapshot.create(0, b"")

        def restore_snapshot(self, snapshot):
            pass

    def test_poison_wave_fails_alone(self):
        """Through the GENERAL per-shard lane: subset blocks (3 of 4
        shards) queue per shard, so failures settle via
        _apply_block_group, not _apply_entries_multi."""
        from rabia_tpu.core.blocks import build_block

        S = 4
        eng = MeshEngine(
            self._StubVectorSM, n_shards=S, n_replicas=4, mesh=_mesh(),
            window=8,
        )
        sub = [0, 1, 2]  # NOT full width -> per-shard queue lane
        ok1 = eng.submit_block(build_block(sub, [[b"SET a 1"]] * len(sub)))
        bad = eng.submit_block(build_block(sub, [[b"POISON"]] * len(sub)))
        ok2 = eng.submit_block(build_block(sub, [[b"SET b 2"]] * len(sub)))
        assert not eng._full_blocks  # really on the general lane
        eng.flush()
        assert ok1.result() == [[b"OK"]] * len(sub)
        assert ok2.result() == [[b"OK"]] * len(sub)
        assert all(
            isinstance(e, RabiaError) and "apply failed" in str(e)
            for e in bad.result()
        )

    def test_poison_wave_fails_alone_fullwidth_lane(self):
        """Same through the full-width fast lane (blocks cover every
        shard, nothing queued per-shard) — the _apply_entries_multi path."""
        from rabia_tpu.core.blocks import build_block

        S = 8
        eng = MeshEngine(
            self._StubVectorSM, n_shards=S, n_replicas=4, mesh=_mesh(),
            window=4,
        )
        shards = list(range(S))
        futs = [
            eng.submit_block(
                build_block(
                    shards,
                    [[b"POISON" if w == 1 else b"SET x 1"]] * S,
                )
            )
            for w in range(3)
        ]
        eng.flush()
        assert futs[0].result() == [[b"OK"]] * S
        assert futs[2].result() == [[b"OK"]] * S
        assert all(
            isinstance(e, RabiaError) and "apply failed" in str(e)
            for e in futs[1].result()
        )


class TestLatencyGovernor:
    """MeshEngine(latency_target_ms=...) auto-tunes `window` on a
    power-of-two ladder against measured per-window wall time, replacing
    the manual knob (the adaptive pattern of core/batching.py on the
    latency axis)."""

    def _mk(self, **kw):
        from rabia_tpu.apps.vector_kv import VectorShardedKV

        S = kw.pop("S", 16)
        return MeshEngine(
            lambda: VectorShardedKV(S, capacity=1 << 12),
            n_shards=S,
            n_replicas=3,
            **kw,
        )

    def test_unreachable_target_shrinks_to_min(self):
        from rabia_tpu.apps.kvstore import encode_set_bin

        eng = self._mk(window=16, latency_target_ms=1e-4, min_window=2)
        op = [encode_set_bin("k", "v")]
        for _ in range(30):
            for _ in range(4):
                for s in range(eng.n_shards):
                    eng.submit(op, s)
            eng.flush()
        assert eng.window == 2
        assert eng.window_resizes >= 3  # 16 -> 8 -> 4 -> 2

    def test_loose_target_grows_under_saturating_demand(self):
        from rabia_tpu.apps.kvstore import encode_set_bin

        eng = self._mk(window=2, latency_target_ms=60_000.0, max_window=16)
        op = [encode_set_bin("k", "v")]
        for _ in range(30):
            for _ in range(16):  # queues deeper than the window
                for s in range(eng.n_shards):
                    eng.submit(op, s)
            eng.flush()
        assert eng.window > 2

    def test_no_growth_without_demand(self):
        from rabia_tpu.apps.kvstore import encode_set_bin

        eng = self._mk(window=4, latency_target_ms=60_000.0, max_window=64)
        op = [encode_set_bin("k", "v")]
        for _ in range(30):  # 1-deep queues: a wider window buys nothing
            for s in range(eng.n_shards):
                eng.submit(op, s)
            eng.flush()
        assert eng.window == 4

    def test_unachievable_target_is_reported(self):
        # a target below the per-window floor must be SURFACED, not
        # silently parked at min_window (round-4 governor sat at W=1
        # with no signal)
        from rabia_tpu.apps.kvstore import encode_set_bin

        eng = self._mk(window=4, latency_target_ms=1e-4, min_window=1)
        op = [encode_set_bin("k", "v")]
        for _ in range(30):
            for s in range(eng.n_shards):
                eng.submit(op, s)
            eng.flush()
        assert eng.window == 1
        assert eng.latency_target_unachievable
        st = eng.governor_stats()
        assert st["unachievable"] is True
        assert st["floor_ms"] is not None and st["floor_ms"] > 1e-4
        assert st["window"] == 1

    def test_single_spike_does_not_veto_upsize(self):
        # one ambient-load outlier among 62 quiet samples: the round-4
        # max-proxy (upsize iff max < 0.4*target -> 200 > 60) would
        # block growth forever; the interpolated p99 (~82ms <= 0.7*150)
        # lets the saturated window grow
        eng = self._mk(window=4, latency_target_ms=150.0, max_window=64)
        eng._lat_samples.extend([10.0] * 62 + [200.0])
        eng._lat_saturated = True
        eng._govern(10.0)
        assert eng.window == 8
        assert eng.window_resizes == 1

    def test_downsize_sets_ceiling_that_blocks_reclimb(self):
        # an overshoot at W=8 must not be re-entered by the next quiet
        # stretch (the 128<->256 limit cycle): the failed size becomes a
        # ceiling that upsizing stays strictly below until it ages out
        # or a sustained-headroom probe clears it (min_window=4 so the
        # deep-overshoot fast descent lands one rung down)
        eng = self._mk(
            window=8, latency_target_ms=100.0, max_window=64, min_window=4
        )
        eng._lat_samples.extend([50.0, 250.0, 250.0])
        eng._govern(250.0)  # two corroborating 2x overshoots -> down
        assert eng.window == 4
        assert eng._lat_ceiling == 8
        eng._lat_samples.extend([60.0] * 10)
        eng._lat_saturated = True
        eng._govern(60.0)
        assert eng.window == 4  # 4*2 == ceiling: parked (p99 > 0.5*t)
        st = eng.governor_stats()
        assert st["ceiling_window"] == 8

    def test_single_spike_does_not_downsize(self):
        # one lone glitch (a single 5-10x overshoot among quiet samples)
        # must not evict a healthy window size: downsizing
        # needs a second corroborating overshoot, or the TRIMMED p99
        # over the target. Round 4 halved on a lone 2x sample, and the
        # resulting ceiling parked the governor at half its sustainable
        # window for the rest of the bench run.
        eng = self._mk(window=8, latency_target_ms=100.0, max_window=64)
        eng._lat_samples.extend([50.0] * 10 + [850.0])  # lone glitch
        eng._govern(850.0)
        assert eng.window == 8  # held
        assert eng._lat_ceiling is None
        # a second overshoot while the first is still in the sample
        # window IS real overload — and at >2x the target on the trimmed
        # estimate it is a deep one: fast-descend to the floor
        eng._lat_samples.append(850.0)
        eng._govern(850.0)
        assert eng.window == eng.min_window
        assert eng._lat_ceiling == 8

    def test_post_resize_glitch_does_not_downsize(self):
        # samples clear on every resize, so the first windows at a new
        # size run with n<8 where the one-outlier trim is off — the p99
        # downsize path must therefore stay off too (it engages at n>=8
        # together with the trim), or a single glitch right after a
        # resize would evict the brand-new size untrimmed and ceiling it
        eng = self._mk(window=8, latency_target_ms=250.0, max_window=64)
        eng._lat_samples.extend([90.0] * 5 + [850.0])  # glitch, n=6
        eng._govern(850.0)
        assert eng.window == 8  # held: 1 spike, p99 path needs n>=8
        assert eng._lat_ceiling is None

    def test_deep_overshoot_jumps_to_floor(self):
        # p99 over 2x target on the trimmed estimate: the target sits at
        # or below the dispatch floor, so the governor jumps straight to
        # min_window rather than paying one jit compile per intermediate
        # ladder rung on the way down (target_60ms in the r5 sweep burned
        # its whole budget walking 16->8->4 and never reached the floor
        # where the unachievable detector lives)
        eng = self._mk(
            window=32, latency_target_ms=50.0, max_window=64, min_window=1
        )
        eng._lat_samples.extend([120.0] * 6)
        eng._govern(120.0)
        assert eng.window == 1  # jumped, not halved
        assert eng._lat_ceiling == 32

    def test_headroom_probe_clears_ceiling(self):
        # a ceiling set by a transient must stop costing throughput once
        # the current size shows sustained deep headroom (trimmed p99
        # <= 0.5*target over >=16 samples): the governor probes the
        # evicted size instead of waiting out the 256-sample age-out
        eng = self._mk(
            window=8, latency_target_ms=100.0, max_window=64, min_window=4
        )
        eng._lat_samples.extend([50.0, 250.0, 250.0])
        eng._govern(250.0)
        assert eng.window == 4 and eng._lat_ceiling == 8
        eng._lat_samples.extend([20.0] * 16)  # deep headroom at W=4
        eng._lat_saturated = True
        eng._govern(20.0)
        assert eng.window == 8  # probed back into the evicted size
        assert eng._lat_ceiling is None
        # the probe is accountable: overload at the re-entered size
        # re-establishes the ceiling within two samples
        eng._lat_samples.extend([250.0, 250.0])
        eng._govern(250.0)
        assert eng.window == 4
        assert eng._lat_ceiling == 8

    def test_growing_needs_demand_that_fills_the_next_rung(self):
        # a deeper window amortizes more only when there is work to put
        # in it (PERF.md, PR 38: with 32 blocks queued the governor went
        # from 32 to 64 and ran the 64-wave program half empty)
        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.core.blocks import build_block

        n = 4
        eng = self._mk(S=n, window=2, latency_target_ms=60_000.0, max_window=8)
        block = lambda i: build_block(
            list(range(n)), [[encode_set_bin(f"k{s}", f"v{i}")] for s in range(n)]
        )
        for depth, grows in ((2, False), (3, False), (4, True)):
            eng.window = 2
            eng._lat_samples.clear()
            eng._lat_skip = 0
            for i in range(12):
                while len(eng._full_blocks) < depth:
                    eng.submit_block(block(i))
                eng.run_cycle()
            eng.flush()
            assert (eng.window > 2) is grows, (depth, eng.window)

    def test_a_drain_cycle_counts_into_its_windows_sample(self):
        # a client that keeps one window outstanding makes the engine
        # resolve each device window in a cycle of its own; that wait
        # and settle belong to the window's sample (PERF.md, PR 38: at
        # 64 the dispatching cycle alone read 22 ms of a 57 ms window)
        import time

        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.core.blocks import build_block

        n = 4
        eng = self._mk(
            S=n, window=2, device_store=True, latency_target_ms=60_000.0,
            min_window=2, max_window=2,
        )
        block = lambda i: build_block(
            list(range(n)), [[encode_set_bin(f"k{s}", f"v{i}")] for s in range(n)]
        )
        for i in range(2):
            eng.submit_block(block(i))
        eng.run_cycle()  # dispatches (and compiles: skipped as a sample)
        assert eng.cycles == 1 and eng._lat_drain_ms == 0.0
        resolve = eng._dev_resolve_one

        def slow_resolve():
            time.sleep(0.05)
            return resolve()

        eng._dev_resolve_one = slow_resolve
        assert eng.run_cycle() == 2 * n  # nothing to dispatch: a drain
        assert eng.cycles == 1 and eng._lat_drain_ms >= 50.0
        assert len(eng._lat_samples) == 0
        assert eng.run_cycle() == 0 and eng._lat_drain_ms >= 50.0  # idle: no time added
        for i in range(2):
            eng.submit_block(block(2 + i))
        eng.run_cycle()
        assert eng.cycles == 2 and eng._lat_drain_ms == 0.0
        assert len(eng._lat_samples) == 1 and eng._lat_samples[0] >= 50.0
        eng.flush()
        eng.close()

    def test_governor_stats_before_any_sample(self):
        eng = self._mk(window=4, latency_target_ms=100.0)
        st = eng.governor_stats()
        assert st["p99_ms"] is None
        assert st["unachievable"] is False
        assert st["window"] == 4
        assert st["settle_p99_ms"] is None

    def test_settle_latency_reported_for_device_lane(self):
        # dispatch->settle samples (the latency a client observes
        # through the pipelined commit — per-cycle samples cannot see
        # the pipe residency) populate in device mode and surface via
        # governor_stats alongside the pipe depth
        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.core.blocks import build_block

        n = 4
        eng = self._mk(S=n, window=2, device_store=True)
        shards = list(range(n))
        for w in range(8):
            eng.submit_block(
                build_block(
                    shards,
                    [[encode_set_bin(f"k{s}", f"v{w}")] for s in shards],
                )
            )
        eng.flush()
        st = eng.governor_stats()
        assert st["inflight"] == 3  # throughput-mode default
        assert st["settle_p99_ms"] is not None and st["settle_p99_ms"] > 0
        assert len(eng._lat_settle) >= 3
        # after demotion there is no pipelined commit: both report None
        # (frozen device-era samples must not read as live latency)
        eng._demote_device_store()
        st = eng.governor_stats()
        assert st["inflight"] is None
        assert st["settle_p99_ms"] is None

    def test_restore_clears_settle_samples(self):
        # restore() is a second device-lane deactivation path besides
        # demotion: pre-restore settle samples must die with the lane
        # (and the stats must read None) so a later re-promotion starts
        # a fresh window population instead of mixing eras
        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.core.blocks import build_block

        n = 4
        eng = self._mk(S=n, window=2, device_store=True)
        shards = list(range(n))
        for w in range(8):
            eng.submit_block(
                build_block(
                    shards,
                    [[encode_set_bin(f"k{s}", f"v{w}")] for s in shards],
                )
            )
        eng.flush()
        assert len(eng._lat_settle) > 0
        ckpt = eng.checkpoint()
        eng.restore(ckpt)
        assert len(eng._lat_settle) == 0
        st = eng.governor_stats()
        assert st["inflight"] is None and st["settle_p99_ms"] is None

    def test_settle_samples_exclude_compile_tainted_windows(self):
        # a window resolved across a jit compile would count seconds of
        # one-off machinery as client latency: dispatches that compile
        # taint every in-flight window and tainted windows contribute
        # no settle sample. The FIRST window of a fresh engine always
        # compiles — deterministically pinning the exclusion
        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.core.blocks import build_block

        n = 4
        eng = self._mk(S=n, window=2, device_store=True)
        shards = list(range(n))
        wave = lambda w: build_block(
            shards, [[encode_set_bin(f"k{s}", f"v{w}")] for s in shards]
        )
        eng.submit_block(wave(0))
        eng.submit_block(wave(1))
        eng.flush()  # one window; its dispatch compiled -> tainted
        assert eng._dev_active
        assert len(eng._lat_settle) == 0, "compile-tainted sample leaked"
        for w in range(2, 8):  # same signature: no compile, samples flow
            eng.submit_block(wave(w))
        eng.flush()
        assert len(eng._lat_settle) >= 2

    def test_governed_state_matches_ungoverned(self):
        from rabia_tpu.apps.kvstore import encode_set_bin

        def run(lat):
            eng = self._mk(S=8, window=8, latency_target_ms=lat)
            rng = np.random.default_rng(5)
            keys = set()
            for i in range(150):
                s = int(rng.integers(0, 8))
                keys.add((s, f"k{i % 17}".encode()))
                eng.submit([encode_set_bin(f"k{i % 17}", f"v{i}")], s)
                if i % 13 == 0:
                    eng.flush()
            eng.flush()
            return eng, keys

        gov, keys = run(0.5)  # tight target: window walks down mid-run
        plain, _ = run(None)
        assert gov.window_resizes > 0
        assert np.array_equal(gov.next_slot, plain.next_slot)
        for s, k in sorted(keys):
            for r in range(3):
                assert gov.sms[r].store.get(s, k) == plain.sms[r].store.get(
                    s, k
                ), (s, k, r)

"""Block lane: PayloadBlock, ProposeBlock wire, engine bulk path, bulk
service API, adaptive batching in the client path, binary kv op codec."""

from __future__ import annotations

import asyncio
import time
import uuid

import numpy as np
import pytest

from netwait import wait_until

from rabia_tpu.apps import ShardedKVService, make_sharded_kv
from rabia_tpu.apps.kvstore import (
    KVOperation,
    KVStore,
    apply_op_bin,
    apply_ops_bin,
    decode_op_bin,
    decode_result_bin,
    encode_op_bin,
    encode_set_bin,
)
from rabia_tpu.core.blocks import block_batch_id, build_block
from rabia_tpu.core.config import BatchConfig, RabiaConfig
from rabia_tpu.core.errors import ValidationError
from rabia_tpu.core.messages import ProposeBlock, ProtocolMessage
from rabia_tpu.core.network import ClusterConfig
from rabia_tpu.core.serialization import Serializer
from rabia_tpu.core.types import NodeId
from rabia_tpu.engine import RabiaEngine
from rabia_tpu.net import InMemoryHub


class TestPayloadBlock:
    def test_build_and_slicing(self):
        blk = build_block(
            [3, 7, 11],
            [[b"a"], [b"bb", b"ccc"], [b"dddd"]],
        )
        assert len(blk) == 3
        assert blk.total_commands == 4
        assert blk.commands_for(0) == [b"a"]
        assert blk.commands_for(1) == [b"bb", b"ccc"]
        assert blk.commands_for(2) == [b"dddd"]
        assert blk.batch_id_for(1) == block_batch_id(blk.id, 7)

    def test_subset_shares_identity(self):
        blk = build_block([1, 2, 3], [[b"x"], [b"yy"], [b"zzz"]])
        sub = blk.subset(np.array([0, 2]))
        assert sub.id == blk.id
        assert sub.commands_for(1) == [b"zzz"]
        assert list(sub.shards) == [1, 3]

    def test_materialize_batch(self):
        blk = build_block([5], [[b"cmd1", b"cmd2"]])
        batch = blk.materialize_batch(0)
        assert int(batch.shard) == 5
        assert [c.data for c in batch.commands] == [b"cmd1", b"cmd2"]

    def test_build_rejects_bad_shapes(self):
        with pytest.raises(ValidationError):
            build_block([1, 1], [[b"a"], [b"b"]])  # duplicate shard
        with pytest.raises(ValidationError):
            build_block([1], [[]])  # empty command list

    def test_wire_roundtrip(self):
        blk = build_block([0, 9], [[b"hello"], [b"wo", b"rld"]])
        blk.slots[:] = [4, 5]
        ser = Serializer()
        msg = ProtocolMessage.new(NodeId.from_int(1), ProposeBlock(block=blk))
        back = ser.deserialize(ser.serialize(msg))
        assert back.payload == ProposeBlock(block=blk)
        assert back.payload.block.commands_for(1) == [b"wo", b"rld"]

    def test_block_batch_ids_are_wire_representable(self):
        # regression: block-lane ids flow into SyncResponse.applied_ids and
        # Decision.batch_id; as tuples they crashed the codec (and with it
        # the engine run loop on the first SyncRequest from a lagging peer)
        from rabia_tpu.core.messages import Decision, DecisionEntry, SyncResponse
        from rabia_tpu.core.types import BatchId, StateValue

        blk = build_block([3, 7], [[b"a"], [b"b"]])
        bid = blk.batch_id_for(1)
        assert isinstance(bid, BatchId)
        # deterministic across independent derivations, distinct per shard
        assert bid == block_batch_id(blk.id, 7)
        assert bid != block_batch_id(blk.id, 3)
        assert block_batch_id(blk.id, 3) == blk.batch_id_for(0)

        ser = Serializer()
        sync = ProtocolMessage.new(
            NodeId.from_int(1),
            SyncResponse(
                responder_phase=5,
                state_version=5,
                snapshot=b"snap",
                per_shard_phase=(2, 3),
                applied_ids=((0, bid), (1, BatchId.new())),
            ),
        )
        back = ser.deserialize(ser.serialize(sync))
        assert back.payload.applied_ids[0] == (0, bid)

        dec = ProtocolMessage.new(
            NodeId.from_int(1),
            Decision(
                decisions=(
                    DecisionEntry(
                        shard=7,
                        phase=4 << 16,
                        decision=StateValue.V1,
                        batch_id=bid,
                    ),
                )
            ),
        )
        back = ser.deserialize(ser.serialize(dec))
        assert back.payload.bids[0] == bid

    def test_wire_rejects_corrupt_data(self):
        from rabia_tpu.core.errors import SerializationError

        blk = build_block([0], [[b"hello"]])
        blk.slots[:] = [0]
        ser = Serializer()
        raw = bytearray(
            ser.serialize(
                ProtocolMessage.new(NodeId.from_int(1), ProposeBlock(block=blk))
            )
        )
        raw[-8] ^= 0xFF  # flip a data byte under the checksum
        with pytest.raises(SerializationError):
            ser.deserialize(bytes(raw))


class TestBinaryOpCodec:
    def test_roundtrip_all_ops(self):
        for op in (
            KVOperation.set("k", "v"),
            KVOperation.get("k"),
            KVOperation.delete("k"),
            KVOperation.exists("k"),
        ):
            assert decode_op_bin(encode_op_bin(op)) == op

    def test_apply_matches_typed_store(self):
        a, b = KVStore(), KVStore()
        r1 = apply_op_bin(a, encode_set_bin("x", "1"))
        r2 = b.set("x", "1")
        assert decode_result_bin(r1).version == r2.version
        ra = decode_result_bin(apply_op_bin(a, encode_op_bin(KVOperation.get("x"))))
        assert ra.value == "1"

    def test_bulk_apply_equivalent_to_sequential(self):
        bulk, seq = KVStore(), KVStore()
        ops = [encode_set_bin(f"k{i % 5}", f"v{i}") for i in range(40)]
        bulk_out = apply_ops_bin(bulk, ops)
        seq_out = [apply_op_bin(seq, b) for b in ops]
        assert [decode_result_bin(r).version for r in bulk_out] == [
            decode_result_bin(r).version for r in seq_out
        ]
        assert {k: e.value for k, e in bulk._data.items()} == {
            k: e.value for k, e in seq._data.items()
        }

    def test_fast_path_respects_notifications(self):
        st = KVStore()
        sub = st.notifications.subscribe()
        # fast path must decline when subscribers exist (notify semantics)
        import time as _t

        assert st.apply_set_bin_fast(encode_set_bin("k", "v"), _t.time()) is None
        st.set("k", "v")
        assert sub.queue.qsize() == 1


def _mk_cluster(S, R=3, persistence=False):
    nodes = [NodeId.from_int(i + 1) for i in range(R)]
    hub = InMemoryHub()
    cfg = RabiaConfig(
        phase_timeout=1.0, heartbeat_interval=0.2, round_interval=0.0005
    ).with_kernel(num_shards=S, shard_pad_multiple=S)
    engines, tasks, stores = [], [], []
    for n in nodes:
        sm, machines = make_sharded_kv(S)
        stores.append(machines)
        engines.append(
            RabiaEngine(ClusterConfig.new(n, nodes), sm, hub.register(n), config=cfg)
        )
    return engines, stores, hub


async def _start(engines):
    tasks = [asyncio.ensure_future(e.run()) for e in engines]
    for _ in range(300):
        await asyncio.sleep(0.01)
        sts = [await e.get_statistics() for e in engines]
        if all(s.has_quorum for s in sts):
            break
    return tasks


async def _stop(engines, tasks):
    for e in engines:
        await e.shutdown()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


class TestBlockLaneEndToEnd:
    @pytest.mark.asyncio
    async def test_submit_block_commits_and_converges(self):
        S = 16
        engines, stores, _ = _mk_cluster(S)
        tasks = await _start(engines)
        try:
            svc = ShardedKVService(
                S,
                engines[0].submit_batch,
                stores[0],
                submit_block=engines[0].submit_block,
            )
            res = await asyncio.wait_for(
                svc.set_many([(f"key{i}", f"val{i}") for i in range(64)]), 30.0
            )
            assert all(r.ok for r in res)

            # every replica applied every write (liveness budget)
            def applied():
                return all(
                    (
                        e := stores[r][svc.shard_of("key3")].store.get("key3")
                    )
                    is not None
                    and e.value == "val3"
                    for r in range(3)
                )

            await wait_until(applied, budget=20.0, desc="replica apply")
        finally:
            await _stop(engines, tasks)

    @pytest.mark.asyncio
    async def test_block_demotion_on_wrong_proposer(self):
        """A block covering shards this replica does NOT propose demotes
        them to the scalar lane (forwarded), and still commits."""
        S = 6
        engines, stores, _ = _mk_cluster(S)
        tasks = await _start(engines)
        try:
            # engine 2 proposes only shards where (s+0)%3==2 at slot 0;
            # cover ALL shards so 2/3 demote+forward
            svc = ShardedKVService(
                S,
                engines[2].submit_batch,
                stores[2],
                submit_block=engines[2].submit_block,
            )
            pairs = [(f"kk{i}", "z") for i in range(24)]
            res = await asyncio.wait_for(svc.set_many(pairs), 30.0)
            assert all(r.ok for r in res), [str(r) for r in res if not r.ok][:3]
        finally:
            await _stop(engines, tasks)

    @pytest.mark.asyncio
    async def test_adaptive_batching_amortizes_slots(self):
        S = 4
        engines, stores, _ = _mk_cluster(S)
        tasks = await _start(engines)
        try:
            svc = ShardedKVService(
                S,
                engines[0].submit_batch,
                stores[0],
                batching=BatchConfig(max_batch_size=8, max_batch_delay=0.01),
            )
            results = await asyncio.wait_for(
                asyncio.gather(*[svc.set(f"b{i}", "x") for i in range(48)]), 30.0
            )
            assert all(r.ok for r in results)
            batches = sum(s.batches_created for s in svc.batch_stats)
            cmds = sum(s.commands_batched for s in svc.batch_stats)
            assert cmds == 48
            assert batches < 48  # multiple commands rode one consensus slot
            await svc.close()
        finally:
            await _stop(engines, tasks)


class TestBlockLaneFaults:
    @pytest.mark.asyncio
    async def test_replica_crash_mid_bulk_load(self):
        """Crash a replica while the block lane is pumping: survivors keep
        committing (dead-proposer shards rotate via null slots) and stay
        convergent."""
        from rabia_tpu.core.config import RabiaConfig
        from rabia_tpu.core.network import ClusterConfig
        from rabia_tpu.core.types import NodeId
        from rabia_tpu.engine import RabiaEngine
        from rabia_tpu.engine.leader import slot_proposer_vec

        S, R = 12, 3
        nodes = [NodeId.from_int(i + 1) for i in range(R)]
        hub = InMemoryHub()
        cfg = RabiaConfig(
            phase_timeout=0.3, heartbeat_interval=0.1, round_interval=0.0005
        ).with_kernel(num_shards=S, shard_pad_multiple=S)
        engines, stores, tasks = [], [], []
        for n in nodes:
            sm, machines = make_sharded_kv(S)
            stores.append(machines)
            engines.append(
                RabiaEngine(ClusterConfig.new(n, nodes), sm, hub.register(n), config=cfg)
            )
            tasks.append(asyncio.ensure_future(engines[-1].run()))
        try:
            for _ in range(300):
                await asyncio.sleep(0.01)
                sts = [await e.get_statistics() for e in engines]
                if all(s.has_quorum for s in sts):
                    break
            import numpy as _np

            from rabia_tpu.apps.kvstore import encode_set_bin
            from rabia_tpu.core.blocks import build_block
            from rabia_tpu.core.types import Command, CommandBatch

            shard_ids = _np.arange(S)

            async def wave(live):
                futs = []
                for e in live:
                    head = _np.maximum(e.rt.next_slot[:S], e.rt.applied_upto[:S])
                    mine = shard_ids[
                        (slot_proposer_vec(shard_ids, head, R) == e.me)
                        & ~e.rt.in_flight[:S]
                        & (e.rt.queue_len[:S] == 0)
                    ]
                    if len(mine):
                        futs.append(
                            await e.submit_block(
                                build_block(
                                    mine,
                                    [[encode_set_bin(f"w{int(s)}", "x")] for s in mine],
                                )
                            )
                        )
                if futs:
                    await asyncio.wait_for(
                        asyncio.gather(*futs, return_exceptions=True), 20.0
                    )

            await wave(engines)  # healthy wave
            # crash replica 0 (tolerated: quorum 2 of 3)
            tasks[0].cancel()
            hub.set_connected(nodes[0], False)
            pre = (await engines[1].get_statistics()).committed_slots
            # post-crash: live proposers pump blocks; shards whose rotation
            # hits the dead row are fed through the scalar lane so the
            # forward-timeout null slot rotates them
            deadline = asyncio.get_event_loop().time() + 20.0
            while asyncio.get_event_loop().time() < deadline:
                await wave(engines[1:])
                e = engines[1]
                head = _np.maximum(e.rt.next_slot[:S], e.rt.applied_upto[:S])
                stuck = shard_ids[
                    (slot_proposer_vec(shard_ids, head, R) == 0)
                    & (e.rt.queue_len[:S] < 1)
                ]
                for s in stuck:
                    try:
                        await e.submit_batch(
                            CommandBatch.new(
                                [Command.new(encode_set_bin(f"w{int(s)}", "x"))],
                                shard=int(s),
                            ),
                            shard=int(s),
                        )
                    except Exception:
                        pass
                await asyncio.sleep(0.05)
                post = (await engines[1].get_statistics()).committed_slots
                if post - pre >= 2 * S:
                    break
            post = (await engines[1].get_statistics()).committed_slots
            assert post - pre >= S, f"survivors stalled: {post - pre} commits"
            # survivors convergent on a sample key (liveness budget)
            def survivors_agree():
                a = stores[1][3].store.get("w3")
                b = stores[2][3].store.get("w3")
                return a is not None and b is not None and a.value == b.value

            await wait_until(
                survivors_agree, budget=20.0, desc="survivor convergence"
            )
        finally:
            for e in engines[1:]:
                await e.shutdown()
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)


class TestJaxBackendEngine:
    """The FENCED device-array engine backend (KernelConfig.backend=
    "jax"): one device dispatch + readback per engine tick, not
    measured on the attached chip (docs/PERFORMANCE.md, 'Engine kernel
    backends'). These tests keep the path correct, not fast."""

    @pytest.mark.jax_backend
    @pytest.mark.asyncio
    async def test_jax_kernel_backend_commits(self):
        """KernelConfig.backend='jax' (device-array state + inbox planes)
        commits the same as the host kernel — the device-engine deployment
        path stays exercised."""
        from rabia_tpu.core.config import RabiaConfig
        from rabia_tpu.core.network import ClusterConfig
        from rabia_tpu.core.types import Command, CommandBatch, NodeId
        from rabia_tpu.engine import RabiaEngine
        from rabia_tpu.apps.kvstore import encode_set_bin

        S, R = 4, 3
        nodes = [NodeId.from_int(i + 1) for i in range(R)]
        hub = InMemoryHub()
        cfg = RabiaConfig(
            phase_timeout=0.5, heartbeat_interval=0.1, round_interval=0.001
        ).with_kernel(num_shards=S, shard_pad_multiple=S, backend="jax")
        engines, stores, tasks = [], [], []
        for n in nodes:
            sm, machines = make_sharded_kv(S)
            stores.append(machines)
            engines.append(
                RabiaEngine(ClusterConfig.new(n, nodes), sm, hub.register(n), config=cfg)
            )
            tasks.append(asyncio.ensure_future(engines[-1].run()))
        try:
            for _ in range(300):
                await asyncio.sleep(0.01)
                sts = [await e.get_statistics() for e in engines]
                if all(s.has_quorum for s in sts):
                    break
            fut = await engines[0].submit_batch(
                CommandBatch.new([Command.new(encode_set_bin("jk", "jv"))], shard=1),
                shard=1,
            )
            responses = await asyncio.wait_for(fut, 30.0)
            assert len(responses) == 1

            def converged():
                vals = [ms[1].store.get("jk") for ms in stores]
                return all(v is not None and v.value == "jv" for v in vals)

            # the fenced backend ticks slowly by design; under ambient
            # load the other replicas' applies can trail the committer
            # by several seconds (liveness budget, not a speed assert)
            await wait_until(converged, budget=30.0, desc="replica catch-up")
        finally:
            for e in engines:
                await e.shutdown()
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)


class TestNoDecisionBroadcast:
    @pytest.mark.asyncio
    async def test_straggler_recovers_without_decision_broadcasts(self):
        """decision_broadcast=False: a partitioned replica that missed a
        stretch of commits catches back up through the targeted stale-vote
        repair (decided-value ring) and/or snapshot sync."""
        from rabia_tpu.core.config import RabiaConfig
        from rabia_tpu.core.network import ClusterConfig
        from rabia_tpu.core.types import NodeId
        from rabia_tpu.engine import RabiaEngine
        from rabia_tpu.engine.leader import slot_proposer_vec
        import numpy as _np

        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.core.blocks import build_block

        S, R = 8, 3
        nodes = [NodeId.from_int(i + 1) for i in range(R)]
        hub = InMemoryHub()
        cfg = RabiaConfig(
            phase_timeout=0.2,
            heartbeat_interval=0.05,
            round_interval=0.0005,
            sync_timeout=1.0,
            decision_broadcast=False,
        ).with_kernel(num_shards=S, shard_pad_multiple=S)
        engines, stores, tasks = [], [], []
        for n in nodes:
            sm, machines = make_sharded_kv(S)
            stores.append(machines)
            engines.append(
                RabiaEngine(ClusterConfig.new(n, nodes), sm, hub.register(n), config=cfg)
            )
            tasks.append(asyncio.ensure_future(engines[-1].run()))
        try:
            for _ in range(300):
                await asyncio.sleep(0.01)
                sts = [await e.get_statistics() for e in engines]
                if all(s.has_quorum for s in sts):
                    break
            shard_ids = _np.arange(S)

            async def wave(live, tag):
                futs = []
                for e in live:
                    head = _np.maximum(e.rt.next_slot[:S], e.rt.applied_upto[:S])
                    mine = shard_ids[
                        (slot_proposer_vec(shard_ids, head, R) == e.me)
                        & ~e.rt.in_flight[:S]
                        & (e.rt.queue_len[:S] == 0)
                    ]
                    if len(mine):
                        try:
                            futs.append(
                                await e.submit_block(
                                    build_block(
                                        mine,
                                        [
                                            [encode_set_bin(f"s{int(s)}", tag)]
                                            for s in mine
                                        ],
                                    )
                                )
                            )
                        except Exception:
                            # a just-healed replica may not have refreshed
                            # its quorum view yet — skip it this wave
                            pass
                if futs:
                    await asyncio.wait_for(
                        asyncio.gather(*futs, return_exceptions=True), 20.0
                    )

            await wave(engines, "pre")
            # partition node 2; the remaining quorum keeps committing for
            # the slots it proposes (rotation parks at row-2 slots since
            # nothing feeds the scalar give-up path — that's the crash
            # test's job; here we only need the straggler to MISS commits)
            hub.set_connected(nodes[2], False)
            await asyncio.sleep(0.3)
            for i in range(4):
                await wave(engines[:2], f"gap{i}")
            mid = (await engines[2].get_statistics()).committed_slots
            lead = (await engines[0].get_statistics()).committed_slots
            assert lead > mid, "quorum pair did not outrun the straggler"
            # heal: traffic resumes cluster-wide; the straggler's fresh
            # votes in already-decided slots must be answered by the
            # targeted repair / sync — NO Decision broadcasts exist
            hub.set_connected(nodes[2], True)
            a = c = 0
            for _ in range(600):
                await asyncio.sleep(0.01)
                await wave(engines, "post")
                a = (await engines[0].get_statistics()).committed_slots
                c = (await engines[2].get_statistics()).committed_slots
                if c >= a - S and c > mid:
                    break
            assert c > mid, "straggler made no progress after heal"
            assert c >= a - S, f"straggler stuck at {c} vs leader {a}"
        finally:
            for e in engines:
                await e.shutdown()
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)


class TestGetMany:
    @pytest.mark.asyncio
    async def test_bulk_reads_through_consensus(self):
        S = 8
        engines, stores, _ = _mk_cluster(S)
        tasks = await _start(engines)
        try:
            svc = ShardedKVService(
                S,
                engines[0].submit_batch,
                stores[0],
                submit_block=engines[0].submit_block,
            )
            pairs = [(f"gk{i}", f"gv{i}") for i in range(20)]
            res = await asyncio.wait_for(svc.set_many(pairs), 30.0)
            assert all(r.ok for r in res)
            got = await asyncio.wait_for(
                svc.get_many([k for k, _ in pairs] + ["absent-key"]), 30.0
            )
            for (k, v), r in zip(pairs, got):
                assert r.ok and r.value == v, (k, r)
            assert not got[-1].ok or got[-1].value is None  # NotFound
        finally:
            await _stop(engines, tasks)


class TestBlockLanePersistence:
    @pytest.mark.asyncio
    async def test_restart_rejoins_after_bulk_waves(self, tmp_path):
        """Bulk-lane commits + durable persistence: restart one replica's
        engine object; it restores its counters/snapshot and keeps
        committing with the cluster."""
        import numpy as _np

        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.core.blocks import build_block
        from rabia_tpu.engine.leader import slot_proposer_vec
        from rabia_tpu.persistence import FileSystemPersistence

        S, R = 6, 3
        nodes = [NodeId.from_int(i + 1) for i in range(R)]
        hub = InMemoryHub()
        # barrier_stride=1: taint only truly-opened slots so the restored
        # replica rejoins immediately (the deep-stride default trades
        # restart taint width for fsync amortization)
        cfg = RabiaConfig(
            phase_timeout=0.3,
            heartbeat_interval=0.05,
            round_interval=0.0005,
            barrier_stride=1,
        ).with_kernel(num_shards=S, shard_pad_multiple=S)
        persist = [FileSystemPersistence(str(tmp_path / f"n{i}")) for i in range(R)]
        nets = [hub.register(n) for n in nodes]

        def mk_engine(i, sm_holder):
            sm, machines = make_sharded_kv(S)
            sm_holder.append(machines)
            return RabiaEngine(
                ClusterConfig.new(nodes[i], nodes),
                sm,
                nets[i],
                persistence=persist[i],
                config=cfg,
            )

        stores: list = []
        engines = [mk_engine(i, stores) for i in range(R)]
        tasks = [asyncio.ensure_future(e.run()) for e in engines]
        try:
            for _ in range(1000):
                await asyncio.sleep(0.01)
                sts = [await e.get_statistics() for e in engines]
                if all(s.has_quorum for s in sts):
                    break
            shard_ids = _np.arange(S)

            async def wave(live, tag):
                futs = []
                for e in live:
                    head = _np.maximum(e.rt.next_slot[:S], e.rt.applied_upto[:S])
                    mine = shard_ids[
                        (slot_proposer_vec(shard_ids, head, R) == e.me)
                        & ~e.rt.in_flight[:S]
                        & (e.rt.queue_len[:S] == 0)
                    ]
                    if len(mine):
                        try:
                            futs.append(
                                await e.submit_block(
                                    build_block(
                                        mine,
                                        [
                                            [encode_set_bin(f"p{int(s)}", tag)]
                                            for s in mine
                                        ],
                                    )
                                )
                            )
                        except Exception:
                            pass
                if futs:
                    await asyncio.wait_for(
                        asyncio.gather(*futs, return_exceptions=True), 20.0
                    )

            for i in range(3):
                await wave(engines, f"w{i}")
            # force a checkpoint, then stop replica 0 cleanly
            await engines[0]._save_state()
            await engines[0].shutdown()
            tasks[0].cancel()
            await asyncio.gather(tasks[0], return_exceptions=True)
            committed_before = (await engines[1].get_statistics()).committed_slots

            # rebuild replica 0's engine from its persisted state
            restored_stores: list = []
            e0 = mk_engine(0, restored_stores)
            tasks[0] = asyncio.ensure_future(e0.run())
            engines[0] = e0
            for _ in range(1000):
                await asyncio.sleep(0.01)
                st = await e0.get_statistics()
                if st.has_quorum and st.committed_slots > 0:
                    break
            assert (await e0.get_statistics()).committed_slots > 0, (
                "restored replica lost its applied counters"
            )
            # wait for the restored replica's per-shard heads to catch up
            # with the cluster: until sync repair lands, every live
            # proposer defers to a peer (proposer is computed from each
            # engine's OWN head), so a wave issued in that window no-ops
            # — the pre-round-5 version assumed exactly 3 waves would
            # commit and flaked under ambient load on exactly this
            def heads(e):
                return _np.maximum(e.rt.next_slot[:S], e.rt.applied_upto[:S])

            await wait_until(
                lambda: _np.all(heads(e0) >= heads(engines[1])),
                budget=20.0,
                desc="restored replica head catch-up",
            )
            # the cluster keeps committing with the restored member:
            # retry waves under a deadline (a wave still no-ops per-shard
            # while that shard's previous slot is settling)
            deadline = time.monotonic() + 30.0
            i = 0
            after = committed_before
            got = None
            while time.monotonic() < deadline:
                await wave(engines, f"r{i}")
                i += 1
                await asyncio.sleep(0.05)
                after = (await engines[1].get_statistics()).committed_slots
                got = restored_stores[0][2].store.get("p2")
                if (
                    after > committed_before
                    and got is not None
                    and got.value.startswith("r")
                ):
                    break
            assert after > committed_before
            # restored replica converges on post-restart writes
            assert got is not None and got.value.startswith("r")
        finally:
            for e in engines:
                try:
                    await asyncio.wait_for(e.shutdown(), 5.0)
                except Exception:
                    pass
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)

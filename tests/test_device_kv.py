"""Device-resident KV lane conformance vs the host vector store.

The device table (apps/device_kv.py) is a bounded fast lane; the host
VectorShardedKV is the semantics owner. Every test drives the SAME
full-width SET workload through a device-store MeshEngine and a host
MeshEngine and compares the observables: per-op version responses, and
the final key -> (value, version) content after demotion/sync-down.
Runs on the virtual CPU mesh (conftest pins JAX to CPU).
"""

from __future__ import annotations

import itertools

import numpy as np
import pytest

from rabia_tpu.apps.kvstore import encode_set_bin
from rabia_tpu.apps.vector_kv import VectorShardedKV
from rabia_tpu.core.blocks import build_block
from rabia_tpu.parallel import MeshEngine, make_mesh


def _mk(n_shards, device: bool, **kw):
    return MeshEngine(
        lambda: VectorShardedKV(n_shards, capacity=1 << 12),
        n_shards=n_shards,
        n_replicas=3,
        mesh=make_mesh(),
        window=kw.pop("window", 4),
        device_store=device,
        **kw,
    )


def _frames(fut):
    """Flatten a block future's responses to a list of frame bytes."""
    return [bytes(g[0]) for g in fut.result_groups()] if hasattr(
        fut, "result_groups"
    ) else [bytes(r[0]) for r in fut._results]


def _set_blocks(n_shards, waves, rng, keyspace=3):
    """Random full-width SET blocks: repeated keys across waves, varied
    value lengths (collision + update coverage)."""
    out = []
    for w in range(waves):
        cmds = []
        for s in range(n_shards):
            k = f"k{s}_{int(rng.integers(0, keyspace))}"
            v = "v" * int(rng.integers(0, 24)) + f"{w}"
            cmds.append([encode_set_bin(k, v)])
        out.append(build_block(list(range(n_shards)), cmds))
    return out


def _store_content(sm: VectorShardedKV, n_shards):
    st = sm.store
    out = {}
    used = np.nonzero(st.state == 1)[0]
    for slot in used.tolist():
        s = int(st.shard_col[slot])
        key = (
            st.key_lanes[slot]
            .view(np.uint8)[: int(st.key_len[slot])]
            .tobytes()
        )
        out[(s, key)] = (sm.store._value_at(slot), int(st.version[slot]))
    return out


class TestDeviceKVConformance:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_versions_and_state_match_host(self, seed):
        n = 8
        rng = np.random.default_rng(seed)
        blocks = _set_blocks(n, waves=6, rng=rng)
        dev = _mk(n, device=True)
        host = _mk(n, device=False)
        dev_futs = [dev.submit_block(b) for b in blocks]
        # identical blocks, fresh identity, through the host engine
        host_blocks = _set_blocks(n, waves=6, rng=np.random.default_rng(seed))
        host_futs = [host.submit_block(b) for b in host_blocks]
        assert dev.flush() == host.flush() == 6 * n
        assert dev._dev_active  # clean SET windows: no demotion
        for df, hf in zip(dev_futs, host_futs):
            d = [list(map(bytes, g)) for g in df._results] if isinstance(
                df._results, list
            ) else None
            h = [list(map(bytes, g)) for g in hf._results] if isinstance(
                hf._results, list
            ) else None
            assert d == h
        # demote and compare the final store content on every replica
        dev._demote_device_store()
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want
        # slot accounting marched identically
        assert np.array_equal(dev.next_slot, host.next_slot)
        assert dev.decided_v1 == host.decided_v1

    def test_mixed_block_demotes_and_stays_correct(self):
        n = 4
        rng = np.random.default_rng(7)
        dev = _mk(n, device=True)
        host = _mk(n, device=False)
        sets = _set_blocks(n, waves=2, rng=rng)
        for b in sets:
            dev.submit_block(b)
        for b in _set_blocks(n, waves=2, rng=np.random.default_rng(7)):
            host.submit_block(b)
        dev.flush()
        host.flush()
        assert dev._dev_active
        # a value wider than the device table's value lanes is outside
        # the envelope (DEL/EXISTS now run in-lane) -> demotion, and
        # the write must act on the device-written state through the
        # host store
        wide = "y" * 100
        getb = build_block(
            list(range(n)),
            [[encode_set_bin(f"k{s}_0", wide)] for s in range(n)],
        )
        getb_h = build_block(
            list(range(n)),
            [[encode_set_bin(f"k{s}_0", wide)] for s in range(n)],
        )
        df, hf = dev.submit_block(getb), host.submit_block(getb_h)
        dev.flush()
        host.flush()
        assert not dev._dev_active  # demoted
        d = [list(map(bytes, g)) for g in df._results]
        h = [list(map(bytes, g)) for g in hf._results]
        assert d == h
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want

    def test_fault_demotes_without_corruption(self):
        n = 4
        rng = np.random.default_rng(3)
        dev = _mk(n, device=True)
        host = _mk(n, device=False)
        for b in _set_blocks(n, waves=2, rng=rng):
            dev.submit_block(b)
        for b in _set_blocks(n, waves=2, rng=np.random.default_rng(3)):
            host.submit_block(b)
        dev.flush()
        host.flush()
        # crash a MINORITY replica: quorum holds, every slot still
        # decides V1, and the device lane keeps going — fault tolerance
        # without demotion (only a quorum-losing window demotes)
        dev.crash_replica(2)
        host.crash_replica(2)
        for b in _set_blocks(n, waves=2, rng=np.random.default_rng(4)):
            dev.submit_block(b)
        for b in _set_blocks(n, waves=2, rng=np.random.default_rng(4)):
            host.submit_block(b)
        assert dev.flush() == host.flush()
        assert dev._dev_active  # minority crash rides the device lane
        dev._demote_device_store()
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want
        assert np.array_equal(dev.next_slot, host.next_slot)

    def test_overflow_demotes(self):
        n = 2
        dev = _mk(n, device=True, device_store_kw={"per_shard_capacity": 4})
        # 6 distinct keys per shard exceeds the 4-slot device table
        for w in range(6):
            dev.submit_block(
                build_block(
                    list(range(n)),
                    [[encode_set_bin(f"key{w}", "x")] for _ in range(n)],
                )
            )
        assert dev.flush() == 6 * n
        assert not dev._dev_active  # overflowed -> demoted mid-stream
        # every key present with version == its wave's shard version
        ref = _mk(n, device=False)
        for w in range(6):
            ref.submit_block(
                build_block(
                    list(range(n)),
                    [[encode_set_bin(f"key{w}", "x")] for _ in range(n)],
                )
            )
        ref.flush()
        assert _store_content(dev.sms[0], n) == _store_content(ref.sms[0], n)

    def test_rollback_respects_submission_order_vs_queued_batches(self):
        # regression (round-5 review): per-batch submissions that arrive
        # while a pipelined device window is IN FLIGHT land directly on
        # the per-shard queues (submit() finds _full_blocks empty). If
        # that window then reads back dirty, the rollback must put its
        # blocks IN FRONT of the queued batches — appending them behind
        # (the old behavior) made the host path apply a newer write
        # before an older one on the same key.
        n = 2
        dev = _mk(
            n,
            device=True,
            device_store_kw={"per_shard_capacity": 4},
            window=8,
        )
        host = _mk(n, device=False, window=8)

        def blocks():
            # 6 distinct keys per shard overflow the 4-slot device
            # table (dirty flags); the last block writes k := A
            out = [
                build_block(
                    list(range(n)),
                    [[encode_set_bin(f"key{w}", "x")] for _ in range(n)],
                )
                for w in range(6)
            ]
            out.append(
                build_block(
                    list(range(n)),
                    [[encode_set_bin("k", "A")] for _ in range(n)],
                )
            )
            return out

        for b in blocks():
            dev.submit_block(b)
        dev.run_cycle()  # dispatches the window; flags resolve later
        assert dev._dev_pipe, "window must be in flight (pipelined)"
        # newer per-batch submission for the same key while in flight
        dev.submit([encode_set_bin("k", "B")], 0)
        dev.flush()
        assert not dev._dev_active  # dirty window -> demoted

        for b in blocks():
            host.submit_block(b)
        host.flush()
        host.submit([encode_set_bin("k", "B")], 0)
        host.flush()

        # submission order holds: k ended as B everywhere, and the full
        # content (incl. versions) matches the host-only reference
        want = _store_content(host.sms[0], n)
        assert want[(0, b"k")][0] == b"B"
        for sm in dev.sms:
            assert _store_content(sm, n) == want

    def test_idle_run_cycle_does_not_demote(self):
        n = 4
        dev = _mk(n, device=True)
        assert dev.run_cycle() == 0  # nothing queued: a no-op, not work
        assert dev._dev_active
        for b in _set_blocks(n, waves=2, rng=np.random.default_rng(5)):
            dev.submit_block(b)
        assert dev.flush() == 2 * n
        assert dev._dev_active

    def test_checkpoint_reflects_device_state(self):
        n = 4
        dev = _mk(n, device=True)
        for b in _set_blocks(n, waves=3, rng=np.random.default_rng(9)):
            dev.submit_block(b)
        dev.flush()
        assert dev._dev_active
        cp = dev.checkpoint()
        assert dev._dev_active  # checkpoint does not leave device mode
        fresh = _mk(n, device=False)
        fresh.restore(cp)
        want = _store_content(fresh.sms[0], n)
        dev._demote_device_store()
        assert _store_content(dev.sms[0], n) == want


class TestRePromotion:
    """After a demotion the engine climbs back onto the device lane:
    upload_from rebuilds the device table from the (authoritative) host
    stores, and subsequent windows run fused again — with version
    continuity and content identical to a pure-host engine."""

    def test_demote_then_repromote_conformance(self):
        n = 4
        rng = np.random.default_rng(11)
        dev = _mk(n, device=True, device_store_repromote=4)
        host = _mk(n, device=False)
        rng_h = np.random.default_rng(11)

        def both(blocks_fn):
            for b in blocks_fn(rng):
                dev.submit_block(b)
            for b in blocks_fn(rng_h):
                host.submit_block(b)
            dev.flush()
            host.flush()

        both(lambda r: _set_blocks(n, waves=3, rng=r))
        assert dev._dev_active
        # demote via an over-width value (DEL/EXISTS now run in-lane)
        g = lambda r: [
            build_block(
                list(range(n)),
                [[encode_set_bin(f"k{s}_0", "y" * 100)] for s in range(n)],
            )
        ]
        both(g)
        assert not dev._dev_active
        # overwrite the wide value with an in-envelope one, or the
        # re-promotion upload keeps declining
        both(lambda r: [
            build_block(
                list(range(n)),
                [[encode_set_bin(f"k{s}_0", "ok")] for s in range(n)],
            )
        ])
        # host-lane SETs while demoted (content the upload must carry)
        both(lambda r: _set_blocks(n, waves=2, rng=r))
        assert not dev._dev_active  # cooldown (4 cycles) not yet served
        # more full-width cycles serve the cooldown and re-promote
        both(lambda r: _set_blocks(n, waves=3, rng=r))
        both(lambda r: _set_blocks(n, waves=3, rng=r))
        assert dev._dev_active, "device lane did not re-promote"
        # device-lane windows after re-promotion stay conformant
        both(lambda r: _set_blocks(n, waves=4, rng=r))
        assert dev._dev_active
        dev._demote_device_store()  # final sync-down for comparison
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want

    def test_upload_declines_outside_envelope(self):
        n = 2
        dev = _mk(n, device=True, device_store_repromote=1)
        # value wider than the device table's VW: host-lane only content
        wide = "x" * 300
        dev.submit_block(
            build_block(
                list(range(n)),
                [[encode_set_bin(f"k{s}", wide)] for s in range(n)],
            )
        )
        dev.flush()
        assert not dev._dev_active  # wide value demoted the lane
        # re-promotion attempts must DECLINE while the wide value lives
        for _ in range(4):
            dev.submit_block(
                build_block(
                    list(range(n)),
                    [[encode_set_bin(f"s{s}", "v")] for s in range(n)],
                )
            )
            dev.flush()
        assert not dev._dev_active
        # content still correct on the host path
        for sm in dev.sms:
            got = sm.store.get(0, b"k0")
            assert got is not None and got[0] == wide.encode()


class TestGovernedDeviceLane:
    def test_governor_resizes_with_device_store_conformant(self):
        """latency_target_ms + device_store compose: the governor walks
        W (each size recompiles the fused program) while the device lane
        stays active and content matches a fixed-window host engine."""
        n = 8
        eng = MeshEngine(
            lambda: VectorShardedKV(n, capacity=1 << 12),
            n_shards=n,
            n_replicas=3,
            mesh=make_mesh(),
            window=2,
            device_store=True,
            latency_target_ms=60_000.0,
            max_window=8,
        )
        host = _mk(n, device=False)
        rng = np.random.default_rng(2)
        rng_h = np.random.default_rng(2)
        for r in range(25):
            for b in _set_blocks(n, waves=8, rng=rng):  # deep: saturates W
                eng.submit_block(b)
            for b in _set_blocks(n, waves=8, rng=rng_h):
                host.submit_block(b)
            eng.flush()
            host.flush()
        assert eng.window_resizes > 0, "governor never resized"
        assert eng._dev_active
        eng._demote_device_store()
        want = _store_content(host.sms[0], n)
        for sm in eng.sms:
            assert _store_content(sm, n) == want


def _governed(n, **kw):
    """A governed device-lane engine on the rungs 1, 2, 4, 8."""
    return MeshEngine(
        lambda: VectorShardedKV(n, capacity=1 << 12),
        n_shards=n,
        n_replicas=3,
        mesh=make_mesh(),
        window=8,
        device_store=True,
        device_store_kw={"per_shard_capacity": 16},
        latency_target_ms=kw.pop("latency_target_ms", 60_000.0),
        min_window=1,
        max_window=8,
        **kw,
    )


def _walk_the_ladder(eng, blocks, hook=None):
    """Submit ``blocks`` a batch at a time and run the engine through
    every rung: down under a target no cycle can meet (two overshooting
    samples a rung), up under one every cycle meets (sixteen samples to
    probe the failed size, eight a rung after that), down again. Batches
    leave partial windows at their ends. Returns the futures in order."""
    futs = []
    it = iter(blocks)

    def feed(k):
        got = list(itertools.islice(it, k))
        futs.extend(eng.submit_block(b) for b in got)
        return len(got)

    def cycles(target, until):
        eng.latency_target_ms = target
        for _ in range(400):
            if until():
                return
            if len(eng._full_blocks) < eng.window and not feed(2 * eng.window + 3):
                break
            before = eng.cycles
            eng.run_cycle()
            if hook is not None and eng.cycles != before:
                hook()
        assert until(), (target, eng.window, eng.governor_stats())

    cycles(1e-6, lambda: eng.window == eng.min_window)
    cycles(60_000.0, lambda: eng.window == eng.max_window)
    cycles(1e-6, lambda: eng.window == eng.min_window)
    feed(1 << 30)
    eng.latency_target_ms = 60_000.0
    eng.flush()
    return futs


class TestWindowLadder:
    """A governed table builds a signature for every rung at once, with
    the first window of its kind and widths (``DeviceKVTable._program``):
    a window that lands on another rung later compiles nothing."""

    @staticmethod
    def _four_kinds(n, rng, waves):
        """Random SET/GET/DEL/EXISTS per (wave, shard), every third wave
        SET only (a wave of a mixed window that bears no GET)."""
        from rabia_tpu.apps.kvstore import KVOperation, KVOpType, encode_op_bin

        out = []
        for w in range(waves):
            cmds = []
            for s in range(n):
                k = f"k{s}_{int(rng.integers(0, 3))}"
                x = 0.0 if w % 3 == 0 else rng.random()
                if x < 0.4:
                    cmds.append([encode_set_bin(k, "v" * int(rng.integers(1, 60)) + str(w))])
                elif x < 0.75:
                    cmds.append([TestDeviceGetWindows._enc_get(k)])
                elif x < 0.9:
                    cmds.append([encode_op_bin(KVOperation(KVOpType.Delete, k))])
                else:
                    cmds.append([encode_op_bin(KVOperation(KVOpType.Exists, k))])
            out.append(build_block(list(range(n)), cmds))
        return out

    def test_every_rung_up_and_down_agrees_with_the_host_engine(self):
        """SET/GET/DEL/EXISTS (``kv_plain`` knows SET and GET only, so the
        four kinds are held to the fixed-window host engine): reply for
        reply, store for store on all replicas, and the lane stays."""
        n = 8
        eng = _governed(n)
        host = _mk(n, device=False)
        blocks = self._four_kinds(n, np.random.default_rng(38), 700)
        futs = _walk_the_ladder(eng, blocks)
        want = [host.submit_block(b) for b in
                self._four_kinds(n, np.random.default_rng(38), 700)][: len(futs)]
        host.flush()
        assert eng.device_lane_active and eng.divergences == 0
        assert all(eng._dev_windows[w] > 0 for w in (1, 2, 4, 8)), eng._dev_windows
        assert eng.window_resizes >= 9
        assert len(futs) > 200
        for i, (a, b) in enumerate(zip(futs, want)):
            assert _frames(a) == _frames(b), i
        eng.sync_to_host()
        content = _store_content(host.sms[0], n)
        assert content
        for sm in eng.sms:
            assert _store_content(sm, n) == content
        eng.close()
        host.close()

    def test_every_rung_up_and_down_agrees_with_kv_plain(self):
        """The benchmark's own generator, wire format, reference and
        check over a governed engine: YCSB-A's SET/GET waves, every reply
        and all replica stores against ``kv_plain``."""
        from chipbench import check, gen
        from chipbench.reference.kv_plain import PlainKV

        config = {"n_shards": 8, "per_shard_capacity": 16, "key_bytes": 32,
                  "value_bytes": 64, "window": 8}
        traffic = {"readproportion": 0.5, "updateproportion": 0.5,
                   "requestdistribution": "zipfian", "zipfian_constant": 0.99,
                   "pool_windows": 80, "check_block_share": 1.0}
        g = gen.Generator(2**31 + 38, config, traffic)
        waves = g.load_waves() + g.pool_waves()
        eng = _governed(g.S)
        futs = _walk_the_ladder(eng, (g.block(*g.encode(w)) for w in waves))
        assert check.lane_faults(eng) == 0
        assert all(eng._dev_windows[w] > 0 for w in (1, 2, 4, 8)), eng._dev_windows
        ref = PlainKV(g.S, g.n_keys, g.VW)
        for i, (wave, fut) in enumerate(zip(waves, futs)):
            want = ref.apply_wave(wave.kind, wave.kid, wave.vlen, wave.val, range(g.S))
            got = fut.result()
            assert {s: bytes(got[s][0]) for s in range(g.S)} == want, i
        assert len(futs) > 200
        eng.sync_to_host()
        assert check.replica_mismatches(eng, ref, g) == (0, "")
        eng.close()

    def test_no_rung_compiles_after_the_first_window_of_its_kind(self, caplog):
        """After the first SET window and the first mixed window the
        table holds both kinds at all four rungs; whatever rung runs next
        ``_fused_cache`` gains nothing, ``compiled_on_last_call`` is never
        set, and JAX compiles no window program (``jax_log_compiles``)."""
        import logging

        import jax

        n = 8
        eng = _governed(n)
        dev = eng._dev
        assert dev.rungs == eng._ladder() == (1, 2, 4, 8)
        rng = np.random.default_rng(7)
        for b in _set_blocks(n, 3, rng):  # a partial SET window at rung 8
            eng.submit_block(b)
        eng.flush()
        assert dev.compiled_on_last_call
        assert set(dev._fused_cache) == {(w, 1, 8) for w in (1, 2, 4, 8)}
        mixed = [
            build_block(
                list(range(n)),
                [[encode_set_bin(f"k{s}_0", "x" * 40)] if (s + w) % 4 == 0
                 else [TestDeviceGetWindows._enc_get(f"k{s}_{w % 3}")]
                 for s in range(n)],
            )
            for w in range(600)
        ]
        f0 = eng.submit_block(mixed[0])
        eng.flush()
        assert f0.done() and dev.compiled_on_last_call
        # a governed table's mixed signature has Gp = W: one program a rung
        sigs = {(w, 1, 8) for w in (1, 2, 4, 8)} | {
            ("mix", w, 1, 16, w) for w in (1, 2, 4, 8)
        }
        assert set(dev._fused_cache) == sigs
        assert eng.metrics.snapshot()["rabia_devkv_program_builds_total"] == 8

        flagged = []
        with jax.log_compiles(), caplog.at_level(logging.WARNING, logger="jax"):
            caplog.clear()
            futs = _walk_the_ladder(
                eng, mixed[1:], hook=lambda: flagged.append(dev.compiled_on_last_call)
            )
            built = [r.getMessage() for r in caplog.records
                     if "Compiling jit(mixed)" in r.getMessage()
                     or "Compiling jit(fused)" in r.getMessage()]
        assert len(futs) > 200 and all(f.done() for f in futs)
        assert all(eng._dev_windows[w] > 0 for w in (1, 2, 4, 8)), eng._dev_windows
        assert set(dev._fused_cache) == sigs
        assert flagged and not any(flagged)
        assert built == []
        assert eng.device_lane_active
        by_rung = {w: eng.metrics.snapshot()[f'rabia_devkv_windows_total{{w="{w}"}}']
                   for w in (1, 2, 4, 8)}
        assert by_rung == eng._dev_windows
        eng.close()

    def test_an_engine_without_a_target_builds_the_parents_signatures(self):
        """One rung, its ``window``: each signature is built where it is
        first needed and nowhere else, and the mixed program keeps its
        ``Gp`` (the GET-bearing waves, rounded up to a power of two)."""
        n = 8
        eng = _mk(n, device=True, window=8)
        dev = eng._dev
        assert dev.rungs == eng._ladder() == (8,)
        get = TestDeviceGetWindows._enc_get
        shards = list(range(n))
        sets = _set_blocks(n, 8, np.random.default_rng(3))
        reads = lambda k: [
            build_block(shards, [[encode_set_bin(f"k{s}_0", "y" * 33)] if s == w % n
                                 else [get(f"k{s}_1")] for s in shards])
            for w in range(k)
        ]
        seen = []
        for batch in (sets, reads(8), reads(3), sets[:2] + reads(1), reads(8)):
            for b in batch:
                eng.submit_block(b)
            eng.flush()
            seen.append(set(dev._fused_cache))
        assert seen == [
            {(8, 1, 8)},
            {(8, 1, 8), ("mix", 8, 1, 16, 8)},
            {(8, 1, 8), ("mix", 8, 1, 16, 8), ("mix", 8, 1, 16, 4)},
            {(8, 1, 8), ("mix", 8, 1, 16, 8), ("mix", 8, 1, 16, 4),
             ("mix", 8, 1, 16, 1)},
            {(8, 1, 8), ("mix", 8, 1, 16, 8), ("mix", 8, 1, 16, 4),
             ("mix", 8, 1, 16, 1)},
        ]
        assert eng._dev_windows == {8: 5}
        eng.close()


class TestDeviceGetWindows:
    """GET-only full-width windows run IN the device lane (read-only
    lookup program): responses are byte-for-byte the host store's GET
    framing, kind boundaries split the FIFO into windows instead of
    demoting, and out-of-envelope reads demote exactly like writes."""

    @staticmethod
    def _enc_get(k: str) -> bytes:
        import struct

        return bytes([2]) + struct.pack("<H", len(k)) + k.encode()

    def _mixed_fifo(self, n, rng):
        out = []
        for w in range(3):
            out.append(
                build_block(
                    list(range(n)),
                    [
                        [encode_set_bin(f"k{s}_{int(rng.integers(0, 3))}", f"v{w}")]
                        for s in range(n)
                    ],
                )
            )
        for w in range(2):  # GET run, including never-set keys
            out.append(
                build_block(
                    list(range(n)),
                    [[self._enc_get(f"k{s}_{w}")] for s in range(n)],
                )
            )
        out.append(
            build_block(
                list(range(n)),
                [[encode_set_bin(f"k{s}_0", "after")] for s in range(n)],
            )
        )
        out.append(
            build_block(
                list(range(n)), [[self._enc_get(f"k{s}_0")] for s in range(n)]
            )
        )
        out.append(
            build_block(
                list(range(n)), [[self._enc_get("missing")] for s in range(n)]
            )
        )
        return out

    def test_mixed_set_get_fifo_byte_identical_no_demotion(self):
        n = 8
        dev = _mk(n, device=True)
        host = _mk(n, device=False)
        fd = [dev.submit_block(b) for b in self._mixed_fifo(n, np.random.default_rng(5))]
        fh = [host.submit_block(b) for b in self._mixed_fifo(n, np.random.default_rng(5))]
        dev.flush()
        host.flush()
        assert dev._dev_active, "GET windows demoted the lane"
        for i, (a, b) in enumerate(zip(fd, fh)):
            ra = [list(map(bytes, g)) for g in a.result()]
            rb = [list(map(bytes, g)) for g in b.result()]
            assert ra == rb, i
        # reads left versions/content untouched: sync down and compare
        dev._demote_device_store()
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want

    def test_intra_block_mixed_ops_run_in_lane(self):
        # a single block interleaving SET and GET across shards used to
        # demote (kind=None); the kind-masked mixed program runs it in
        # the lane, byte-identical to the host path
        n = 8
        dev = _mk(n, device=True)
        host = _mk(n, device=False)

        def fifo():
            out = []
            out.append(
                build_block(
                    list(range(n)),
                    [[encode_set_bin(f"k{s}", f"v{s}")] for s in range(n)],
                )
            )
            for w in range(3):
                cmds = [
                    [encode_set_bin(f"k{s}", f"w{w}")]
                    if s % 2 == w % 2
                    else [self._enc_get(f"k{s}")]
                    for s in range(n)
                ]
                out.append(build_block(list(range(n)), cmds))
            return out

        fd = [dev.submit_block(b) for b in fifo()]
        fh = [host.submit_block(b) for b in fifo()]
        dev.flush()
        host.flush()
        assert dev._dev_active, "intra-block mixed ops demoted the lane"
        for i, (a, b) in enumerate(zip(fd, fh)):
            ra = [list(map(bytes, g)) for g in a.result()]
            rb = [list(map(bytes, g)) for g in b.result()]
            assert ra == rb, i
        dev._demote_device_store()
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want

    @pytest.mark.parametrize("seed", [11, 12, 13])
    def test_random_kind_fuzz_byte_identical(self, seed):
        # random SET/GET/DEL/EXISTS kind per (wave, shard) over deep
        # FIFOs: reads must observe exactly the applies of earlier waves
        # (host FIFO semantics), DEL's data-dependent version bumps must
        # track the host store's counters, responses byte-identical,
        # versions conformant
        from rabia_tpu.apps.kvstore import (
            KVOperation,
            KVOpType,
            encode_op_bin,
        )

        n = 8
        rng = np.random.default_rng(seed)

        def fifo(r):
            out = []
            for w in range(9):
                cmds = []
                for s in range(n):
                    k = f"k{s}_{int(r.integers(0, 2))}"
                    x = r.random()
                    if x < 0.45:
                        cmds.append([encode_set_bin(k, f"v{w}_{s}")])
                    elif x < 0.75:
                        cmds.append([self._enc_get(k)])
                    elif x < 0.9:
                        cmds.append(
                            [encode_op_bin(KVOperation(KVOpType.Delete, k))]
                        )
                    else:
                        cmds.append(
                            [encode_op_bin(KVOperation(KVOpType.Exists, k))]
                        )
                out.append(build_block(list(range(n)), cmds))
            return out

        dev = _mk(n, device=True)
        host = _mk(n, device=False)
        fd = [dev.submit_block(b) for b in fifo(np.random.default_rng(seed))]
        fh = [host.submit_block(b) for b in fifo(np.random.default_rng(seed))]
        dev.flush()
        host.flush()
        assert dev._dev_active
        for i, (a, b) in enumerate(zip(fd, fh)):
            ra = [list(map(bytes, g)) for g in a.result()]
            rb = [list(map(bytes, g)) for g in b.result()]
            assert ra == rb, (seed, i)
        dev._demote_device_store()
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want
        del rng

    def test_get_values_resolve_host_side(self):
        # steady state: GET frames come from the host-retained SET
        # segments via a SNAPSHOT resolver (meta-only readback), never
        # the value planes — and the snapshot survives later evictions
        from rabia_tpu.apps.device_kv import ResolvedGetFrameGroups

        n = 4
        dev = _mk(n, device=True)
        dev.submit_block(
            build_block(
                list(range(n)),
                [[encode_set_bin(f"k{s}", f"val{s}")] for s in range(n)],
            )
        )
        f = dev.submit_block(
            build_block(
                list(range(n)), [[self._enc_get(f"k{s}")] for s in range(n)]
            )
        )
        dev.flush()
        assert isinstance(f._results, ResolvedGetFrameGroups)
        # evict every retained segment AFTER settlement: the settled
        # view's snapshot must still resolve (round-5 review finding)
        dev._dev_vseg.clear()
        dev._dev_vseg_bytes = 0
        frames = [list(map(bytes, g)) for g in f.result()]
        # version 1, found, value text round-trips
        for s, fr in enumerate(frames):
            assert f"val{s}".encode() in fr[0]

    def test_evicted_segment_falls_back_to_value_download(self):
        n = 4
        dev = _mk(n, device=True)
        host = _mk(n, device=False)
        dev._dev_vseg_cap = 1  # evict every segment immediately
        for e in (dev, host):
            for w in range(3):
                e.submit_block(
                    build_block(
                        list(range(n)),
                        [
                            [encode_set_bin(f"k{s}", f"w{w}")]
                            for s in range(n)
                        ],
                    )
                )
                e.flush()  # one window (= one segment) per block
        fd = dev.submit_block(
            build_block(
                list(range(n)), [[self._enc_get(f"k{s}")] for s in range(n)]
            )
        )
        fh = host.submit_block(
            build_block(
                list(range(n)), [[self._enc_get(f"k{s}")] for s in range(n)]
            )
        )
        dev.flush()
        host.flush()
        assert dev._dev_active
        assert bool((dev._dev_floor[:n] > 0).any())  # evictions happened
        assert [list(map(bytes, g)) for g in fd.result()] == [
            list(map(bytes, g)) for g in fh.result()
        ]

    def test_native_pack_gather_matches_numpy(self, monkeypatch):
        # the C one-pass gather (native/hostkernel.cpp rk_pack_gather)
        # must produce byte-identical planes to the numpy gather — the
        # semantics owner — across SET and mixed windows with varied
        # value widths; RABIA_PY_DEVPACK=1 forces the numpy path. The
        # native run is ASSERTED to have engaged (a silent fallback
        # would compare numpy against numpy, passing vacuously).
        from rabia_tpu.apps.kvstore import (
            KVOperation,
            KVOpType,
            encode_op_bin,
        )
        from rabia_tpu.native.build import load_hostkernel

        if load_hostkernel() is None:
            pytest.skip("native host kernel unavailable")
        monkeypatch.delenv("RABIA_PY_DEVPACK", raising=False)
        n, W = 8, 6
        dev = _mk(n, device=True, window=W)
        engaged = []
        orig = type(dev._dev)._native_pack_gather

        def spy(self_, *a, **kw):
            r = orig(self_, *a, **kw)
            engaged.append(r)
            return r

        monkeypatch.setattr(type(dev._dev), "_native_pack_gather", spy)
        rng = np.random.default_rng(9)

        def window(mixed):
            out = []
            for w in range(W):
                cmds = []
                for s in range(n):
                    if mixed and s % 3 == 1:
                        cmds.append(
                            [encode_op_bin(
                                KVOperation(KVOpType.Get, f"k{s % 3}")
                            )]
                        )
                    elif mixed and s % 5 == 2:
                        cmds.append(
                            [encode_op_bin(
                                KVOperation(KVOpType.Delete, f"k{s % 3}")
                            )]
                        )
                    else:
                        v = "v" * int(rng.integers(0, 9)) + str(w)
                        cmds.append([encode_set_bin(f"k{s % 3}", v)])
                out.append(build_block(list(range(n)), cmds))
            return out

        for mixed in (False, True):
            bs = window(mixed)
            allow = "mixed" if mixed else "set"
            engaged.clear()
            g_native = dev._dev._gather_window(bs, allow)
            assert engaged == [True], "native gather did not engage"
            monkeypatch.setenv("RABIA_PY_DEVPACK", "1")
            g_numpy = dev._dev._gather_window(bs, allow)
            monkeypatch.delenv("RABIA_PY_DEVPACK")
            for a, b in zip(g_native, g_numpy):
                assert np.array_equal(a, b), f"divergence (mixed={mixed})"

    def test_eviction_pressure_during_deferred_del_windows(self):
        # segment-cap pressure while DEL-bearing (deferred) windows are
        # in flight: eviction stops at PROVISIONAL segments (their
        # exact version range is unknown until settlement patches
        # them), settlement re-runs the eviction loop, and GETs of
        # evicted versions fall back to the value-plane download —
        # all byte-identical to the host path under a 1-byte cap
        from rabia_tpu.apps.kvstore import (
            KVOperation,
            KVOpType,
            encode_op_bin,
        )

        enc = lambda t, k: encode_op_bin(KVOperation(t, k))
        n = 4
        dev = _mk(n, device=True, window=2)
        host = _mk(n, device=False, window=2)
        dev._dev_vseg_cap = 1  # evict every settled segment immediately

        def stream():
            shards = list(range(n))
            blk = lambda op: build_block(shards, [[op] for _ in shards])
            out = []
            for w in range(3):
                out.append(blk(encode_set_bin(f"k{w}", f"v{w}" * 5)))
            out.append(blk(enc(KVOpType.Delete, "k0")))      # deferred
            out.append(blk(encode_set_bin("k0", "back")))    # deferred
            out.append(blk(enc(KVOpType.Get, "k0")))         # same-pipe read
            out.append(blk(enc(KVOpType.Get, "k1")))         # evicted read
            out.append(blk(enc(KVOpType.Delete, "k2")))      # deferred
            out.append(blk(enc(KVOpType.Get, "k2")))         # deleted read
            out.append(blk(encode_set_bin("k3", "tail")))
            return out

        fd = [dev.submit_block(b) for b in stream()]
        fh = [host.submit_block(b) for b in stream()]
        dev.flush()
        host.flush()
        assert dev._dev_active
        assert dev._dev_defer == 0 and not dev._dev_pipe
        assert bool((dev._dev_floor[:n] > 0).any())  # evictions happened
        for i, (a, b) in enumerate(zip(fd, fh)):
            assert _frames(a) == _frames(b), i
        dev._demote_device_store()
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want

    def test_repromotion_seed_resolves_old_versions(self):
        n = 4
        dev = _mk(n, device=True, device_store_repromote=1)
        host = _mk(n, device=False)
        for e in (dev, host):
            e.submit_block(
                build_block(
                    list(range(n)),
                    [[encode_set_bin(f"k{s}", f"old{s}")] for s in range(n)],
                )
            )
            e.flush()
        # force a demotion (an over-width value is outside the lane
        # envelope; DEL/EXISTS now run in-lane)
        for e in (dev, host):
            e.submit_block(
                build_block(
                    list(range(n)),
                    [[encode_set_bin("other", "x" * 100)] for s in range(n)],
                )
            )
            e.flush()
        assert not dev._dev_active
        # overwrite the wide value so the upload accepts (the attempt
        # at this cycle's START still sees the wide value and declines,
        # re-arming the cooldown), then one more full-width cycle whose
        # start-of-cycle attempt succeeds; then GET the PRE-promotion
        # version: it must resolve from the seed, byte-identical to the
        # host path
        for tag in ("x", "warm"):
            for e in (dev, host):
                e.submit_block(
                    build_block(
                        list(range(n)),
                        [[encode_set_bin("other", tag)] for s in range(n)],
                    )
                )
                e.flush()
        # the re-promotion attempt fires at the start of the NEXT
        # full-width cycle with a served cooldown — that's the GET
        # cycle below, which then runs in-lane (asserted after it)
        fd = dev.submit_block(
            build_block(
                list(range(n)), [[self._enc_get(f"k{s}")] for s in range(n)]
            )
        )
        fh = host.submit_block(
            build_block(
                list(range(n)), [[self._enc_get(f"k{s}")] for s in range(n)]
            )
        )
        dev.flush()
        host.flush()
        assert dev._dev_active
        assert [list(map(bytes, g)) for g in fd.result()] == [
            list(map(bytes, g)) for g in fh.result()
        ]

    def test_repetitive_set_stream_conforms(self):
        # a SET stream of two rows a shard, then a read of what it
        # wrote: responses and final content identical to the host path
        n = 4
        dev = _mk(n, device=True)
        host = _mk(n, device=False)
        for e in (dev, host):
            for w in range(3):
                e.submit_block(
                    build_block(
                        list(range(n)),
                        [
                            [encode_set_bin(f"k{s % 2}", f"v{w % 2}")]
                            for s in range(n)
                        ],
                    )
                )
            e.flush()  # a pure-SET window
            fd = e.submit_block(
                build_block(
                    list(range(n)),
                    [[self._enc_get("k0")] for s in range(n)],
                )
            )
            e.flush()
            if e is dev:
                dev_get = fd
            else:
                host_get = fd
        assert dev._dev_active
        assert [list(map(bytes, g)) for g in dev_get.result()] == [
            list(map(bytes, g)) for g in host_get.result()
        ]
        dev._demote_device_store()
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want

    def test_forty_distinct_rows_a_shard_conform(self):
        # a window in which every wave writes another (key, value) row
        from rabia_tpu.parallel.mesh_engine import _RowSeg

        n = 2
        dev = _mk(n, device=True, window=40)
        host = _mk(n, device=False, window=40)
        for e in (dev, host):
            for w in range(40):
                e.submit_block(
                    build_block(
                        list(range(n)),
                        [
                            [encode_set_bin(f"k{w}", f"v{w}")]
                            for s in range(n)
                        ],
                    )
                )
            e.flush()
        assert dev._dev_active
        assert any(isinstance(sg, _RowSeg) for sg in dev._dev_vseg)
        dev._demote_device_store()
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want

    def test_del_exists_run_in_lane_byte_identical(self):
        # DEL and EXISTS join the device lane's mixed envelope instead
        # of demoting: deterministic sequence covering found DEL,
        # not-found DEL, SET-after-DEL (fresh version continues from
        # the bumped counter), GET-after-DEL (not-found), and EXISTS
        # both ways — responses and final content byte-identical to the
        # host path, no demotion
        from rabia_tpu.apps.kvstore import (
            KVOperation,
            KVOpType,
            encode_op_bin,
        )

        enc = lambda t, k: encode_op_bin(KVOperation(t, k))
        n = 4
        dev = _mk(n, device=True, window=4)
        host = _mk(n, device=False, window=4)

        def stream():
            shards = list(range(n))
            blk = lambda op: build_block(shards, [[op] for _ in shards])
            return [
                blk(encode_set_bin("a", "v1")),
                blk(enc(KVOpType.Delete, "a")),       # found DEL
                blk(enc(KVOpType.Delete, "a")),       # not-found DEL
                blk(enc(KVOpType.Get, "a")),          # not-found GET
                blk(encode_set_bin("a", "v2")),       # SET after DEL
                blk(enc(KVOpType.Exists, "a")),       # true
                blk(enc(KVOpType.Exists, "missing")),  # false
                blk(enc(KVOpType.Get, "a")),          # found GET
                blk(encode_set_bin("b", "v3")),
                blk(enc(KVOpType.Delete, "missing")),  # not-found DEL
            ]

        fd = [dev.submit_block(b) for b in stream()]
        fh = [host.submit_block(b) for b in stream()]
        dev.flush()
        host.flush()
        assert dev._dev_active, "DEL/EXISTS demoted the lane"
        for i, (a, b) in enumerate(zip(fd, fh)):
            assert _frames(a) == _frames(b), i
        dev._demote_device_store()
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want

    def test_del_windows_pipeline_with_deferred_versions(self):
        # DEL-bearing windows PIPELINE (no synchronous drain): version
        # derivation defers to settlement, and every window dispatched
        # while one is in flight inherits the deferral — a later SET's
        # response version must count the earlier DEL's found-dependent
        # bump even though that bump is unknown at its dispatch. This
        # stream is sized so window k+1 (pure SET) dispatches while the
        # DEL window k is still unsettled: wrong-base derivation would
        # shift every subsequent version by the found-DEL count.
        from rabia_tpu.apps.kvstore import (
            KVOperation,
            KVOpType,
            encode_op_bin,
        )

        enc = lambda t, k: encode_op_bin(KVOperation(t, k))
        n = 4
        dev = _mk(n, device=True, window=2)
        host = _mk(n, device=False, window=2)

        def stream():
            shards = list(range(n))
            blk = lambda op: build_block(shards, [[op] for _ in shards])
            out = []
            # wave pairs = windows of 2: [SET, SET] [DEL, DEL] [SET, SET]
            # [GET, EXISTS] [SET, DEL] [GET, GET]
            out.append(blk(encode_set_bin("a", "v0")))
            out.append(blk(encode_set_bin("b", "v1")))
            out.append(blk(enc(KVOpType.Delete, "a")))      # found
            out.append(blk(enc(KVOpType.Delete, "missing")))  # not found
            out.append(blk(encode_set_bin("a", "v2")))  # ver counts the bump
            out.append(blk(encode_set_bin("c", "v3")))
            out.append(blk(enc(KVOpType.Get, "a")))
            out.append(blk(enc(KVOpType.Exists, "b")))
            out.append(blk(encode_set_bin("b", "v4")))
            out.append(blk(enc(KVOpType.Delete, "c")))      # found
            out.append(blk(enc(KVOpType.Get, "b")))
            out.append(blk(enc(KVOpType.Get, "c")))         # not found
            return out

        fd = [dev.submit_block(b) for b in stream()]
        fh = [host.submit_block(b) for b in stream()]
        dev.flush()
        host.flush()
        assert dev._dev_active, "DEL windows demoted the lane"
        assert dev._dev_defer == 0, "deferral bookkeeping leaked"
        assert not dev._dev_pipe
        for i, (a, b) in enumerate(zip(fd, fh)):
            assert _frames(a) == _frames(b), i
        dev._demote_device_store()
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want

    def test_deeper_inflight_pipe_byte_identical(self):
        # device_store_inflight=3 keeps three dispatched-but-unresolved
        # windows in the pipe (the throughput-mode default, with one
        # fetch worker per window); responses and final content must be
        # byte-identical to the host path
        n = 8
        dev = _mk(n, device=True, device_store_inflight=3, window=2)
        host = _mk(n, device=False, window=2)
        rng = np.random.default_rng(21)
        fd = [dev.submit_block(b) for b in self._mixed_fifo(n, rng)]
        fh = [
            host.submit_block(b)
            for b in self._mixed_fifo(n, np.random.default_rng(21))
        ]
        dev.flush()
        host.flush()
        assert dev._dev_active
        assert dev._dev_defer == 0 and not dev._dev_pipe
        for i, (a, b) in enumerate(zip(fd, fh)):
            ra = [list(map(bytes, g)) for g in a.result()]
            rb = [list(map(bytes, g)) for g in b.result()]
            assert ra == rb, i
        dev._demote_device_store()
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want

    def test_deferred_del_window_dirty_rollback(self):
        # a DEL-bearing (deferred) window that reads back DIRTY: the
        # rollback must unwind the deferral bookkeeping (_dev_defer
        # back to 0, provisional segments popped) for BOTH the dirty
        # window and the deferred window pipelined behind it, then the
        # host path must replay everything in submission order —
        # exercises the rollback branch the clean-path test can't
        from rabia_tpu.apps.kvstore import (
            KVOperation,
            KVOpType,
            encode_op_bin,
        )

        n = 2
        mk = lambda device: _mk(
            n,
            device=device,
            device_store_kw={"per_shard_capacity": 4},
            window=4,
        )
        dev, host = mk(True), mk(False)
        shards = list(range(n))
        blk = lambda op: build_block(shards, [[op] for _ in shards])

        warm = [blk(encode_set_bin(f"k{w}", "x")) for w in range(3)]
        # window 1 (DEL-bearing -> deferred): the DEL frees one slot
        # but three new keys need 5 total -> table overflow -> dirty
        w1 = [blk(enc) for enc in (
            encode_op_bin(KVOperation(KVOpType.Delete, "k0")),
            encode_set_bin("k3", "x"),
            encode_set_bin("k4", "x"),
            encode_set_bin("k5", "x"),
        )]
        # window 2 dispatched while window 1 is in flight: inherits
        # the deferral (pure SET behind a DEL window)
        w2 = [blk(encode_set_bin(f"m{w}", "y")) for w in range(4)]

        for b in warm:
            dev.submit_block(b)
        dev.flush()
        assert dev._dev_active
        for b in w1 + w2:
            dev.submit_block(b)
        dev.run_cycle()  # dispatches window 1, flags resolve later
        assert dev._dev_pipe and dev._dev_defer == 1
        dev.flush()
        assert not dev._dev_active, "dirty DEL window must demote"
        assert dev._dev_defer == 0, "rollback leaked deferral count"
        assert not dev._dev_pipe

        for b in warm + w1 + w2:
            host.submit_block(b)
        host.flush()
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want
        assert np.array_equal(dev.next_slot, host.next_slot)

    def test_repetitive_get_stream_conforms(self):
        # a GET stream that repeats two keys a shard: responses stay
        # byte-identical to the host path
        n = 4
        dev = _mk(n, device=True, window=4)
        host = _mk(n, device=False, window=4)
        for e in (dev, host):
            e.submit_block(
                build_block(
                    list(range(n)),
                    [[encode_set_bin(f"k{s % 2}", "v")] for s in range(n)],
                )
            )
            e.flush()

        def gets():
            return [
                build_block(
                    list(range(n)),
                    [[self._enc_get(f"k{s % 2}")] for s in range(n)],
                )
                for _ in range(8)
            ]

        fd = [dev.submit_block(b) for b in gets()]
        fh = [host.submit_block(b) for b in gets()]
        dev.flush()
        host.flush()
        assert dev._dev_active, "a GET window demoted the lane"
        for a, b in zip(fd, fh):
            assert _frames(a) == _frames(b)

    def test_repetitive_mixed_stream_conforms(self):
        # a repetitive INTERLEAVED stream through the MIXED program:
        # responses and final content byte-identical to the host path
        n = 4
        dev = _mk(n, device=True, window=6)
        host = _mk(n, device=False, window=6)

        def stream():
            out = []
            for w in range(4):
                out.append(
                    build_block(
                        list(range(n)),
                        [
                            [encode_set_bin(f"k{s % 2}", "v")]
                            for s in range(n)
                        ],
                    )
                )
                out.append(
                    build_block(
                        list(range(n)),
                        [[self._enc_get(f"k{s % 2}")] for s in range(n)],
                    )
                )
            return out

        fd = [dev.submit_block(b) for b in stream()]
        fh = [host.submit_block(b) for b in stream()]
        dev.flush()
        host.flush()
        assert dev._dev_active, "a mixed window demoted the lane"
        for a, b in zip(fd, fh):
            assert _frames(a) == _frames(b)
        dev._demote_device_store()
        want = _store_content(host.sms[0], n)
        for sm in dev.sms:
            assert _store_content(sm, n) == want

    def test_long_key_get_demotes_byte_identical(self):
        n = 4
        dev = _mk(n, device=True)
        host = _mk(n, device=False)
        for e in (dev, host):
            e.submit_block(
                build_block(
                    list(range(n)),
                    [[encode_set_bin(f"k{s}", "v")] for s in range(n)],
                )
            )
            e.flush()
        gd = dev.submit_block(
            build_block(
                list(range(n)), [[self._enc_get("K" * 100)] for s in range(n)]
            )
        )
        gh = host.submit_block(
            build_block(
                list(range(n)), [[self._enc_get("K" * 100)] for s in range(n)]
            )
        )
        dev.flush()
        host.flush()
        assert not dev._dev_active  # key over the table width: host path
        assert [list(map(bytes, g)) for g in gd.result()] == [
            list(map(bytes, g)) for g in gh.result()
        ]


# -- windows by their distinct rows a shard: one upload form for all -------

_ROWS_W = 40  # waves a window
_ROWS = 32  # distinct (key, value) rows a shard in the "at_max" window
_ODD_SHARD = 5  # the one shard of "one_over" that holds a row more


def _row_op(allow: str, key: str, r: int) -> bytes:
    """Row ``r``'s op under ``allow``; a mixed window interleaves kinds."""
    if allow == "set" or (allow == "mixed" and r % 2 == 0):
        return encode_set_bin(key, f"v{r}")
    return TestDeviceGetWindows._enc_get(key)


def _row_blocks(n: int, allow: str, case: str) -> list:
    """One window of ``_ROWS_W`` full-width blocks whose shards hold, by
    ``case``: one row each; exactly ``_ROWS`` distinct rows each;
    ``_ROWS`` each and one more in one shard; or one more in every shard
    (the windows the dictionary upload used to take, the first two, or
    refuse)."""

    def cmd(s: int, w: int) -> bytes:
        if case == "one_row":
            r = s % 2
        elif case == "at_max":
            r = w % _ROWS
        elif case == "one_over":
            r = w % (_ROWS + (s == _ODD_SHARD))
        else:  # "all_over"
            r = w % (_ROWS + 1)
        return _row_op(allow, f"k{r:02d}", r)

    return [
        build_block(list(range(n)), [[cmd(s, w)] for s in range(n)])
        for w in range(_ROWS_W)
    ]


@pytest.fixture(scope="module")
def pack_tables():
    """One table with zero padding on the S axis (12 shards of 16) and
    one without (16 of 16), on the 8-device CPU mesh."""
    engines = {n: _mk(n, device=True, window=_ROWS_W) for n in (12, 16)}
    assert engines[12]._dev.S == 16 and engines[16]._dev.S == 16
    yield {n: e._dev for n, e in engines.items()}
    for e in engines.values():
        e.close()


@pytest.mark.parametrize("n", [12, 16], ids=["padded", "full"])
@pytest.mark.parametrize("allow", ["set", "get", "mixed"])
@pytest.mark.parametrize(
    "case", ["one_row", "at_max", "one_over", "all_over"]
)
def test_windows_of_few_and_many_rows_conform(n, allow, case):
    """However few distinct rows a window holds, it uploads as rows and
    answers as the host engine does. The window runs on an empty table
    (its reads miss), after a SET window of the same rows, and again
    (its reads hit)."""
    dev = _mk(n, device=True, window=_ROWS_W)
    host = _mk(n, device=False, window=_ROWS_W)
    futs = {}
    for e in (dev, host):
        futs[e] = [
            e.submit_block(b)
            for a in (allow, "set", allow)
            for b in _row_blocks(n, a, case)
        ]
        e.flush()
    assert dev.device_lane_active
    replies = [_frames(f) for f in futs[dev]]
    assert replies == [_frames(f) for f in futs[host]]
    if allow != "set":
        from rabia_tpu.apps.kvstore import _result_bin

        missed = [_result_bin(1, 0) in r for r in replies]
        assert any(missed[:_ROWS_W]) and not any(missed[2 * _ROWS_W :])
    # one program a window kind, keyed on (W, widths) alone
    programs = {"set": _ROWS_W, "get": "get", "mixed": "mix"}
    assert {k[0] for k in dev._dev._fused_cache} == {
        _ROWS_W, programs[allow]
    }
    dev.sync_to_host()
    want = _store_content(host.sms[0], n)
    assert want
    for sm in dev.sms:
        assert _store_content(sm, n) == want
    dev.close()
    host.close()


# -- the pack's plane pool: warm buffers, handed out again only when idle --

_WIDTHS = ((2, 3), (13, 40), (2, 3))  # (key bytes, largest value) a window


def _width_blocks(n: int, allow: str, klen: int, vmax: int, W: int = 5):
    """One window whose keys are ``klen`` bytes and whose values run up
    to ``vmax`` bytes: the pair sets the window's ku / vu buckets."""

    def cmd(s: int, w: int) -> bytes:
        key = f"{(s + w) % 7}".rjust(klen, "k")
        if allow == "set" or (allow == "mixed" and (s + w) % 2 == 0):
            return encode_set_bin(key, "v" * ((s * 5 + w) % (vmax + 1)))
        return TestDeviceGetWindows._enc_get(key)

    return [
        build_block(list(range(n)), [[cmd(s, w)] for s in range(n)])
        for w in range(W)
    ]


def _numpy_planes_on_zeros(dev, blocks, allow) -> tuple:
    """The semantics owner's planes: the numpy parse, and the numpy
    gather into zeroed ones."""
    parsed, ku, vu = dev._parse_window(blocks, allow)
    W, S = len(blocks), dev.S
    planes = (
        np.zeros((W, S), np.int8),
        np.zeros((W, S), np.int16),
        np.zeros((W, S), np.int16),
        np.zeros((W, S, ku), np.uint8),
        np.zeros((W, S, vu), np.uint8),
    )
    dev._gather_into(parsed, *planes)
    return planes


def _gather_into_dirty_pool(dev, blocks, allow) -> tuple:
    """``_gather_window`` with every plane a pooled buffer full of 0xFF."""
    assert dev._gather_window(blocks, allow) is not None  # stocks the pool
    for i in range(len(dev._planes)):
        dev._planes._bufs[i].fill(0xFF)  # (a loop variable would hold one)
    before = dict(dev.pack_buffers)
    got = dev._gather_window(blocks, allow)
    assert dev.pack_buffers == {
        "reused": before["reused"] + 5, "fresh": before["fresh"]
    }
    return got


def _assert_same_planes(got, want) -> None:
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


@pytest.fixture
def native_gather_calls(monkeypatch):
    """What each ``_native_pack_gather`` call returned, in order."""
    from rabia_tpu.apps.device_kv import DeviceKVTable
    from rabia_tpu.native.build import load_hostkernel

    if load_hostkernel() is None:
        pytest.skip("native host kernel unavailable")
    monkeypatch.delenv("RABIA_PY_DEVPACK", raising=False)
    calls = []
    orig = DeviceKVTable._native_pack_gather

    def spy(self_, scan, *planes):
        calls.append(orig(self_, scan, *planes))
        return calls[-1]

    monkeypatch.setattr(DeviceKVTable, "_native_pack_gather", spy)
    return calls


def _packed_by(dev, before: dict) -> dict:
    """Windows packed since ``before`` (a copy of ``dev.pack_windows``),
    by path: what ``devkv_pack_windows_total`` would have grown by."""
    return {k: v - before[k] for k, v in dev.pack_windows.items()}


@pytest.mark.parametrize("n", [12, 16], ids=["padded", "full"])
@pytest.mark.parametrize("allow", ["set", "get", "mixed"])
class TestGatherIntoDirtyPlanes:
    """A reused plane is not zeroed first: every path writes every byte,
    so a plane full of 0xFF ends up the numpy path's on a zeroed one."""

    def test_native_gather_over_changing_widths(
        self, pack_tables, native_gather_calls, n, allow
    ):
        dev = pack_tables[n]
        shapes = set()
        for klen, vmax in _WIDTHS:  # consecutive windows, other buckets
            blocks = _width_blocks(n, allow, klen, vmax)
            want = _numpy_planes_on_zeros(dev, blocks, allow)
            native_gather_calls.clear()
            before = dict(dev.pack_windows)
            got = _gather_into_dirty_pool(dev, blocks, allow)
            assert native_gather_calls == [True, True]
            assert _packed_by(dev, before) == {"native": 2, "numpy": 0}
            _assert_same_planes(got, want)
            shapes.add(got[3].shape[2:] + got[4].shape[2:])
        assert len(shapes) == 2

    def test_bounds_trip_falls_back_to_numpy(
        self, pack_tables, native_gather_calls, monkeypatch, n, allow
    ):
        from rabia_tpu.apps.device_kv import DeviceKVTable

        spy = DeviceKVTable._native_pack_gather

        def short_block(self_, scan, *planes):
            # the C loop writes the first waves' rows, then meets an op
            # that ends past the bytes it is told its block has (the
            # scan saw them all: only memory that changed under the
            # pack could do this)
            scan[0].data_len[-1] //= 2
            return spy(self_, scan, *planes)

        dev = pack_tables[n]
        blocks = _width_blocks(n, allow, 13, 40)
        want = _numpy_planes_on_zeros(dev, blocks, allow)
        monkeypatch.setattr(DeviceKVTable, "_native_pack_gather", short_block)
        native_gather_calls.clear()
        before = dict(dev.pack_windows)
        got = _gather_into_dirty_pool(dev, blocks, allow)
        assert native_gather_calls == [False, False]
        assert _packed_by(dev, before) == {"native": 0, "numpy": 2}
        _assert_same_planes(got, want)

    def test_block_arrays_of_another_layout_are_copied_for_c(
        self, pack_tables, native_gather_calls, n, allow
    ):
        dev = pack_tables[n]
        blocks = _width_blocks(n, allow, 13, 40)
        want = _numpy_planes_on_zeros(dev, blocks, allow)
        for b in blocks[::2]:
            # every other element of an array twice as long, and 32-bit
            # counts: the same numbers, not as C reads them
            b.cmd_sizes = np.repeat(b.cmd_sizes, 2)[::2]
            b.shards = np.repeat(b.shards, 2)[::2]
            b.counts = b.counts.astype(np.int32)
            assert not b.cmd_sizes.flags.c_contiguous
        native_gather_calls.clear()
        before = dict(dev.pack_windows)
        got = _gather_into_dirty_pool(dev, blocks, allow)
        assert native_gather_calls == [True, True]
        assert _packed_by(dev, before) == {"native": 2, "numpy": 0}
        _assert_same_planes(got, want)

    def test_scattered_blocks_take_the_numpy_path(
        self, pack_tables, native_gather_calls, n, allow
    ):
        # blocks that are not the full sorted grid: the scatter covers
        # only the ops' cells, so the planes are cleared first
        dev = pack_tables[n]
        full = _width_blocks(n, allow, 13, 40)
        shards = list(range(n - 1, 0, -2))
        blocks = [
            build_block(
                shards,
                [
                    [bytes(b.data[b.cmd_offsets[s] : b.cmd_offsets[s + 1]])]
                    for s in shards
                ],
            )
            for b in full
        ]
        want = _numpy_planes_on_zeros(dev, blocks, allow)
        before = dict(dev.pack_windows)
        got = _gather_into_dirty_pool(dev, blocks, allow)
        assert native_gather_calls == []
        assert _packed_by(dev, before) == {"native": 0, "numpy": 2}
        _assert_same_planes(got, want)
        covered = np.zeros(dev.S, bool)
        covered[shards] = True
        assert got[1][:, covered].all() and not got[1][:, ~covered].any()


# -- the native window pack: one C scan, one C gather, numpy the owner ------

_K, _VW = 32, 64  # the tables' widest key and value (_mk's defaults)


def _op(code: int, key: bytes, value: bytes = b"") -> bytes:
    """One binary op as the wire has it: u8 opcode | u16 klen LE | key |
    value. Says nothing about what the lane admits."""
    return bytes([code]) + len(key).to_bytes(2, "little") + key + value


def _shape_blocks(n: int, allow: str, shape: str) -> list:
    """A window inside the ``allow`` envelope, by ``shape``: the four op
    kinds interleaved (a mixed window; a SET or GET window has its one
    kind), SETs of empty values among others, keys of exactly K and
    values of exactly VW bytes, one block, or two (a lower rung of a
    table built for five)."""
    W = {"one_block": 1, "lower_rung": 2}.get(shape, 5)

    def cmd(s: int, w: int) -> bytes:
        code = {"set": 1, "get": 2, "mixed": 1 + (s + w) % 4}[allow]
        klen = _K if shape == "widest" else 1 + (3 * s + w) % 11
        key = bytes([97 + (s + w) % 26]) * klen
        if code != 1:
            return _op(code, key)
        vlen = {
            "zero_values": 0 if (s + w) % 8 else 7,
            "widest": _VW,
        }.get(shape, (5 * s + w) % 23)
        return _op(1, key, bytes([48 + s % 10]) * vlen)

    return [
        build_block(list(range(n)), [[cmd(s, w)] for s in range(n)])
        for w in range(W)
    ]


def _both_paths(dev, blocks, allow, monkeypatch) -> tuple:
    """``_gather_window`` as it runs and under ``RABIA_PY_DEVPACK=1``,
    with the windows each run packed, by path."""
    monkeypatch.delenv("RABIA_PY_DEVPACK", raising=False)
    before = dict(dev.pack_windows)
    got = dev._gather_window(blocks, allow)
    packed = _packed_by(dev, before)
    monkeypatch.setenv("RABIA_PY_DEVPACK", "1")
    before = dict(dev.pack_windows)
    want = dev._gather_window(blocks, allow)
    assert _packed_by(dev, before) == {"native": 0, "numpy": 1}
    monkeypatch.delenv("RABIA_PY_DEVPACK")
    return got, want, packed


@pytest.mark.parametrize("n", [12, 16], ids=["padded", "full"])
@pytest.mark.parametrize("allow", ["set", "get", "mixed"])
@pytest.mark.parametrize(
    "shape",
    ["interleaved", "zero_values", "widest", "one_block", "lower_rung"],
)
def test_native_window_pack_matches_numpy(
    pack_tables, native_gather_calls, monkeypatch, n, allow, shape
):
    """The native scan and gather against the numpy path: all five
    planes byte for byte, the bucketed widths with them, and the
    counter says that the first run was native (no case compares numpy
    with numpy)."""
    dev = pack_tables[n]
    blocks = _shape_blocks(n, allow, shape)
    got, want, packed = _both_paths(dev, blocks, allow, monkeypatch)
    assert packed == {"native": 1, "numpy": 0}
    assert native_gather_calls == [True]
    _assert_same_planes(got, want)
    _assert_same_planes(got, _numpy_planes_on_zeros(dev, blocks, allow))
    _parsed, ku, vu = dev._parse_window(blocks, allow)
    assert dev._native_scan(blocks, allow)[1:] == (ku, vu)
    assert (got[3].shape[2], got[4].shape[2]) == (ku, vu)
    if shape == "widest":
        assert ku == _K and (allow == "get" or vu == _VW)
        assert got[1][:, :n].min() == _K
    if shape == "interleaved" and allow == "mixed":
        assert set(np.unique(got[0][:, :n])) == {1, 2, 3, 4}
    if shape == "zero_values" and allow != "get":
        sets = got[0][:, :n] == 1
        assert (got[2][:, :n][sets] == 0).any()
    assert not got[1][:, n:].any() and not got[3][:, n:].any()


def _rebuilt(block, *, shards=None, counts=None, sizes=None, data=None):
    """``block`` with some of its arrays or its bytes replaced."""
    from rabia_tpu.core.blocks import PayloadBlock

    shards = block.shards if shards is None else shards
    return PayloadBlock(
        block.id,
        shards,
        np.full(len(shards), -1, np.int64),
        block.counts if counts is None else counts,
        block.cmd_sizes if sizes is None else sizes,
        block.data if data is None else data,
    )


def _with_op(block, s: int, op: bytes):
    """``block`` with shard ``s``'s one op replaced by ``op``."""
    o = block.cmd_offsets
    sizes = block.cmd_sizes.copy()
    sizes[s] = len(op)
    data = block.data[: o[s]] + op + block.data[o[s + 1] :]
    return _rebuilt(block, sizes=sizes, data=data)


# every way out of the envelope or the grid: what it does to the last
# block of a sound window, and whether numpy then refuses the window
# (None: the caller demotes) or packs it on its scatter path
_WAYS_OUT = {
    "opcode_outside_allow": (
        lambda b, n, allow: _with_op(
            b, 3, _op({"set": 2, "get": 1, "mixed": 9}[allow], b"k")
        ),
        True,
    ),
    "klen_zero": (lambda b, n, allow: _with_op(b, 3, _op(1, b"")), True),
    "klen_over_K": (
        lambda b, n, allow: _with_op(
            b, 3, _op(2 if allow == "get" else 1, b"k" * (_K + 1))
        ),
        True,
    ),
    "vlen_over_VW": (
        lambda b, n, allow: _with_op(b, 3, _op(1, b"k", b"v" * (_VW + 1))),
        True,
    ),
    "get_with_value_bytes": (
        lambda b, n, allow: _with_op(b, 3, _op(2, b"k", b"v")),
        True,
    ),
    "two_ops_in_a_shard": (
        lambda b, n, allow: _rebuilt(
            b,
            counts=np.r_[2, np.ones(n - 2, np.int64)],
            shards=np.arange(n - 1),
        ),
        True,
    ),
    "missing_shard": (
        lambda b, n, allow: build_block(
            list(range(n - 1)),
            [[bytes(c)] for s in range(n - 1) for c in b.commands_for(s)],
        ),
        False,
    ),
    "unsorted_shards": (
        lambda b, n, allow: _rebuilt(b, shards=np.arange(n)[::-1].copy()),
        False,
    ),
    "block_shorter_than_its_headers": (
        lambda b, n, allow: _rebuilt(
            b, sizes=np.full(n, 2, np.int64), data=b.data[: 2 * n]
        ),
        True,
    ),
    "cmd_size_under_3": (
        lambda b, n, allow: _with_op(b, 3, b.data[:2]),
        True,
    ),
}


@pytest.mark.parametrize("allow", ["set", "get", "mixed"])
@pytest.mark.parametrize("way", list(_WAYS_OUT))
def test_window_outside_the_envelope_takes_the_numpy_path(
    pack_tables, native_gather_calls, monkeypatch, allow, way
):
    """The native scan takes no window that has left the grid shape or
    the envelope; the numpy parse then decides, as under
    ``RABIA_PY_DEVPACK=1``: it refuses the window or packs it."""
    n = 12
    dev = pack_tables[n]
    spoil, refused = _WAYS_OUT[way]
    sound = _shape_blocks(n, allow, "interleaved")
    assert dev._native_scan(sound, allow) is not None
    for at in (0, len(sound) - 1):
        blocks = list(sound)
        blocks[at] = spoil(sound[at], n, allow)
        assert dev._native_scan(blocks, allow) is None
        got, want, packed = _both_paths(dev, blocks, allow, monkeypatch)
        assert packed == {"native": 0, "numpy": 1}
        assert native_gather_calls == []
        if refused:
            assert got is None and want is None
        else:
            _assert_same_planes(got, want)


def test_engine_packs_a_mixed_window_natively_and_in_numpy(monkeypatch):
    """One engine packs its windows natively, one under
    ``RABIA_PY_DEVPACK=1``: the same replies, the same table, and
    ``devkv_pack_windows_total`` counts a window each way."""
    from rabia_tpu.native.build import load_hostkernel

    if load_hostkernel() is None:
        pytest.skip("native host kernel unavailable")
    n, W = 12, 5
    windows = [_shape_blocks(n, "set", "interleaved")] + [
        _shape_blocks(n, "mixed", shape)
        for shape in ("interleaved", "zero_values", "widest")
    ]
    replies, tables, counted = {}, {}, {}
    for path in ("native", "numpy"):
        if path == "numpy":
            monkeypatch.setenv("RABIA_PY_DEVPACK", "1")
        else:
            monkeypatch.delenv("RABIA_PY_DEVPACK", raising=False)
        eng = _mk(n, device=True, window=W)
        futs = []
        for blocks in windows:
            futs += [eng.submit_block(_rebuilt(b)) for b in blocks]
            eng.run_cycle()
        eng.flush()
        assert eng.device_lane_active
        replies[path] = [_frames(f) for f in futs]
        snap = eng.metrics.snapshot()
        counted[path] = {
            p: snap[f'rabia_devkv_pack_windows_total{{path="{p}"}}']
            for p in ("native", "numpy")
        }
        assert f'rabia_devkv_pack_windows_total{{path="{path}"}}' in (
            eng.metrics.render_prometheus()
        )
        eng.sync_to_host()
        tables[path] = [_store_content(sm, n) for sm in eng.sms]
        eng.close()
    assert counted["native"] == {"native": len(windows), "numpy": 0}
    assert counted["numpy"] == {"native": 0, "numpy": len(windows)}
    assert replies["native"] == replies["numpy"]
    assert tables["native"] == tables["numpy"] and tables["native"][0]


def _same_window_read_blocks(n: int, W: int, k: int) -> list:
    """Window ``k`` of the holder tests: wave 0 sets one key in every
    shard, the later waves read it on the odd shards and overwrite it on
    the even ones. Every read finds a version of its own window, so the
    window settles through a resolver snapshot over the live segments."""

    def cmd(s: int, w: int) -> bytes:
        if w == 0 or s % 2 == 0:
            return encode_set_bin("key", f"window{k}-wave{w}-shard{s}")
        return TestDeviceGetWindows._enc_get("key")

    return [
        build_block(list(range(n)), [[cmd(s, w)] for s in range(n)])
        for w in range(W)
    ]


def _run_same_window_reads(dev, host, n, W, windows, after_window=None):
    """``windows`` windows of ``_same_window_read_blocks`` through both
    engines, one ``run_cycle`` of ``dev`` a window; returns the (dev,
    host) future pairs."""
    pairs = []
    for k in range(windows):
        for b, bh in zip(
            _same_window_read_blocks(n, W, k), _same_window_read_blocks(n, W, k)
        ):
            pairs.append((dev.submit_block(b), host.submit_block(bh)))
        dev.run_cycle()
        if after_window is not None:
            after_window(k)
    return pairs


def _assert_same_replies(pairs) -> None:
    for fd, fh in pairs:
        assert [list(map(bytes, g)) for g in fd.result()] == [
            list(map(bytes, g)) for g in fh.result()
        ]


class TestPlanePoolHolders:
    """The pool hands a buffer out again only when nothing can still
    read it. Each test keeps one kind of holder over many windows, with
    a segment cap of four windows so that segments do leave the engine,
    then reads through the holder and finds the bytes it was given."""

    N, W, WINDOWS = 8, 4, 12

    def _engines(self):
        dev = _mk(self.N, device=True, window=self.W)
        host = _mk(self.N, device=False, window=self.W)
        return dev, host

    def _drive(self, dev, host, after_window=lambda k: None) -> list:
        def after(k):
            if k == 0:  # segments of one size: keep four windows of them
                dev._dev_vseg_cap = 4 * dev._dev_vseg[-1].nbytes + 1
            after_window(k)

        return _run_same_window_reads(
            dev, host, self.N, self.W, self.WINDOWS, after
        )

    @staticmethod
    def _guard_pool(dev, held_ids) -> None:
        """Fail the moment the pool hands out a buffer in ``held_ids()``."""
        pool = dev._dev._planes
        idle = pool.idle

        def guarded(nbytes):
            buf = idle(nbytes)
            assert buf is None or id(buf) not in held_ids()
            return buf

        pool.idle = guarded

    @staticmethod
    def _segment_buffers(resolvers) -> set:
        return {
            id(plane.base)
            for r in resolvers
            for seg in r.segs
            for plane in (seg.vwin8, seg.vlen, seg.kind)
        }

    def test_unread_replies_keep_their_segments(self):
        dev, host = self._engines()
        pairs = []

        def resolvers():
            out = []
            for fd, _ in pairs:
                if fd.done():
                    for view in (fd._results, getattr(fd._results, "_get", None)):
                        if hasattr(view, "resolver"):
                            out.append(view.resolver)
            return out

        self._guard_pool(dev, lambda: self._segment_buffers(resolvers()))
        pairs.extend(self._drive(dev, host))
        dev.flush()
        host.flush()
        assert dev._dev_active and dev._dev_floor[: self.N].all()
        assert len(resolvers()) == len(pairs) - self.WINDOWS  # wave 0: SETs
        assert dev._dev.pack_buffers["reused"] > 0
        _assert_same_replies(pairs)  # read only now, windows after they settled

    def test_a_resolver_snapshot_keeps_its_segments(self):
        dev, host = self._engines()
        snap, want = [], {}

        def after_window(k):
            if k != 5:
                return
            snap.append(dev._dev_make_resolver())
            for seg in snap[0].segs:
                if seg.provisional:
                    continue
                for s in range(self.N):
                    for v in range(int(seg.start[s]) + 1, int(seg.end[s]) + 1):
                        want[(s, v)] = snap[0](s, v)

        self._guard_pool(dev, lambda: self._segment_buffers(snap))
        pairs = self._drive(dev, host, after_window)
        dev.flush()
        host.flush()
        del pairs  # the snapshot alone holds the segments now
        assert len(want) >= self.N * self.W  # whole windows of versions
        assert not set(snap[0].segs) & set(dev._dev_vseg)  # all evicted
        assert dev._dev.pack_buffers["reused"] > 0
        assert {sv: snap[0](*sv) for sv in want} == want
        assert all(b"window" in v for v in want.values())

    def test_placed_operands_keep_their_planes(self, monkeypatch):
        from rabia_tpu.apps.device_kv import DeviceKVTable

        dev, host = self._engines()
        kept = []
        place = DeviceKVTable._place_ops

        def keeping(self_, ops):
            placed = place(self_, ops)
            if len(kept) < 3:  # three windows' operands, and what they held
                kept.append((placed, [np.array(a) for a in ops]))
            return placed

        monkeypatch.setattr(DeviceKVTable, "_place_ops", keeping)
        pairs = self._drive(dev, host)
        dev.flush()
        host.flush()
        assert len(kept) == 3 and dev._dev.pack_buffers["reused"] > 0
        for placed, copies in kept:
            for on_device, copy in zip(placed, copies):
                assert np.array_equal(np.asarray(on_device), copy)
        _assert_same_replies(pairs)


class TestPlanePoolBounds:
    def test_steady_windows_stop_allocating(self):
        # same-shape windows whose replies are read and dropped, a
        # segment cap of one window: once the pipe is full every plane
        # of every window is a reused buffer
        n, W = 8, 4
        dev = _mk(n, device=True, window=W)
        dev._dev_vseg_cap = 1
        pool = dev._dev._planes
        counter = lambda o: dev.metrics.counter(
            "devkv_pack_buffers_total", "", {"outcome": o}
        ).value()
        fresh = []
        for k in range(24):
            futs = [
                dev.submit_block(b) for b in _same_window_read_blocks(n, W, k)
            ]
            dev.run_cycle()
            del futs
            fresh.append(pool.outcomes["fresh"])
        dev.flush()
        assert dev._dev_active
        assert fresh[-1] == fresh[8] <= 5 * 6  # six windows' worth, no more
        assert pool.outcomes["reused"] == 5 * 24 - fresh[-1]
        assert counter("fresh") == fresh[-1]
        assert counter("reused") == pool.outcomes["reused"]
        assert len(pool) == fresh[-1]

    def test_pool_keeps_no_more_than_its_cap(self):
        # every reply kept unread under the default segment cap: no
        # buffer of a value plane ever comes back, the pool allocates
        # for them every window and forgets the oldest over its cap
        from rabia_tpu.apps.device_kv import _PLANE_POOL_CAP

        n, W = 8, 4
        dev = _mk(n, device=True, window=W)
        host = _mk(n, device=False, window=W)
        pool = dev._dev._planes
        sizes = []
        pairs = _run_same_window_reads(
            dev, host, n, W, _PLANE_POOL_CAP, lambda k: sizes.append(len(pool))
        )
        assert max(sizes) <= _PLANE_POOL_CAP
        dev.flush()
        host.flush()
        assert len(pool) == _PLANE_POOL_CAP
        assert pool.outcomes["fresh"] >= 3 * _PLANE_POOL_CAP
        _assert_same_replies(pairs)


# -- the mixed window's value resolve: reads and writes once a window ------
#
# The scan over the waves carries no value plane: it records which slot
# each GET reads and each SET writes, and ``_resolve_values`` fetches and
# writes the rows once, after it. The cases below are one window in one
# shard each (every shard runs the same waves with values of its own).

_RESOLVE_W = 8  # waves a window: most cases fill fewer, the rest are fillers


def _resolve_case(case: str, P: int, vlen: int) -> tuple:
    """``(prefill waves, the window's waves)``, a wave being
    ``(opcode letter, key, value)`` for every shard."""
    val = lambda tag: (tag * vlen)[:vlen]
    if case == "set_then_get":
        return [], [("S", "k", val("a")), ("G", "k", "")]
    if case == "set_set_get":
        return [], [("S", "k", val("a")), ("S", "k", val("b")), ("G", "k", "")]
    if case == "get_before_its_set":
        return [("S", "k", val("o"))], [
            ("G", "k", ""), ("S", "k", val("n")), ("G", "k", ""),
        ]
    if case == "del_then_insert_into_freed_slot":
        # at P == 1 the freed slot is the only one j can take
        return [("S", "k", val("o"))], [
            ("D", "k", ""), ("S", "j", val("n")), ("G", "j", ""), ("G", "k", ""),
        ]
    if case == "set_refused_by_full_shard":
        fill = [("S", f"f{i}", val("f")) for i in range(P)]
        return fill, [("S", "one_more", val("x")), ("G", "f0", "")]
    if case == "fillers_past_depth":
        return [("S", "k", val("o"))], [("G", "k", "")]
    if case == "a_full_window":
        return [], [("S", "k", val("a"))] + [
            ("G", "k", "") if t % 2 else ("S", "k", val("bcdefgh"[t // 2]))
            for t in range(1, _RESOLVE_W)
        ]
    raise KeyError(case)


def _resolve_block(n: int, wave: tuple):
    from rabia_tpu.apps.kvstore import KVOperation, KVOpType, encode_op_bin

    op, key, value = wave
    if op == "S":
        cmds = [[encode_set_bin(key, f"{s}{value}"[: len(value)])] for s in range(n)]
    else:
        kind = {"G": KVOpType.Get, "D": KVOpType.Delete}[op]
        cmds = [[encode_op_bin(KVOperation(kind, key))] for _ in range(n)]
    return build_block(list(range(n)), cmds)


@pytest.mark.parametrize("vlen", [5, 64], ids=["v5", "v64"])
@pytest.mark.parametrize("P", [1, 4, 64], ids=["p1", "p4", "p64"])
@pytest.mark.parametrize(
    "case",
    [
        "set_then_get", "set_set_get", "get_before_its_set",
        "del_then_insert_into_freed_slot", "set_refused_by_full_shard",
        "fillers_past_depth", "a_full_window",
    ],
)
def test_value_resolve_in_one_window(case, P, vlen):
    n = 2
    pre, win = _resolve_case(case, P, vlen)
    engines = []
    for device in (True, False):
        e = _mk(
            n, device=device, window=_RESOLVE_W,
            device_store_kw={"per_shard_capacity": P, "value_width": 64},
        )
        if device:
            # no host segment outlives its window: every GET frame below
            # is made of the bytes the device program answered with
            e._dev_vseg_cap = 1
        for k in range(0, len(pre), _RESOLVE_W):
            for wave in pre[k : k + _RESOLVE_W]:
                e.submit_block(_resolve_block(n, wave))
            e.flush()
        futs = [e.submit_block(_resolve_block(n, wave)) for wave in win]
        e.flush()
        engines.append((e, futs))
    (dev, fd), (host, fh) = engines
    refused = case == "set_refused_by_full_shard"
    assert dev._dev_active != refused
    for t, (a, b) in enumerate(zip(fd, fh)):
        assert _frames(a) == _frames(b), (case, t)
    want = _store_content(host.sms[0], n)
    if not refused:
        assert dev._dev_value_fetch["unused"] == 0
        rows = dev._dev.dump()["rows"]
        assert {(s, k): (v, ver) for s, k, v, ver in rows} == want
        dev._demote_device_store()
    for sm in dev.sms:
        assert _store_content(sm, n) == want


def _random_table_and_windows(seed: int, n: int, P: int, W: int, mesh):
    """A table with garbage rows under its clear ``used`` bits and three
    random windows of all four kinds over a keyspace a little larger than
    a shard holds: inserts into freed slots, refused SETs, fillers."""
    from rabia_tpu.apps.device_kv import DeviceKVTable, DeviceWindowOps
    from rabia_tpu.parallel.mesh import MeshPhaseKernel

    rng = np.random.default_rng(seed)
    tab = DeviceKVTable(n, MeshPhaseKernel(n, 3, mesh), per_shard_capacity=P)
    state = list(tab.state)
    state[4] = tab._put_shards(
        rng.integers(0, 2**32, size=state[4].shape, dtype=np.uint32)
    )
    windows = []
    for depth in (W, W, W - 5):
        S = tab.S
        kind = np.zeros((W, S), np.int8)
        klen = np.zeros((W, S), np.int16)
        vlen = np.zeros((W, S), np.int16)
        kwin = np.zeros((W, S, tab.K), np.uint8)
        vwin = np.zeros((W, S, tab.VW), np.uint8)
        kk = rng.choice([1, 2, 3, 4], size=(depth, n), p=(0.4, 0.3, 0.15, 0.15))
        kid = rng.integers(0, P + 3, size=(depth, n))
        kl = 4 + kid % 5
        kb = np.zeros((depth, n, tab.K), np.uint8)
        kb[..., 0], kb[..., 1], kb[..., 2:4] = kid, kid >> 8, 0x41
        for j in range(4, 9):
            kb[..., j] = np.where(kl > j, 0x30 + kid % 7, 0)
        vl = np.where(kk == 1, rng.integers(0, tab.VW + 1, size=(depth, n)), 0)
        vb = rng.integers(0, 256, size=(depth, n, tab.VW), dtype=np.uint8)
        vb[np.arange(tab.VW) >= vl[..., None]] = 0
        kind[:depth, :n], klen[:depth, :n], vlen[:depth, :n] = kk, kl, vl
        kwin[:depth, :n], vwin[:depth, :n] = kb, vb
        ops = DeviceWindowOps(
            klen, vlen, kwin.view(np.uint32), vwin.view(np.uint32)
        )
        gets = np.nonzero((kind >= 2).any(1))[0]
        windows.append((depth, kind, ops, gets))
    # the last window reads back a third of its GET waves: Gp < W
    depth, kind, ops, gets = windows[-1]
    windows[-1] = (depth, kind, ops, gets[: max(1, len(gets) // 3)])
    return tab, tuple(state), windows


def _same_outputs(got, want) -> None:
    import jax

    got = jax.tree_util.tree_leaves(got)
    want = jax.tree_util.tree_leaves(want)
    assert len(got) == len(want) == 10  # seven planes, flags, meta, gval
    for i, (a, b) in enumerate(zip(got, want)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, i
        assert np.array_equal(a, b), (i, int((a != b).sum()))


def _run_both_programs(tab, state, windows, reference=None) -> list:
    """Chain the windows through ``mixed_apply`` (and the per-wave
    reference, when given, from the same states); the outputs of each."""
    S, W = tab.S, windows[0][1].shape[0]
    alive = np.ones((S, 3), bool)
    base = np.zeros(S, np.int32)
    outs = []
    for depth, kind, ops, gets in windows:
        new = tab.mixed_apply(alive, base, depth, kind, gets, ops, W=W, state=state)
        if reference is not None:
            gidx = np.zeros(new[3].shape[0], np.int32)
            gidx[: len(gets)] = gets
            old = reference(
                state, alive, base, np.int32(depth), kind, gidx, ops,
                W=W, max_phases=4,
            )
            _same_outputs(new, old)
        outs.append(new)
        state = new[0]
    return outs


@pytest.mark.parametrize(
    "seed,n,P,W",
    [(s, 16, 8, 32) for s in range(6)] + [(100, 8, 1, 8), (101, 8, 64, 16)],
)
def test_mixed_program_equals_the_per_wave_program(seed, n, P, W):
    # all four outputs, bit for bit, every word of the new value plane in
    # slots whose used bit is clear included
    import jax

    from per_wave_mixed import build_per_wave_mixed

    tab, state, windows = _random_table_and_windows(
        seed, n, P, W, make_mesh(jax.devices()[:1])
    )
    outs = _run_both_programs(
        tab, state, windows, build_per_wave_mixed(tab, tab.K4, tab.VW4)
    )
    flags = np.array([np.asarray(o[1]) for o in outs])
    assert flags[:, 0].all()  # every window decided
    if P < 64:
        assert flags[:, 1].any()  # and some SET met a full shard


def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    from jax.extend import core as jcore

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _scans(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _scans(sub)


def test_no_scan_of_the_mixed_program_carries_the_value_plane():
    import jax

    n, P, W = 16, 8, 4
    tab, state, windows = _random_table_and_windows(
        3, n, P, W + 5, make_mesh(jax.devices()[:1])
    )
    depth, kind, ops, gets = windows[0]
    fn = tab._build_mixed(tab.K4, tab.VW4, 8)
    closed = jax.make_jaxpr(
        lambda *a: fn(*a, W=W + 5, max_phases=4)
    )(state, np.ones((tab.S, 3), bool), np.zeros(tab.S, np.int32),
      np.int32(depth), kind, np.zeros(8, np.int32), ops)
    plane = (tab.S, P, tab.VW4)
    assert tuple(state[4].shape) == plane != tuple(state[1].shape)
    scans = list(_scans(closed.jaxpr))
    carried = [
        tuple(v.aval.shape)
        for eqn in scans
        for v in eqn.invars[
            eqn.params["num_consts"] : eqn.params["num_consts"]
            + eqn.params["num_carry"]
        ]
    ]
    assert (tab.S, P) in carried  # the scan over the waves was looked at
    assert plane not in carried
    # nor does any scan read or emit it by the wave
    assert not [
        v.aval.shape
        for eqn in scans
        for v in list(eqn.invars) + list(eqn.outvars)
        if tuple(v.aval.shape)[-3:] == plane
    ]


def test_sharded_mixed_program_equals_one_device_and_adds_no_collective():
    import re

    import jax

    from per_wave_mixed import build_per_wave_mixed

    n, P, W = 16, 8, 16
    results, texts = [], {}
    for devices in (1, 4):
        tab, state, windows = _random_table_and_windows(
            7, n, P, W, make_mesh(jax.devices()[:devices])
        )
        results.append(_run_both_programs(tab, state, windows))
        if devices == 4:
            depth, kind, ops, gets = windows[0]
            args = (
                state, tab.kernel.place(np.ones((tab.S, 3), bool)),
                tab._put_shards(np.zeros(tab.S, np.int32)), np.int32(depth),
                tab._put_waves(kind), np.zeros(16, np.int32),
                tab._place_ops(ops),
            )
            for name, fn in (
                ("resolve_once", tab._build_mixed(tab.K4, tab.VW4, 16)),
                ("per_wave", build_per_wave_mixed(tab, tab.K4, tab.VW4)),
            ):
                texts[name] = (
                    fn.lower(*args, W=W, max_phases=4).compile().as_text()
                )
    for one, four in zip(*results):
        _same_outputs(four, one)

    def collectives(text: str) -> list:
        """``(collective, the jax op that asked for it)`` of each one."""
        found = re.findall(
            r"= \S+ (all-gather|all-reduce|all-to-all|collective-permute|"
            r"reduce-scatter|collective-broadcast)(?:-start)?\("
            r".*?op_name=\"([^\"]*)\"", text,
        )
        return sorted(found)

    # the shard is the resolve's batch axis, so a table split four ways
    # needs nothing from another device. What the per-wave program had
    # stays: the consensus's exchange over the replica axis and the
    # scalar reductions behind the three flags
    got = collectives(texts["resolve_once"])
    assert got == collectives(texts["per_wave"])
    assert got and all(
        op == "all-reduce" or "/consensus/" in name for op, name in got
    )
    assert not [name for _, name in got if "value_resolve" in name]
    assert "value_resolve" in texts["resolve_once"]

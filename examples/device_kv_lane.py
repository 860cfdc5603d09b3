"""Device-resident KV lane walkthrough: the fastest path in the framework.

With ``device_store=True`` the KV table itself lives on the device and
"decide the window + apply every decided op" is ONE fused program per
window — version responses derive host-side, so a SET window's readback
is 12 bytes. Windows pipeline three deep (``device_store_inflight``),
SET/GET/DEL/EXISTS interleavings run kind-masked mixed windows, and
anything outside the lane's envelope demotes to the host path and
re-promotes automatically. This demo drives every lane transition:

  1. full-width SET waves through the fused device windows;
  2. GET waves answered from device meta + host-retained segments;
  3. mixed SET/GET/DEL waves (deferred version derivation);
  4. client-observed settle latency via ``governor_stats()``;
  5. a crash that demotes the lane mid-stream, then heals and
     RE-PROMOTES it — with state identical throughout.

Run: python examples/device_kv_lane.py
(uses whatever devices jax exposes; force a virtual mesh with
 JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8)
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from rabia_tpu.apps.kvstore import (
    KVOperation,
    KVOpType,
    encode_op_bin,
    encode_set_bin,
)
from rabia_tpu.apps.vector_kv import VectorShardedKV
from rabia_tpu.core.blocks import build_block
from rabia_tpu.core.compile_cache import place_compile_cache
from rabia_tpu.parallel import MeshEngine


def main() -> int:
    place_compile_cache()
    S, R = 16, 5
    eng = MeshEngine(
        lambda: VectorShardedKV(S, capacity=1 << 12),
        n_shards=S,
        n_replicas=R,
        window=4,
        device_store=True,
        device_store_repromote=2,
    )
    shards = list(range(S))
    blk = lambda op_for: build_block(shards, [[op_for(s)] for s in shards])
    enc = lambda t, k: encode_op_bin(KVOperation(t, k))

    # 1. SET waves: fused decide+apply, 12-byte readback per window
    futs = [
        blk(lambda s, w=w: encode_set_bin(f"k{s}", f"v{w}")) for w in range(8)
    ]
    futs = [eng.submit_block(b) for b in futs]
    eng.flush()
    # SET frames carry host-DERIVED versions (never transferred): the
    # 8th write of every key reports version 8 (frame layout:
    # u8 kind, u32-LE version, u8 has-value — vector_kv._RESP_DT)
    ver8 = int.from_bytes(bytes(futs[-1].result()[0][0])[1:5], "little")
    print(
        f"8 SET waves x {S} shards committed in {eng.cycles} dispatches; "
        f"device lane active: {eng.device_lane_active}; k0 at version {ver8}"
    )

    # 2. GET waves: meta-only readback, values resolve host-side
    g = eng.submit_block(blk(lambda s: enc(KVOpType.Get, f"k{s}")))
    eng.flush()
    frame = bytes(g.result()[0][0])
    print(f"GET k0 -> frame kind {frame[0]} (0=found), {len(frame)}B frame")

    # 3. mixed SET/GET/DEL wave: one kind-masked dispatch, DEL's
    # found-dependent version bump derives at settlement
    def mixed(s):
        if s % 3 == 0:
            return encode_set_bin(f"k{s}", "rewritten")
        if s % 3 == 1:
            return enc(KVOpType.Get, f"k{s}")
        return enc(KVOpType.Delete, f"k{s}")

    m = eng.submit_block(blk(mixed))
    eng.flush()
    kinds = {0: "SET", 1: "GET", 2: "DEL"}
    print(
        "mixed wave settled:",
        ", ".join(
            f"shard{s}({kinds[s % 3]})={bytes(m.result()[s][0])[:7]!r}"
            for s in (0, 1, 2)
        ),
    )

    # 4. the latency a client actually observes through the pipe
    st = eng.governor_stats()
    print(
        f"pipe depth {st['inflight']}, client settle p99 "
        f"{st['settle_p99_ms']}ms over the last windows"
    )

    # 5. crash -> quorum holds (f=2 of 5) -> lane rides through;
    # a majority crash demotes; heal -> the lane RE-PROMOTES
    eng.crash_replica(0)
    eng.crash_replica(1)
    f1 = eng.submit_block(blk(lambda s: encode_set_bin(f"k{s}", "minority")))
    eng.flush()
    assert f1.done()
    print(
        f"2/{R} replicas crashed: lane still active: "
        f"{eng.device_lane_active}"
    )
    eng.crash_replica(2)  # no quorum: the next window reads back dirty
    f2 = eng.submit_block(blk(lambda s: encode_set_bin(f"k{s}", "parked")))
    try:
        eng.flush(max_cycles=3)
    except Exception as e:
        print(f"3/{R} crashed: {type(e).__name__} (no quorum; demoted)")
    eng.heal_replica(0)
    eng.heal_replica(1)
    eng.heal_replica(2)
    eng.flush()
    assert f2.done()
    # a few clean full-width cycles re-promote the device lane
    for w in range(6):
        eng.submit_block(blk(lambda s, w=w: encode_set_bin(f"k{s}", f"z{w}")))
    eng.flush()
    print(f"healed; device lane re-promoted: {eng.device_lane_active}")

    # state is identical on every replica, across every lane transition
    eng.sync_to_host()  # sync device table down for inspection
    want = eng.sms[0].store.get(5, b"k5")
    assert all(sm.store.get(5, b"k5") == want for sm in eng.sms)
    print(f"k5 on every replica: {want[0].decode()} (version {want[1]})")
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())

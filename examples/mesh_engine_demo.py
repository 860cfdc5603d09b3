"""MeshEngine walkthrough: the full SMR stack on the device plane.

Consensus replicas live on a mesh axis (vote exchange = collectives);
deciding a window of slots per shard is ONE device dispatch. This demo
commits through the columnar vector store, survives a minority crash,
stalls without quorum, heals, and resumes from a checkpoint.

Run: python examples/mesh_engine_demo.py
(uses whatever devices jax exposes; force a virtual mesh with
 JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8)
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from rabia_tpu.apps.kvstore import encode_set_bin
from rabia_tpu.apps.vector_kv import VectorShardedKV
from rabia_tpu.core.compile_cache import place_compile_cache
from rabia_tpu.core.errors import RabiaError
from rabia_tpu.parallel import MeshEngine


def main() -> int:
    place_compile_cache()
    S, R = 8, 5
    eng = MeshEngine(
        lambda: VectorShardedKV(S, capacity=1 << 12),
        n_shards=S,
        n_replicas=R,
        window=4,
    )

    # 1. commit a wave of binary SET ops (bulk apply_block path)
    futs = [
        eng.submit([encode_set_bin(f"user{i}", f"balance{i}")], shard=i % S)
        for i in range(24)
    ]
    applied = eng.flush()
    assert all(f.done() for f in futs)
    print(f"committed {applied} batches in {eng.cycles} device dispatches")

    # 2. replicas hold identical state
    v = eng.sms[0].store.get(3, b"user3")
    assert all(sm.store.get(3, b"user3") == v for sm in eng.sms)
    print(f"user3 on every replica: {v[0].decode()} (version {v[1]})")

    # 3. minority crash: f=2 of 5 may fail, commits continue
    eng.crash_replica(0)
    eng.crash_replica(1)
    f = eng.submit([encode_set_bin("after", "crash")], shard=0)
    eng.flush()
    print("2/5 crashed, still committing:", f.result()[0][:6], "...")

    # 4. majority crash: no quorum, progress stalls (futures stay pending)
    eng.crash_replica(2)
    g = eng.submit([encode_set_bin("never", "lands")], shard=1)
    try:
        eng.flush(max_cycles=3)
    except RabiaError as e:
        print(f"3/5 crashed: {e}")
    assert not g.done()

    # 5. heal: the parked shard re-runs its window and the batch commits
    eng.heal_replica(2)
    eng.flush()
    print("healed, stalled batch committed:", g.done())

    # 6. checkpoint -> fresh engine -> restore -> resume
    ckpt = eng.checkpoint()
    eng2 = MeshEngine(
        lambda: VectorShardedKV(S, capacity=1 << 12),
        n_shards=S,
        n_replicas=R,
        window=4,
    )
    eng2.restore(ckpt)
    assert eng2.sms[0].store.get(3, b"user3") is not None
    h = eng2.submit([encode_set_bin("post", "restore")], shard=3)
    eng2.flush()
    print(
        "restored engine resumed at slots",
        eng2.next_slot.tolist(),
        "->",
        h.result()[0][:6],
    )
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())

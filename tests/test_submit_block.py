"""MeshEngine.submit_block: the identity block and every other shape.

The block every deployment sends covers each shard once, in order.
``submit_block`` proves such a block valid by one compare against the
engine's ``arange(n_shards)`` and routes it with that same read-only
array as its ``inv``; every other block (partial-width, permuted,
refused) keeps the range check and the sort. These tests hold the two
paths to one result: the same replies and replica state whichever shape
carried the ops, today's ``ValidationError`` for every bad block, the
counter ``mesh_submit_blocks_total{path=}``, the shared ``inv``, and the
per-entry settle of futures whose list is built only when needed.
"""

from __future__ import annotations

import uuid

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
from rabia_tpu.core.blocks import PayloadBlock, build_block
from rabia_tpu.core.errors import ValidationError
from rabia_tpu.parallel import MeshBlockFuture, MeshEngine, make_mesh

N = 8
KEYS = 3  # keys a shard


def _engine(device: bool, **kw) -> MeshEngine:
    return MeshEngine(
        lambda: VectorShardedKV(N, capacity=1 << 12),
        n_shards=N,
        n_replicas=3,
        mesh=make_mesh(),
        window=4,
        device_store=device,
        **kw,
    )


def _waves(seed: int) -> list[dict[int, bytes]]:
    """Four waves of one op a shard: SETs, then SET/GET mixed, then GETs."""
    rng = np.random.default_rng(seed)
    out = []
    for w, p_get in enumerate((0.0, 0.0, 0.5, 1.0)):
        wave = {}
        for s in range(N):
            key = f"k{s}_{int(rng.integers(0, KEYS))}"
            if rng.random() < p_get:
                wave[s] = encode_op_bin(KVOperation(KVOpType.Get, key))
            else:
                wave[s] = encode_set_bin(key, "v" * int(rng.integers(0, 20)) + f"{w}")
        out.append(wave)
    return out


def _blocks(wave: dict[int, bytes], shape: str, rng) -> list:
    if shape == "identity":
        groups = [list(range(N))]
    elif shape == "permuted":
        groups = [rng.permutation(N).tolist()]
    else:  # two partial-width blocks
        groups = [list(range(0, N, 2)), list(range(1, N, 2))]
    return [build_block(g, [[wave[s]] for s in g]) for g in groups]


def _run(device: bool, shape: str, seed: int):
    eng = _engine(device)
    rng = np.random.default_rng(seed + 1)
    placed = []
    for wave in _waves(seed):
        for blk in _blocks(wave, shape, rng):
            placed.append((blk.shards.tolist(), eng.submit_block(blk)))
    eng.flush(max_cycles=200)
    replies: dict[int, list[bytes]] = {s: [] for s in range(N)}
    for shards, fut in placed:
        for s, entry in zip(shards, fut.result()):
            replies[s].append(bytes(entry[0]))
    if eng.device_lane_active:
        eng.sync_to_host()
    state = [
        {
            (s, k): sm.store.get(s, f"k{s}_{k}".encode())
            for s in range(N)
            for k in range(KEYS)
        }
        for sm in eng.sms
    ]
    counted = dict(eng._submit_blocks)
    eng.close()
    return replies, state, counted


@pytest.mark.parametrize("device", [False, True], ids=["host", "device"])
def test_three_shapes_settle_to_the_same_replies_and_state(device):
    want_replies, want_state, counted = _run(device, "identity", seed=7)
    assert counted == {"identity": 4, "checked": 0}
    assert all(st == want_state[0] for st in want_state)
    assert any(v is not None for v in want_state[0].values())
    for shape, checked in (("permuted", 4), ("partial", 8)):
        replies, state, counted = _run(device, shape, seed=7)
        assert counted == {"identity": 0, "checked": checked}, shape
        assert replies == want_replies, shape
        assert state == want_state, shape


def _raw_block(shards) -> PayloadBlock:
    k = len(shards)
    return PayloadBlock(
        uuid.uuid4(),
        np.asarray(shards, np.int64),
        np.full(k, -1, np.int64),
        np.ones(k, np.int64),
        np.ones(k, np.int64),
        b"X" * k,
    )


@pytest.mark.parametrize(
    "shards, message",
    [
        ([], "empty block"),
        ([0, -1], "block shard out of range"),
        ([0, N], "block shard out of range"),
        ([-1, *range(1, N)], "block shard out of range"),
        ([*range(N - 1), N], "block shard out of range"),
        ([0, 1, 1, *range(3, N)], "block shards must be unique"),
        ([0, 0, *range(2, N - 1), N - 1], "block shards must be unique"),
        ([2, 2], "block shards must be unique"),
    ],
    ids=[
        "empty", "minus-one", "n-shards", "full-minus-one", "full-n-shards",
        "full-dup", "full-dup-at-head", "partial-dup",
    ],
)
def test_bad_block_raises_todays_message(shards, message):
    eng = _engine(device=False)
    with pytest.raises(ValidationError, match=f"(^|: ){message}$"):
        eng.submit_block(_raw_block(shards))
    # refused blocks count as checked and leave nothing behind
    assert eng._submit_blocks == {"identity": 0, "checked": 1}
    assert not eng._full_blocks and eng._queued_entries == 0
    eng.close()


def test_counter_counts_each_path():
    eng = _engine(device=False)
    ops = lambda shards: [[encode_set_bin(f"c{s}", "v")] for s in shards]
    for _ in range(3):
        eng.submit_block(build_block(list(range(N)), ops(range(N))))
    eng.submit_block(build_block(list(range(N))[::-1], ops(range(N))))
    eng.submit_block(build_block([1, 3], ops([1, 3])))
    with pytest.raises(ValidationError):
        eng.submit_block(_raw_block([0, 0]))
    eng.flush()
    snap = eng.metrics.snapshot()
    assert snap['rabia_mesh_submit_blocks_total{path="identity"}'] == 3
    assert snap['rabia_mesh_submit_blocks_total{path="checked"}'] == 3
    assert 'rabia_mesh_submit_blocks_total{path="identity"} 3' in (
        eng.metrics.render_prometheus()
    )
    eng.close()


def test_identity_blocks_share_one_read_only_inv():
    eng = _engine(device=False)
    ops = [[encode_set_bin(f"i{s}", "v")] for s in range(N)]
    perm = [3, 1, 7, 0, 2, 6, 4, 5]
    eng.submit_block(build_block(list(range(N)), ops))
    eng.submit_block(build_block(perm, [ops[s] for s in perm]))
    eng.submit_block(build_block(list(range(N)), ops))
    invs = [inv for _, _, inv in eng._full_blocks]
    assert invs[0] is invs[2] is eng._shard_ids
    assert not invs[0].flags.writeable
    with pytest.raises(ValueError):
        invs[0][0] = 1
    # a permuted block gets a fresh map of its own: shard -> entry
    assert invs[1] is not eng._shard_ids
    assert invs[1].tolist() == [perm.index(s) for s in range(N)]
    eng.flush()
    # the decision log reads the entry of each shard through that inv
    for s in (0, 5):
        log = eng.decisions_for(s)
        assert [b.commands[0].data for _, b in log.values()] == [ops[s][0]] * 3
    assert eng._shard_ids.tolist() == list(range(N))
    eng.close()


def test_demotion_after_identity_blocks_settles_every_entry():
    """Identity blocks staged on the device lane and a parked identity
    read, then a demotion and a scalar submit that sends the staged
    blocks to the per-shard queues: each entry settles on its own, so the
    futures build their lists lazily, and each gets the right reply."""
    eng = _engine(device=True, device_read_lane=True)
    try:
        eng.submit_block(
            build_block(list(range(N)), [[encode_set_bin(f"d{s}", "v0")] for s in range(N)])
        )
        eng.flush(max_cycles=200)
        staged = eng.submit_block(
            build_block(list(range(N)), [[encode_set_bin(f"d{s}", "v1")] for s in range(N)])
        )
        parked = eng.submit_block(
            build_block(
                list(range(N)),
                [[encode_op_bin(KVOperation(KVOpType.Get, f"d{s}"))] for s in range(N)],
            )
        )
        assert eng._read_pending  # the GET block parked on the read lane
        eng._demote_device_store()
        assert not eng._dev_active
        # the parked read re-entered the staged stream with the shared inv
        assert [inv is eng._shard_ids for _, _, inv in eng._full_blocks] == [True, True]
        assert staged._results is None and parked._results is None
        scalar = eng.submit([encode_set_bin("late", "x")], shard=2)
        assert not eng._full_blocks  # demoted to per-shard entries
        eng.flush(max_cycles=200)
        assert staged.done() and parked.done() and scalar.done()
        assert isinstance(staged._results, list) and len(staged._results) == N
        for entry in parked.result():
            assert decode_result_bin(bytes(entry[0])).value == "v1"
        assert eng._submit_blocks == {"identity": 3, "checked": 0}
    finally:
        eng.close()


def test_block_future_builds_its_list_on_the_first_entry_settle():
    f = MeshBlockFuture(3)
    assert f._results is None and not f.done()
    f._settle(1, ["b"])
    assert f._results == [None, ["b"], None] and not f.done()
    f._settle(0, ["a"])
    f._settle(2, ["c"])
    assert f.done() and f.result() == [["a"], ["b"], ["c"]]

    g = MeshBlockFuture(2)
    g._settle_bulk([["x"], ["y"]])
    g._settle(0, ["late"])  # dropped: the block settled in bulk
    assert g.result() == [["x"], ["y"]]
    assert MeshBlockFuture(0).result() == []

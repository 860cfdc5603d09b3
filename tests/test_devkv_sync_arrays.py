"""``DeviceKVTable.dump()`` / ``sync_into()`` read the table as arrays.

The row-by-row dump and rebuild they replaced are kept here as the oracle:
every replica store must come out byte for byte what the old loop built
(keys, values, versions, ``shard_version``, slot for slot), on one device
and over four of the virtual CPU devices, with the shard axis padded past
``n_shards``, with empty and full shards, zero-length values and a deleted
slot. Also here: the sync's spans and counters.
"""

from __future__ import annotations

import struct

import jax
import numpy as np
import pytest

from rabia_tpu.apps.device_kv import TableDump
from rabia_tpu.apps.kvstore import encode_set_bin
from rabia_tpu.apps.vector_kv import _USED, VectorKVStore, VectorShardedKV
from rabia_tpu.core.blocks import build_block
from rabia_tpu.core.tracing import tracer
from rabia_tpu.parallel import MeshEngine, make_mesh

N_SHARDS = 6  # over 4 devices the shard axis pads to 8
CAPACITY = 4
WINDOW = 4


def _engine(n_devices: int) -> MeshEngine:
    return MeshEngine(
        lambda: VectorShardedKV(N_SHARDS, capacity=1 << 10),
        n_shards=N_SHARDS,
        n_replicas=3,
        mesh=make_mesh(jax.devices()[:n_devices]),
        window=WINDOW,
        device_store=True,
        device_store_kw={"per_shard_capacity": CAPACITY},
    )


def _op(code: int, key: str) -> bytes:
    return bytes([code]) + struct.pack("<H", len(key)) + key.encode()


def _get(key: str) -> bytes:
    return _op(2, key)


def _del(key: str) -> bytes:
    return _op(3, key)


def _waves() -> list:
    """One op a shard a wave. Shard 0 stays empty (reads only), shard 1
    fills to capacity, shard 2 sets and deletes (a freed slot among live
    ones), shard 3 holds zero-length values, shard 4 a key of the full 32
    bytes and a value of the full 64, shard 5 overwrites one key."""
    long_key, long_val = "K" * 32, "V" * 64
    return [
        [_get("a"), encode_set_bin("k0", "one"), encode_set_bin("x", "1"),
         encode_set_bin("e0", ""), encode_set_bin(long_key, long_val),
         encode_set_bin("w", "first")],
        [_get("b"), encode_set_bin("k1", "two"), encode_set_bin("y", "22"),
         encode_set_bin("e1", "nonempty"), _get(long_key),
         encode_set_bin("w", "second, longer")],
        [_get("a"), encode_set_bin("k2", "three"), _del("x"),
         encode_set_bin("e1", ""), encode_set_bin("s", "short"),
         encode_set_bin("w", "3")],
        [_get("c"), encode_set_bin("k3", "four"), encode_set_bin("z", "333"),
         _get("e0"), _del("never-there"), _get("w")],
    ]


def _load(eng: MeshEngine) -> None:
    for cmds in _waves():
        eng.submit_block(build_block(list(range(N_SHARDS)), [[c] for c in cmds]))
    eng.flush()
    assert eng.device_lane_active


def _old_dump(dev) -> dict:
    """PR 28's ``dump()``: one tuple of two ``tobytes()`` a record."""
    used, keyw, klen, ver, valw, vlen, sver = (
        np.ascontiguousarray(np.asarray(a)) for a in dev.state
    )
    key_bytes = keyw.view(np.uint8).reshape(dev.S, dev.P, dev.K)
    val_bytes = valw.view(np.uint8).reshape(dev.S, dev.P, dev.VW)
    rows = []
    s_idx, p_idx = np.nonzero(used[: dev.n_shards])
    for s, p in zip(s_idx.tolist(), p_idx.tolist()):
        rows.append(
            (s, key_bytes[s, p, : klen[s, p]].tobytes(),
             val_bytes[s, p, : vlen[s, p]].tobytes(), int(ver[s, p]))
        )
    return {"rows": rows, "shard_version": sver[: dev.n_shards].astype(np.int64)}


def _old_store(dev, d: dict) -> VectorKVStore:
    """PR 28's ``sync_into()``: lists of per-row bytes into ``bulk_set``."""
    rows = d["rows"]
    store = VectorKVStore(dev.n_shards, capacity=max(1 << 10, 2 * len(rows)))
    if rows:
        n = len(rows)
        shards = np.fromiter((r[0] for r in rows), np.int64, n)
        lanes, klens = store._lanes_from_keys([r[1] for r in rows])
        store.bulk_set(shards, lanes, klens, [r[2] for r in rows])
        slot = store._lookup(shards, lanes, klens)
        store.version[slot] = np.fromiter((r[3] for r in rows), np.int64, n)
    store.shard_version[:] = 0
    store.shard_version[: dev.n_shards] = d["shard_version"]
    return store


def _assert_same_store(got: VectorKVStore, want: VectorKVStore) -> None:
    assert got.C == want.C and len(got) == len(want)
    for col in ("state", "key_hash", "key_len", "key_lanes", "shard_col",
                "version", "val_len", "shard_version"):
        assert np.array_equal(getattr(got, col), getattr(want, col)), col
    for s in np.nonzero(want.state == _USED)[0].tolist():
        assert bytes(got._value_at(s)) == bytes(want._value_at(s)), s


@pytest.fixture(params=[1, 4], ids=["1dev", "4dev"])
def loaded(request):
    eng = _engine(request.param)
    _load(eng)
    yield eng
    eng.close()


def test_table_has_the_shapes_the_cases_name(loaded, request):
    dev = loaded._dev
    assert dev.n_devices == request.node.callspec.params["loaded"]
    assert dev.S == (8 if dev.n_devices == 4 else N_SHARDS)  # padded past 6
    used = np.asarray(dev.state[0])[:N_SHARDS]
    assert used.sum(axis=1).tolist() == [0, CAPACITY, 2, 2, 2, 1]
    assert not used[2].all() and used[2].sum() == 2  # x was set, then deleted
    vlen = np.asarray(dev.state[5])[:N_SHARDS]
    assert (vlen[3][used[3]] == 0).all()  # both values of shard 3 are empty


def test_dump_arrays_are_the_old_rows(loaded):
    dev = loaded._dev
    want = _old_dump(dev)
    got = dev.dump()
    assert "rows" not in got  # built on demand, never on sync's path
    assert got["rows"] == want["rows"] and len(want["rows"]) == 11
    assert np.array_equal(got["shard_version"], want["shard_version"])
    assert got["shard_version"].dtype == np.int64
    assert got["shards"].tolist() == [r[0] for r in want["rows"]]
    assert got["versions"].tolist() == [r[3] for r in want["rows"]]
    assert got["vbuf"] == b"".join(r[2] for r in want["rows"])
    assert got["keys"].flags.c_contiguous
    # and back: the arrays of a row list are the dump's own
    back = TableDump.from_rows(want["rows"], want["shard_version"], dev.K)
    for name, a in got.items():
        assert np.array_equal(back[name], a), name


def test_every_replica_store_is_byte_for_byte_the_old_one(loaded):
    dev = loaded._dev
    want = _old_store(dev, _old_dump(dev))
    loaded.sync_to_host()
    assert len(want) == 11
    for sm in loaded.sms:
        _assert_same_store(sm.store, want)
    # reads through the store's own surface
    assert loaded.sms[0].store.get(1, b"k3") == (b"four", 4)
    assert loaded.sms[1].store.get(2, b"x") is None
    assert loaded.sms[2].store.get(3, b"e1") == (b"", 3)
    assert loaded.sms[0].store.get(5, b"w") == (b"3", 3)


def test_an_empty_table_syncs_to_empty_stores():
    eng = _engine(4)
    eng.sync_to_host()
    for sm in eng.sms:
        assert len(sm.store) == 0 and not sm.store.shard_version.any()
    eng.close()


def test_a_dump_that_carries_rows_is_rebuilt_from_them(loaded):
    """``chipbench/control.py``'s lagging replica hands ``sync_into`` a
    dump whose first row it has altered: the rows given win."""
    dev = loaded._dev
    d = dev.dump()
    s, key, val, ver = d["rows"][0]
    altered = dict(d, rows=[(s, key, val, ver - 1)] + d["rows"][1:])
    dev.sync_into(loaded.sms[0], dump=altered)
    dev.sync_into(loaded.sms[1], dump=d)
    assert loaded.sms[0].store.get(s, key) == (val, ver - 1)
    assert loaded.sms[1].store.get(s, key) == (val, ver)


def test_a_key_wider_than_the_host_store_is_refused(loaded):
    dev = loaded._dev
    d = dev.dump()
    wide = dict(d, klens=d["klens"] + 32)  # no such rows: a guard's input
    with pytest.raises(ValueError, match="does not fit"):
        dev.sync_into(loaded.sms[0], dump=wide)


# -- spans and counters -----------------------------------------------------------


@pytest.fixture
def traced():
    was = tracer.enabled
    tracer.reset()
    tracer.enabled = True
    try:
        yield tracer
    finally:
        tracer.enabled = was
        tracer.reset()


def test_a_sync_enters_dump_once_and_rebuild_once_a_replica(traced):
    eng = _engine(4)
    _load(eng)
    traced.reset()
    eng.sync_to_host()
    rep = traced.report()
    assert rep["rabia.sync.dump"]["count"] == 1
    assert rep["rabia.sync.rebuild"]["count"] == len(eng.sms) == 3
    assert not [n for n in rep if n.startswith("rabia.devkv.")]
    snap = eng.metrics.snapshot()
    assert snap["rabia_devkv_sync_rows_total"] == 11
    text = eng.metrics.render_prometheus()
    assert "rabia_devkv_sync_rows_total 11" in text
    assert "rabia_devkv_value_download_bytes_total" in text
    for name in ("rabia.sync.dump", "rabia.sync.rebuild"):
        assert f'rabia_span_seconds_count{{span="{name}"}}' in text, name
    eng.close()


def test_value_download_counter_counts_the_settles_bytes(traced):
    """A read whose version left the host segments makes the settle
    download the window's value plane: its bytes are counted."""
    eng = _engine(4)
    eng._dev_vseg_cap = 1  # evict every segment but the newest
    _load(eng)  # SET windows: no read needs a download yet
    key = "rabia_devkv_value_download_bytes_total"
    before = eng.metrics.snapshot()[key]
    for _ in range(WINDOW):
        cmds = [_get("a"), _get("k0"), _get("y"), encode_set_bin("e0", "now"),
                _get("s"), _get("w")]
        eng.submit_block(build_block(list(range(N_SHARDS)), [[c] for c in cmds]))
    eng.flush()
    assert traced.report()["rabia.cycle.settle.download"]["count"] >= 1
    got = eng.metrics.snapshot()[key] - before
    dev = eng._dev
    assert got > 0 and got % (dev.S * dev.VW) == 0  # whole [waves, S, VW] planes
    eng.close()

"""A mixed device window's derived SET versions: the native pass and numpy.

``rk_book_versions`` (native/hostkernel.cpp) derives a mixed window's
versions in one pass over its kind plane; the numpy expression it replaces
(``mirror + cumsum(kind == 1, axis=0)``) stays the semantics owner and the
fallback where the host kernel does not build. Here both are held to each
other element for element, on random and on degenerate kind planes and
around the mirror bases where a narrower sum would wrap, and a window run
through the device lane on either path gives the same replies, segments
and mirror. Runs on the virtual CPU mesh.
"""

from __future__ import annotations

import numpy as np
import pytest

from rabia_tpu.apps.kvstore import KVOperation, KVOpType, encode_op_bin, encode_set_bin
from rabia_tpu.apps.vector_kv import VectorShardedKV
from rabia_tpu.core.blocks import build_block
from rabia_tpu.native import build
from rabia_tpu.parallel import MeshEngine, make_mesh, mesh_engine

pytestmark = pytest.mark.skipif(
    build.load_hostkernel() is None,
    reason="native hostkernel unavailable (no toolchain)",
)

BASES = (2**31 - 40, 2**32 - 3, 2**62 + 5)


def _numpy_book(kind: np.ndarray, mirror: np.ndarray) -> dict:
    """The numpy lines of the engine's book.versions, as they stood."""
    mirror = mirror.copy()
    slot = int((kind == 2).sum())
    set_cum = np.cumsum(kind == 1, axis=0, dtype=np.int64)
    svers = mirror[None, :] + set_cum
    seg_start = mirror.copy()
    seg_end = seg_start + set_cum[-1]
    sver_delta = set_cum[-1].copy()
    mirror += sver_delta
    return dict(svers=svers, end=seg_end, delta=sver_delta, mirror=mirror, slot=slot)


def _native_book(kind: np.ndarray, mirror: np.ndarray) -> dict:
    """The same through ``_cum_versions``, as book.versions now runs it."""
    mirror = mirror.copy()
    svers, slot, path = mesh_engine._cum_versions(kind, mirror)
    assert path == "native"
    seg_start = mirror.copy()
    seg_end = svers[-1].copy()
    sver_delta = seg_end - seg_start
    mirror[:] = seg_end
    return dict(svers=svers, end=seg_end, delta=sver_delta, mirror=mirror, slot=slot)


def _assert_same(kind: np.ndarray, mirror: np.ndarray) -> None:
    want, got = _numpy_book(kind, mirror), _native_book(kind, mirror)
    for k in want:
        if k == "slot":
            assert got[k] == want[k]
        else:
            assert got[k].dtype == want[k].dtype == np.int64, k
            assert got[k].shape == want[k].shape, k
            assert np.array_equal(got[k], want[k]), k
    assert got["svers"].flags.c_contiguous


@pytest.mark.parametrize("S", (64, 4096, 16384))
@pytest.mark.parametrize("W", (1, 8, 32, 64))
@pytest.mark.parametrize("base", BASES)
def test_random_planes_match_numpy(S, W, base):
    rng = np.random.default_rng(S * 131 + W)
    kind = rng.integers(0, 5, size=(W, S), dtype=np.int8)
    mirror = base + rng.integers(0, 1000, size=S, dtype=np.int64)
    _assert_same(kind, mirror)


@pytest.mark.parametrize("fill", ("all_set", "no_set", "all_get"))
@pytest.mark.parametrize("base", BASES)
def test_degenerate_planes_match_numpy(fill, base):
    W, S = 64, 4096
    if fill == "all_set":
        kind = np.ones((W, S), np.int8)
    elif fill == "all_get":
        kind = np.full((W, S), 2, np.int8)
    else:  # GETs, DELs, EXISTS and empty cells, never a SET
        kind = np.random.default_rng(3).choice(
            np.array([0, 2, 3, 4], np.int8), size=(W, S)
        )
    mirror = np.full(S, base, np.int64)
    _assert_same(kind, mirror)
    got = _native_book(kind, mirror)
    if fill == "all_set":
        assert np.array_equal(got["delta"], np.full(S, W))
    else:
        assert not got["delta"].any()
    assert got["slot"] == (W * S if fill == "all_get" else int((kind == 2).sum()))


def test_strided_plane_is_read_as_its_values():
    """A plane that is not C-contiguous (a column slice of a wider one)
    gives the values numpy gives, not its buffer's neighbours."""
    rng = np.random.default_rng(9)
    wide = rng.integers(0, 3, size=(16, 256), dtype=np.int8)
    kind = wide[:, ::2]
    assert not kind.flags.c_contiguous
    _assert_same(kind, np.arange(128, dtype=np.int64))


# -- through the device lane --------------------------------------------------

N_SHARDS = 8
WINDOW = 4
SHARDS = list(range(N_SHARDS))


def _engine(device: bool = True) -> MeshEngine:
    return MeshEngine(
        lambda: VectorShardedKV(N_SHARDS, capacity=1 << 12),
        n_shards=N_SHARDS,
        n_replicas=3,
        mesh=make_mesh(),
        window=WINDOW,
        device_store=device,
    )


def _op(t: KVOpType, key: str) -> bytes:
    return encode_op_bin(KVOperation(t, key))


def _mixed_window() -> list:
    """WINDOW full-width blocks mixing SETs and GETs per shard: each shard
    SETs its key on some waves and reads it (same-window writes included)
    on the others."""
    out = []
    for w in range(WINDOW):
        cmds = []
        for s in SHARDS:
            if (w + s) % 3 == 0 or w == 0:
                cmds.append([encode_set_bin(f"k{s}", f"v{w}-{s}" * (1 + s % 3))])
            else:
                cmds.append([_op(KVOpType.Get, f"k{s}")])
        out.append(build_block(SHARDS, cmds))
    return out


def _run(eng: MeshEngine, blocks: list) -> list:
    futs = [eng.submit_block(b) for b in blocks]
    eng.flush(max_cycles=400)
    return [[list(map(bytes, g)) for g in f.result()] for f in futs]


def _paths(eng: MeshEngine) -> dict:
    snap = eng.metrics.snapshot()
    return {
        p: snap[f'rabia_mesh_book_versions_windows_total{{path="{p}"}}']
        for p in ("native", "numpy")
    }


def _segment_values(eng: MeshEngine) -> list:
    """Every SET's (shard, version, value) as the host segments resolve it."""
    out = []
    for seg in eng._dev_vseg:
        assert isinstance(seg, mesh_engine._MixedSeg)
        for t, s in zip(*np.nonzero(seg.kind == 1)):
            ver = int(seg.svers[t, s])
            out.append((int(s), ver, seg.value(int(s), ver)))
    return out


def _drive(monkeypatch, path: str, blocks: list) -> tuple:
    with monkeypatch.context() as mp:
        if path == "numpy":
            mp.setattr(build, "load_hostkernel", lambda: None)
        eng = _engine()
        replies = _run(eng, blocks)
        assert eng.device_lane_active
        out = (
            replies,
            _segment_values(eng),
            [seg.svers.copy() for seg in eng._dev_vseg],
            eng._dev_sver.copy(),
            _paths(eng),
        )
        eng.close()
    return out


def test_mixed_window_same_on_both_paths(monkeypatch):
    native = _drive(monkeypatch, "native", _mixed_window())
    numpy_ = _drive(monkeypatch, "numpy", _mixed_window())
    host = _engine(device=False)
    want = _run(host, _mixed_window())
    host.close()
    assert native[0] == numpy_[0] == want
    assert native[1] == numpy_[1] and native[1]
    assert all(v is not None for _, _, v in native[1])
    assert len(native[2]) == len(numpy_[2]) == 1
    assert np.array_equal(native[2][0], numpy_[2][0])
    assert np.array_equal(native[3], numpy_[3])
    assert native[4] == {"native": 1, "numpy": 0}
    assert numpy_[4] == {"native": 0, "numpy": 1}


def _del_stream() -> list:
    """A SET window on the even shards only, then a DEL-bearing window
    whose DEL finds the key on the even shards and not on the odd ones
    (found bits that differ by shard), SETs after it in the same window,
    and a window that reads what is left."""
    k = "d"
    out = []
    for w in range(WINDOW):  # window 1: the even shards write k
        out.append(
            build_block(
                SHARDS,
                [
                    [encode_set_bin(k if s % 2 == 0 else f"o{s}", f"a{w}{s}")]
                    for s in SHARDS
                ],
            )
        )
    # window 2: DEL k everywhere, then SETs and a GET
    out.append(build_block(SHARDS, [[_op(KVOpType.Delete, k)] for _ in SHARDS]))
    out.append(build_block(SHARDS, [[encode_set_bin(f"e{s}", f"b{s}")] for s in SHARDS]))
    out.append(
        build_block(
            SHARDS,
            [
                [encode_set_bin(k, "back") if s % 4 == 0 else _op(KVOpType.Get, k)]
                for s in SHARDS
            ],
        )
    )
    out.append(build_block(SHARDS, [[_op(KVOpType.Get, f"e{s}")] for s in SHARDS]))
    # window 3 (dispatched behind the DEL window): reads and writes
    for w in range(WINDOW):
        out.append(
            build_block(
                SHARDS,
                [
                    [
                        _op(KVOpType.Get, k)
                        if (w + s) % 2
                        else encode_set_bin(f"e{s}", f"c{w}{s}")
                    ]
                    for s in SHARDS
                ],
            )
        )
    return out


@pytest.mark.parametrize("path", ("native", "numpy"))
def test_del_window_found_by_shard_matches_host(monkeypatch, path):
    with monkeypatch.context() as mp:
        if path == "numpy":
            mp.setattr(build, "load_hostkernel", lambda: None)
        dev = _engine()
        got = _run(dev, _del_stream())
        assert dev.device_lane_active
        assert dev._dev_defer == 0 and not dev._dev_pipe
        mirror = dev._dev_sver.copy()
        dev.close()
    host = _engine(device=False)
    want = _run(host, _del_stream())
    versions = _host_shard_versions(host)
    host.close()
    assert got == want
    # a found DEL bumped the even shards' versions, not the odd ones'
    assert list(mirror[:N_SHARDS]) == versions
    assert mirror[0] != mirror[1]


def _host_shard_versions(eng: MeshEngine) -> list:
    """The newest version each shard's host store has handed out."""
    st = eng.sms[0].store
    return [int(v) for v in st.shard_version[:N_SHARDS]]

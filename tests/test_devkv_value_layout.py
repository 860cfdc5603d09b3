"""The order in which a window program hands its GET value plane to the host.

Every program whose values can reach a reply (the mixed program, its lossy
twin, the lean GET window and the read probe) returns the value words flat,
``u32[G, S·VW4]``: the default layout of that shape is the host's row order,
where a chip lays a 3-D ``[G, S, VW4]`` out with the shard on the lanes and
the host had to transpose it. The engine views the fetched plane as
``[G, S, VW4]`` again without a copy, and counts how the device handed each
plane over (``devkv_value_fetch_layout_total{layout=}``). On a mesh the flat
plane stays split over the shard axis, and nothing crosses a device for it.
Runs on the virtual CPU mesh.
"""

from __future__ import annotations

import re

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from rabia_tpu.apps.device_kv import (
    DeviceKVTable,
    DeviceWindowOps,
    GetFrameGroups,
)
from rabia_tpu.apps.kvstore import (
    KVOperation,
    KVOpType,
    encode_op_bin,
    encode_set_bin,
)
from rabia_tpu.apps.vector_kv import VectorShardedKV
from rabia_tpu.core.blocks import build_block
from rabia_tpu.gateway.server import devkv_read_handler
from rabia_tpu.parallel import MeshEngine, make_mesh, mesh_engine
from rabia_tpu.parallel.mesh import SHARD_AXIS, MeshPhaseKernel

N_SHARDS = 8
WINDOW = 4
SHARDS = list(range(N_SHARDS))
PROGRAMS = ("mixed", "mixnet", "get", "read")
DEVICES = (1, N_SHARDS)  # one device, and the conftest's whole mesh
LAYOUTS = ("row_major", "strided")


def _mesh(devices: int):
    return make_mesh(jax.devices()[:devices])


# -- the programs ----------------------------------------------------------


def _table(program: str, devices: int, n: int = 16) -> DeviceKVTable:
    loss = 0.05 if program == "mixnet" else 0.0
    kernel = MeshPhaseKernel(n, 5, _mesh(devices), seed=3, net_loss=loss)
    return DeviceKVTable(n, kernel, per_shard_capacity=8)


def _operands(tab: DeviceKVTable, W: int = 8):
    """An empty window's host operands at the table's widths: every op a
    GET of an empty key, so the programs run their whole read path."""
    S = tab.S
    kind = np.full((W, S), 2, np.int8)
    ops = DeviceWindowOps(
        np.zeros((W, S), np.int16),
        np.zeros((W, S), np.int16),
        np.zeros((W, S, tab.K4), np.uint32),
        np.zeros((W, S, tab.VW4), np.uint32),
    )
    return kind, ops


def _value_plane(tab: DeviceKVTable, program: str, W: int = 8):
    """The value-words handle of one window of ``program``."""
    kind, ops = _operands(tab, W)
    alive = np.ones((tab.S, 5), bool)
    base = np.zeros(tab.S, np.int32)
    gets = np.arange(W, dtype=np.int32)
    if program == "mixed":
        return tab.mixed_apply(alive, base, W, kind, gets, ops, W=W)[3]
    if program == "mixnet":
        slots = tab._put_shards(np.zeros(tab.S, np.int32))
        return tab.mixed_apply_net(alive, slots, W, kind, gets, ops, W=W)[5]
    if program == "get":
        return tab.lookup_window(alive, base, W, (ops.klen, ops.kwin), W=W)[4]
    return tab.lookup_only((ops.klen, ops.kwin), W=W)[3]


def _compiled(tab: DeviceKVTable, program: str, W: int = 8):
    """``program`` compiled for the table's mesh, on placed operands."""
    kind, ops = _operands(tab, W)
    alive = tab.kernel.place(np.ones((tab.S, 5), bool))
    base = tab._put_shards(np.zeros(tab.S, np.int32))
    klen, kwin = tab._put_waves(ops.klen), tab._put_waves(ops.kwin)
    K4, VW4, depth = tab.K4, tab.VW4, np.int32(W)
    if program == "read":
        fn = tab._build_lookup_only(K4)
        return fn.lower(tab.state, klen, kwin, W=W).compile()
    if program == "get":
        fn = tab._build_lookup(K4)
        low = fn.lower(tab.state, alive, base, depth, klen, kwin, W=W, max_phases=4)
        return low.compile()
    rest = (depth, tab._put_waves(kind), np.arange(W, dtype=np.int32), tab._place_ops(ops))
    if program == "mixnet":
        fn = tab._build_mixed(K4, VW4, W, net=True)
        return fn.lower(tab.state, alive, base, *rest, W=W).compile()
    fn = tab._build_mixed(K4, VW4, W)
    return fn.lower(tab.state, alive, base, *rest, W=W, max_phases=4).compile()


def _collectives(text: str) -> list:
    """``(collective, the jax op that asked for it)`` of each one."""
    return sorted(
        re.findall(
            r"= \S+ (all-gather|all-reduce|all-to-all|collective-permute|"
            r"reduce-scatter|collective-broadcast)(?:-start)?\("
            r".*?op_name=\"([^\"]*)\"",
            text,
        )
    )


@pytest.mark.parametrize("devices", DEVICES)
@pytest.mark.parametrize("program", PROGRAMS)
def test_each_program_hands_back_its_value_plane_flat(program, devices):
    """A 2-D ``[G, S·VW4]`` plane, split over the shard axis; the engine's
    ``[G, S, VW4]`` view of it is the fetched array itself."""
    tab = _table(program, devices)
    plane = _value_plane(tab, program)
    G = 8
    assert plane.shape == (G, tab.S * tab.VW4) and plane.dtype == np.uint32
    if devices > 1:
        assert plane.sharding.spec == P(None, SHARD_AXIS)
    (words,), layout = mesh_engine._host_values((plane,), tab.VW4)
    fetched = np.asarray(plane)  # the array the engine fetched
    assert layout == "row_major" and fetched.flags.c_contiguous
    assert words.shape == (G, tab.S, tab.VW4)
    assert np.shares_memory(words, fetched)
    assert not words.any()  # every GET found nothing


@pytest.mark.parametrize("program", PROGRAMS)
def test_flat_plane_stays_on_the_shard_axis_and_adds_no_collective(program, monkeypatch):
    """Compiled for the whole mesh, the plane's output sharding is
    ``P(None, SHARD_AXIS)``: each device's piece is whole rows of its own
    shards, and no all-gather or all-to-all outside the consensus stage
    moves it. The program's collectives are those it has with the plane
    left 3-D."""
    tab = _table(program, N_SHARDS)
    flat = _compiled(tab, program)
    assert flat.output_shardings[-1].spec == P(None, SHARD_AXIS)
    got = _collectives(flat.as_text())
    assert not [
        name
        for op, name in got
        if op in ("all-gather", "all-to-all") and "/consensus/" not in name
    ]
    monkeypatch.setattr(DeviceKVTable, "_host_rows", lambda self, rows: rows)
    left_3d = _compiled(_table(program, N_SHARDS), program)
    assert got == _collectives(left_3d.as_text())


# -- the engine ------------------------------------------------------------

KINDS = ("mixed4", "get", "read", "lossy")


@pytest.fixture(autouse=True)
def every_plane_is_worth_a_worker(monkeypatch):
    """The planes of these engines are a few KB, under the size from which
    a window hands them to a worker: take the floor away, so the second
    falling-back window is prefetched."""
    monkeypatch.setattr(mesh_engine, "_PREFETCH_MIN_BYTES", 0)


def _engine(kind: str, devices: int, device: bool = True) -> MeshEngine:
    return MeshEngine(
        lambda: VectorShardedKV(N_SHARDS, capacity=1 << 12),
        n_shards=N_SHARDS,
        n_replicas=5,
        mesh=_mesh(devices),
        window=WINDOW,
        device_store=device,
        device_read_lane=device and kind == "read",
        net_loss=0.05 if device and kind == "lossy" else 0.0,
    )


def _op(kind: KVOpType, key: str) -> bytes:
    return encode_op_bin(KVOperation(kind, key))


def _set_window(tag: str) -> list:
    return [
        build_block(
            SHARDS,
            [[encode_set_bin(f"{tag}-{s}", f"{tag}{w}" * (1 + s))] for s in SHARDS],
        )
        for w in range(WINDOW)
    ]


def _read_window(kind: str, tag: str) -> list:
    """WINDOW blocks that read ``<tag>-<shard>``. ``mixed4`` and ``lossy``
    mix all four ops by shard: a quarter of the shards SET a key of their
    own in even waves and DEL it in odd ones (a found DEL: the window's
    versions are derived at its settle), the others GET or ask EXISTS of
    the read key."""
    out = []
    for w in range(WINDOW):
        cmds = []
        for s in SHARDS:
            op = s % 4 if kind in ("mixed4", "lossy") else 1
            if op == 0 and w % 2 == 0:
                cmds.append([encode_set_bin(f"w-{s}", f"{tag}{w}")])
            elif op == 0:
                cmds.append([_op(KVOpType.Delete, f"w-{s}")])
            elif op == 2:
                cmds.append([_op(KVOpType.Exists, f"{tag}-{s}")])
            else:
                cmds.append([_op(KVOpType.Get, f"{tag}-{s}")])
        out.append(build_block(SHARDS, cmds))
    return out


def _run(eng: MeshEngine, blocks: list) -> list:
    futs = [eng.submit_block(b) for b in blocks]
    eng.flush(max_cycles=400)
    return futs


def _replies(futs: list) -> list:
    return [[list(map(bytes, g)) for g in f.result()] for f in futs]


def _load(eng: MeshEngine) -> None:
    """Two SET windows, after which the first one's segment is evicted:
    reads of ``a`` must take the value plane from the device."""
    if eng._dev is not None:
        eng._dev_vseg_cap = 1
    _run(eng, _set_window("a"))
    _run(eng, _set_window("b"))
    if eng._dev is not None:
        eng._dev_vseg_cap = 1 << 30


def _counts(eng: MeshEngine) -> dict:
    snap = eng.metrics.snapshot()
    fetch = "rabia_devkv_value_fetch_total{{outcome=\"{}\"}}"
    layout = "rabia_devkv_value_fetch_layout_total{{layout=\"{}\"}}"
    return {
        **{o: snap[fetch.format(o)] for o in ("inline", "prefetched", "unused")},
        **{k: snap[layout.format(k)] for k in LAYOUTS},
    }


def _inline_then_prefetched(kind: str, devices: int) -> tuple:
    """Two falling-back windows on a device engine and on a host engine:
    the first downloads its plane inline, the second has it prefetched.
    Returns the device engine and both windows' replies, each as the two
    engines gave them."""
    dev, host = _engine(kind, devices), _engine(kind, devices, device=False)
    _load(dev)
    _load(host)
    got = []
    for _ in range(2):
        window = _read_window(kind, "a")
        got.append((_replies(_run(dev, window)), _replies(_run(host, window))))
    assert dev.device_lane_active
    host.close()
    return dev, got


@pytest.mark.parametrize("devices", DEVICES)
@pytest.mark.parametrize("kind", KINDS)
def test_replies_are_the_host_engines_inline_and_prefetched(kind, devices):
    dev, got = _inline_then_prefetched(kind, devices)
    for i, (mine, want) in enumerate(got):
        assert mine == want, i
    c = _counts(dev)
    assert (c["inline"], c["prefetched"], c["unused"]) == (1, 1, 0)
    # every plane a settle took came back in the host's row order
    assert (c["row_major"], c["strided"]) == (2, 0)
    dev.close()


def test_a_transposed_fetch_counts_strided_and_replies_the_same(monkeypatch):
    """A device that hands a plane back in another order: the fetch copies
    it into row order (``DeviceKVTable._fetch``), the counter says so, and
    the replies are the same bytes."""
    host_values = mesh_engine._host_values

    def transposed(planes, vw4):
        return host_values(tuple(np.asarray(p).T.copy().T for p in planes), vw4)

    monkeypatch.setattr(mesh_engine, "_host_values", transposed)
    for kind in ("mixed4", "get"):
        dev, got = _inline_then_prefetched(kind, N_SHARDS)
        for i, (mine, want) in enumerate(got):
            assert mine == want, (kind, i)
        c = _counts(dev)
        assert (c["inline"], c["prefetched"]) == (1, 1)
        assert (c["row_major"], c["strided"]) == (0, 2), kind
        dev.close()


@pytest.mark.parametrize("kind", ("mixed4", "get"))
def test_an_inline_plane_is_viewed_where_it_was_fetched(kind, monkeypatch):
    """The settle's own download: the rows each reply holds are views of
    the fetched flat array, as a worker's are."""
    rows, fetched = [], []
    host_values = mesh_engine._host_values
    init = GetFrameGroups.__init__

    def recording_fetch(planes, vw4):
        got = host_values(planes, vw4)
        fetched.append(np.asarray(planes[-1]))
        return got

    def recording(self, shards, found, ver, vlen, val_words):
        rows.append(val_words)
        init(self, shards, found, ver, vlen, val_words)

    monkeypatch.setattr(mesh_engine, "_host_values", recording_fetch)
    monkeypatch.setattr(GetFrameGroups, "__init__", recording)
    dev = _engine(kind, N_SHARDS)
    _load(dev)
    _run(dev, _read_window(kind, "a"))
    assert _counts(dev)["inline"] == 1
    (plane,) = fetched
    assert plane.ndim == 2 and plane.flags.c_contiguous
    assert len(rows) == WINDOW
    for row in rows:
        assert row.shape == (dev.S, dev._dev.VW4)
        assert np.shares_memory(row, plane)
    dev.close()


def test_gateway_probe_reads_the_flat_plane_by_wave_and_shard():
    """The gateway's batched probe serve (``devkv_read_handler``) on the
    eviction edge: it reads the flat plane itself, two waves of it here
    (two keys on shard 0), and answers as the host store does."""
    dev, host = _engine("read", N_SHARDS), _engine("read", N_SHARDS, device=False)
    _load(dev)
    _load(host)
    items = [(s, f"a-{s}".encode()) for s in SHARDS]
    items += [(0, b"b-0"), (3, b"missing")]
    before = dev._read_stats["fallback"]
    got = devkv_read_handler(dev).read_many(items)
    assert dev._read_stats["fallback"] > before
    assert got == devkv_read_handler(host).read_many(items)
    dev.close()
    host.close()

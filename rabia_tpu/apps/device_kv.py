"""Device-resident KV table: the SET-dominant block lane's apply plane.

The round-3 MeshEngine applied every decided wave on the HOST (numpy
hash/probe in :class:`~rabia_tpu.apps.vector_kv.VectorKVStore`), so each
window cycle paid a device->host readback of the decided plane PLUS a
host apply pass. This module moves the table itself onto the device and
fuses "decide the window + apply every decided SET" into ONE jitted
program per window (reference behavior being accelerated:
rabia-kvstore/src/store.rs:313-348 apply_batch). Per window, only a
3-word status vector comes back to the host: version responses are
DERIVED on the host (a clean all-V1 full-width window advances every
shard's version counter by exactly its wave count), so the readback is
pure latency, not bandwidth.

Design (built to move few bytes and make few round trips between host
and device; what each choice is worth on the attached chip is not yet
measured, the value plane's apart):
- the host pre-gathers each op's key/value bytes into fixed-width
  windows bucketed to the LARGEST ACTUAL width in the window (Ku/VWu,
  power-of-two, not the table max) and packs them as u32 words — the
  upload carries ~(key+value) bytes per op, no raw-buffer slack;
- the device table stores keys/values as u32 words too, so matching is
  word compares and updates are one-hot word selects, not byte-wise
  dynamic-index gathers/scatters (which lower poorly on TPU);
- the mixed window's scan over the waves carries every plane but the
  value plane: it decides from ``used``, the keys, the lengths and the
  versions, records the slot each GET reads and each SET writes, and
  the value rows are fetched and written once a window, after it
  (:meth:`DeviceKVTable._resolve_values`, one-hot contractions on the
  matrix unit). Measured on a TPU v5e (4096 shards, 64 waves, a full
  table, a YCSB-B-like window; PERF.md, PR 36): the whole window
  program takes 16.2 ms at 512 slots a shard where the per-wave
  one-hot pass over the plane took 42.1, and 3.6 ms for 2.5 at 64
  slots; with the rows fetched by ``take_along_axis`` and written by
  a scatter, 46.8 and 11.7 ms: dynamic-index gathers do lower poorly;
- per-op versions never travel: ``vers[t, s] = shard_ver[s] + t + 1``
  on a clean window, computed by the engine from its host-side mirror.

Scope (the fast lane, not a general store): full-width blocks of
well-formed binary SET ops, one op per covered shard per wave, keys up
to ``key_lanes*8`` bytes, values up to ``value_width`` bytes, at most
``per_shard_capacity`` distinct keys per shard. Anything outside that
envelope — mixed ops, GETs, scalar batches, table overflow, a fault
outcome — makes the engine DEMOTE: the device state syncs down into the
host replica stores once and the cycle re-runs on the host path, which
remains the semantics owner. Behavioral conformance (versions returned,
final key->value/version content) is pinned against the host store in
tests/test_device_kv.py.

Table layout (all arrays sharded over the mesh shard axis; K4 = K/4,
VW4 = VW/4 u32 words):
  used     bool[S, P]      key_words u32[S, P, K4]  key_len  i32[S, P]
  version  i32[S, P]       val_words u32[S, P, VW4] val_len  i32[S, P]
  shard_ver i32[S]

Matching is a FULL-key compare against all P slots of the op's shard
(P is small; no hashing, no probe loop), so slot layout differs from
the host store but the observable key->(value, version) mapping cannot.
"""

from __future__ import annotations

import ctypes
import os
import sys
from collections.abc import Sequence
from typing import NamedTuple, Optional

import numpy as np

from rabia_tpu.core.tracing import device_annotation
from rabia_tpu.core.types import V0, V1
from rabia_tpu.apps.vector_kv import _RESP_DT

__all__ = ["DeviceKVTable", "DeviceWindowOps", "MixedFrameGroups"]

_SET_HDR = 3  # binary SET op: u8 opcode(1) + u16 klen + key + value
# the window packers' envelopes as rk_pack_scan takes them: bit o set =
# opcode o allowed (1 SET, 2 GET, 3 DEL, 4 EXISTS; _parse_window's masks)
_ALLOW_OPCODES = {"set": 1 << 1, "get": 1 << 2, "mixed": 0b11110}

# buffers the plane pool keeps, idle or handed out: five planes a window
# times the windows that hold them (the pipe's three in flight, the
# retained segments, the one being packed), with room for a second shape
_PLANE_POOL_CAP = 40


class _PlanePool:
    """Byte buffers for the window planes, kept across windows so the
    gather writes into memory the process has already touched (a fresh
    plane of tens of MB is a new mapping, faulted in page by page under
    the gather, every window).

    A buffer is handed out again only when the pool's own reference to
    it is the last one. Every plane is a view of its buffer, and so is
    everything taken from a plane (``.view(np.uint32)`` operands, a
    segment's ``vwin8``, a reply's row): a view keeps its base alive,
    and a ``device_put`` keeps the array it was handed until the runtime
    has stopped reading it (for the array's whole life where the CPU
    backend aliases host memory). So the buffer's reference count sees
    every holder, whoever it is and however long it holds, and nothing
    has to tell the pool about a release. While anything can still read
    a buffer the pool allocates instead: correctness never waits on it.
    """

    def __init__(self) -> None:
        # least recently handed out first
        self._bufs: list = [np.empty(0, np.uint8)]
        # what _holders reads of a buffer that only the pool holds
        self._idle = self._holders(0)
        self._bufs.clear()
        self.outcomes = {"reused": 0, "fresh": 0}

    def _holders(self, i: int) -> int:
        return sys.getrefcount(self._bufs[i])

    def __len__(self) -> int:
        return len(self._bufs)

    def idle(self, nbytes: int) -> Optional[np.ndarray]:
        """An idle buffer of exactly ``nbytes``, now the caller's, or
        None (the caller then asks for a ``fresh`` one)."""
        bufs = self._bufs
        for i in range(len(bufs)):
            if bufs[i].nbytes == nbytes and self._holders(i) == self._idle:
                bufs.append(bufs.pop(i))
                self.outcomes["reused"] += 1
                return bufs[-1]
        return None

    def fresh(self, nbytes: int) -> np.ndarray:
        """A new buffer, kept for later windows; over the cap the pool
        forgets the buffer it handed out longest ago."""
        self._bufs.append(np.empty(nbytes, np.uint8))
        del self._bufs[:-_PLANE_POOL_CAP]
        self.outcomes["fresh"] += 1
        return self._bufs[-1]


def _address(a: np.ndarray) -> int:
    """The address of a C-contiguous array's first byte, for native
    code: ``a.ctypes.data``, at a third of its cost where the array is
    writable (the window packers take three of these a block)."""
    try:
        return ctypes.addressof(ctypes.c_char.from_buffer(a))
    except (TypeError, ValueError):  # read-only, or empty
        return a.ctypes.data


def _bucket(n: int, lo: int = 4) -> int:
    """Round up to a power of two (>= lo, multiple of 4 for u32 views)."""
    b = lo
    while b < n:
        b <<= 1
    return b


class DeviceWindowOps(NamedTuple):
    """One window's ops packed for device apply (host numpy arrays).

    ``kwin``/``vwin`` are the ops' key/value bytes, zero-padded to the
    window's bucketed widths and viewed as u32 words — the fused
    program compares/stores words, never bytes.
    """

    klen: np.ndarray  # i16[W, S] (0 = no op on this (wave, shard))
    vlen: np.ndarray  # i16[W, S]
    kwin: np.ndarray  # u32[W, S, Ku/4]
    vwin: np.ndarray  # u32[W, S, VWu/4]


class _BlockPointers(NamedTuple):
    """A window's blocks as ``rk_pack_scan`` / ``rk_pack_gather`` read
    them, with everything the addresses point into kept alive."""

    lib: object  # the host kernel library
    data: list  # bytes of each block
    cols: list  # i64 cmd_sizes, counts, shards of each block, in turn
    data_p: object  # char*[W]
    data_len: np.ndarray  # i64[W]
    cols_p: np.ndarray  # uintp[3 W]


def _get_frame(found: bool, ver: int, val: bytes) -> bytes:
    """One GET response frame, byte-for-byte the host store's framing
    (`_result_bin`) — shared by every lazy GET view so the encoding
    lives in exactly one place."""
    from rabia_tpu.apps.kvstore import _result_bin

    if not found:
        return _result_bin(1, 0)
    try:
        return _result_bin(0, ver, val.decode("utf-8"))
    except UnicodeDecodeError:
        return _result_bin(2, ver, "value is not utf-8 text")


class _ShardFrameGroups(Sequence):
    """Shared per-shard lazy response machinery for the window views
    below: group ``j`` covers ``shards[j]`` with exactly one frame,
    materialized by the subclass's ``_frame(shard)`` on client read."""

    __slots__ = ()

    def __len__(self) -> int:
        return len(self.shards)

    def __getitem__(self, j):
        if isinstance(j, slice):
            return [self[i] for i in range(*j.indices(len(self)))]
        if j < 0:
            j += len(self)
        if not (0 <= j < len(self)):
            raise IndexError(j)
        return [self._frame(int(self.shards[j]))]

    def __iter__(self):
        for j in range(len(self)):
            yield self[j]

    def group_counts(self) -> np.ndarray:
        return np.ones(len(self), np.int64)

    def __eq__(self, other) -> bool:
        if not isinstance(other, (list, tuple, Sequence)):
            return NotImplemented
        return len(self) == len(other) and all(
            a == b for a, b in zip(self, other)
        )


class GetFrameGroups(_ShardFrameGroups):
    """Lazy per-shard GET responses over one wave's lookup readback.

    Frames materialize only when a client reads them — the commit path
    stores this view (one object per block, no per-op Python).
    """

    __slots__ = ("shards", "found", "ver", "vlen", "valb")

    def __init__(self, shards, found, ver, vlen, val_words) -> None:
        self.shards = shards  # i64[k] covered shards, group order
        self.found = found  # bool[S]
        self.ver = ver  # i32[S]
        self.vlen = vlen  # i32[S]
        # contiguous: a fetched device array slice can come back with a
        # non-contiguous layout, which .view(uint8) rejects
        self.valb = np.ascontiguousarray(val_words).view(np.uint8)  # u8[S, VW]

    def _frame(self, s: int) -> bytes:
        return _get_frame(
            bool(self.found[s]),
            int(self.ver[s]),
            self.valb[s, : int(self.vlen[s])].tobytes(),
        )


class ResolvedGetFrameGroups(_ShardFrameGroups):
    """Per-shard GET responses resolved from HOST-side value segments —
    the zero-value-download read path.

    Only ``found`` bits and version words are read back (~5
    bytes/op); the value bytes come from a SNAPSHOT resolver over the
    engine's retained SET windows / re-promotion seed
    (``resolver(s, ver) -> bytes``), justified by (shard, version)
    uniquely identifying content: shard versions are a monotone
    counter, each value assigned exactly once. The snapshot pins
    exactly the segments live at settle time — later evictions in the
    engine cannot invalidate an already-settled response, and the view
    holds no reference back to the engine. Byte-for-byte the host
    store's GET framing; frames materialize on client read. The engine
    only constructs this view after its vectorized resolvability
    check — the resolver cannot miss."""

    __slots__ = ("shards", "found", "ver", "resolver")

    def __init__(self, shards, found, ver, resolver) -> None:
        self.shards = shards  # i64[k] covered shards, group order
        self.found = found  # bool[S]
        self.ver = ver  # i32[S]
        self.resolver = resolver

    def _frame(self, s: int) -> bytes:
        if not self.found[s]:
            return _get_frame(False, 0, b"")
        ver = int(self.ver[s])
        return _get_frame(True, ver, self.resolver(s, ver))


class MixedFrameGroups(_ShardFrameGroups):
    """Lazy per-shard responses for one MIXED wave (SET/GET/DEL/EXISTS
    ops in the same wave): SET ops answer with the derived 6-byte
    version frame (byte-identical to ``VectorShardedKV._vers_frames``),
    GET ops with the host store's GET framing over the lookup readback,
    DEL/EXISTS with their found-bit framing (byte-identical to the
    vector store's ``apply_op_bin``). One object per block, frames
    materialize on client read."""

    __slots__ = ("shards", "kind", "svers", "_get")

    def __init__(self, shards, kind_row, set_vers, get_frames) -> None:
        self.shards = shards  # i64[k] covered shards, group order
        self.kind = kind_row  # i8[S]: 1=SET 2=GET 3=DEL 4=EXISTS
        self.svers = set_vers  # i64[S] derived SET response versions
        # GetFrameGroups/ResolvedGetFrameGroups view for this wave —
        # also the carrier of the found bits DEL/EXISTS frames need
        self._get = get_frames

    def _frame(self, s: int) -> bytes:
        from rabia_tpu.apps.kvstore import _result_bin

        k = int(self.kind[s])
        if k == 1:
            arr = np.zeros(1, _RESP_DT)
            arr["version"] = np.uint32(self.svers[s])
            return arr.tobytes()
        if k == 3:  # DEL: found bit, no version/value (vector_kv framing)
            return _result_bin(0 if self._get.found[s] else 1, 0)
        if k == 4:  # EXISTS: boolean text
            return _result_bin(
                0, 0, "true" if self._get.found[s] else "false"
            )
        return self._get._frame(s)


class TableDump(dict):
    """``DeviceKVTable.dump()``'s result: the table's live entries as
    arrays, entry ``i`` being ``shards[i]`` (i64[n], ascending),
    ``keys[i, :klens[i]]`` (u8[n, K], zero tails), ``vbuf[o:o + vlens[i]]``
    with ``o = vlens[:i].sum()`` (the values back to back, one ``bytes``),
    ``versions[i]`` (i64[n]); and ``shard_version`` (i64[n_shards]).

    ``d["rows"]`` builds the entries as ``(shard, key, value, version)``
    tuples on each read and is not kept: ``sync_into`` reads the arrays,
    unless the dump it is handed holds ``"rows"`` of its own."""

    def __missing__(self, name):
        if name != "rows":
            raise KeyError(name)
        keys, klens = self["keys"], self["klens"].tolist()
        vbuf, vlens = self["vbuf"], self["vlens"]
        vends = np.cumsum(vlens).tolist()
        return [
            (s, keys[i, : klens[i]].tobytes(), vbuf[e - n : e], v)
            for i, (s, e, n, v) in enumerate(
                zip(
                    self["shards"].tolist(), vends, vlens.tolist(),
                    self["versions"].tolist(),
                )
            )
        ]

    @classmethod
    def from_rows(cls, rows, shard_version, key_width: int) -> "TableDump":
        """The arrays of ``rows`` (``d["rows"]``'s inverse)."""
        n = len(rows)
        keys = np.zeros((n, key_width), np.uint8)
        for i, r in enumerate(rows):
            keys[i, : len(r[1])] = np.frombuffer(r[1], np.uint8)
        return cls(
            shards=np.fromiter((r[0] for r in rows), np.int64, n),
            keys=keys,
            klens=np.fromiter((len(r[1]) for r in rows), np.int64, n),
            vbuf=b"".join(r[2] for r in rows),
            vlens=np.fromiter((len(r[2]) for r in rows), np.int64, n),
            versions=np.fromiter((r[3] for r in rows), np.int64, n),
            shard_version=shard_version,
        )


def _state_planes(S: int, P: int, K4: int, VW4: int) -> tuple:
    """The table's seven state planes as ``(shape, dtype)``, in the order
    of ``DeviceKVTable.state``: ``S`` shards of ``P`` slots, keys of
    ``K4`` and values of ``VW4`` 32-bit words."""
    return (
        ((S, P), np.bool_),  # used
        ((S, P, K4), np.uint32),  # key words
        ((S, P), np.int32),  # key len
        ((S, P), np.int32),  # version
        ((S, P, VW4), np.uint32),  # value words
        ((S, P), np.int32),  # value len
        ((S,), np.int32),  # shard_ver
    )


def _plane_bytes(planes) -> int:
    return sum(int(np.prod(sh)) * np.dtype(dt).itemsize for sh, dt in planes)


class DeviceKVTable:
    """Device twin of the vector store's SET lane (see module doc)."""

    def __init__(
        self,
        n_shards: int,
        kernel,  # MeshPhaseKernel — decide plane + sharding owner
        *,
        per_shard_capacity: int = 64,
        key_lanes: int = 4,
        value_width: int = 64,
        rungs: Sequence[int] = (),
    ) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from rabia_tpu.parallel.mesh import SHARD_AXIS

        self.n_shards = int(n_shards)
        self.kernel = kernel
        self.S = kernel.S  # padded shard width (mesh-divisible)
        self.P = int(per_shard_capacity)
        self.K = int(key_lanes) * 8
        self.VW = _bucket(int(value_width))
        self.K4 = self.K // 4
        self.VW4 = self.VW // 4
        # devices that every placed operand and the table are split over
        self.n_devices = int(kernel.mesh.devices.size)
        S, Pc = self.S, self.P
        # host arrays go STRAIGHT to their shard-axis placement: the
        # table ([S, ...]) and every per-window operand ([W, S, ...])
        # land S/devices wide on each device, never on device 0 first
        # and re-sharded per dispatch
        shard_sharding = NamedSharding(kernel.mesh, P(SHARD_AXIS))
        wave_sharding = NamedSharding(kernel.mesh, P(None, SHARD_AXIS))
        put = self._put_shards = lambda a: jax.device_put(a, shard_sharding)
        self._put_waves = lambda a: jax.device_put(a, wave_sharding)
        planes = _state_planes(S, Pc, self.K4, self.VW4)
        self.state = tuple(put(jnp.zeros(sh, dt)) for sh, dt in planes)
        # what the table holds on the device, every chip's share together
        # (the engine's devkv_table_bytes reads it)
        self.table_bytes = _plane_bytes(planes)
        self._fused = None  # built per (W, Ku4, VWu4) — see decide_apply
        self._fused_cache: dict = {}
        # True when the most recent decide_apply/lookup_window built a
        # new program: the engine's latency governor must not read that
        # dispatch's wall time as window latency
        self.compiled_on_last_call = False
        # the window ladder: every static window size W the owner will
        # dispatch at (a governed engine's rungs). With more than one
        # rung a signature is built for all of them at once, when its
        # kind and widths are first needed at any (see _program), so a
        # window that lands on another rung later finds its program.
        # None or one rung: each signature is built where it is needed
        self.rungs = tuple(sorted({int(w) for w in rungs}))
        self._laddered = len(self.rungs) > 1
        self._building_ladder = False
        # host bytes device_put for window dispatches, ever (the engine's
        # devkv_upload_bytes_total reads it)
        self.upload_bytes = 0
        # table rows materialized on the host by dump(), ever (the
        # engine's devkv_sync_rows_total reads it)
        self.sync_rows = 0
        # the window planes' buffers, reused once nothing else holds them
        self._planes = _PlanePool()
        # planes taken from it by outcome, ever (the engine's
        # devkv_pack_buffers_total reads it)
        self.pack_buffers = self._planes.outcomes
        # windows packed by the path that packed them, ever (the
        # engine's devkv_pack_windows_total reads it)
        self.pack_windows = {"native": 0, "numpy": 0}

    # -- host-side packing -------------------------------------------------

    def _parse_block(self, b):
        """Shared per-block op parse for the window packers: returns
        ``(dbuf, off, klen, vlen, opcode)`` host arrays (dbuf is the
        block's bytes padded with K+header slack so fixed-width gathers
        past the last op stay in bounds), or None when the block is not
        one-op-per-shard / too short to parse."""
        if not bool((b.counts == 1).all()):
            return None
        raw = np.frombuffer(b.data, np.uint8)
        if len(raw) < _SET_HDR * len(b):
            return None
        off = b.cmd_offsets[:-1]
        ln = b.cmd_sizes
        dbuf = np.concatenate([raw, np.zeros(self.K + _SET_HDR, np.uint8)])
        opcode = dbuf[off]
        klen = dbuf[off + 1].astype(np.int64) | (
            dbuf[off + 2].astype(np.int64) << 8
        )
        vlen = ln - _SET_HDR - klen
        return dbuf, off, klen, vlen, opcode

    def _gather_window(self, blocks, allow: str) -> Optional[tuple]:
        """Shared validate + bucket + fixed-width gather behind the
        three window packers (``allow``: "set", "get" or "mixed").

        On the grid shape (every block full width, one op a shard,
        shards in order) native code reads the blocks where they lie:
        one scan validates the window and finds its widths
        (:meth:`_native_scan`), one gather writes the planes
        (:meth:`_native_pack_gather`), and no per-op numpy array is
        made. Whatever the scan does not take (another shape, an op
        outside the envelope, no library, ``RABIA_PY_DEVPACK=1``) goes
        to :meth:`_parse_window` and :meth:`_gather_into` whole: the
        numpy path is the semantics owner, and it alone decides that a
        window is outside the envelope.

        Returns ``(kind i8[W,S], klen i16[W,S], vlen i16[W,S],
        kwin u8[W,S,Ku], vwin u8[W,S,VWu])`` or None when any op is
        outside the requested envelope (wrong opcode, >1 op per shard,
        key/value over the table widths) — the caller demotes."""
        W = len(blocks)
        with device_annotation("rabia.cycle.pack.parse") as span:
            parsed = None
            scan = self._native_scan(blocks, allow)
            if scan is None:
                parsed = self._parse_window(blocks, allow)
            if span is not None:
                span.set_metadata(path="numpy" if scan is None else "native")
        planes = None
        if scan is not None:
            planes = self._take_planes(W, *scan[1:])
            with device_annotation("rabia.cycle.pack.gather"):
                if self._native_pack_gather(scan, *planes):
                    self.pack_windows["native"] += 1
                    return planes
            # the C gather's own bounds check: numpy writes every row again
            parsed = self._parse_window(blocks, allow)
        self.pack_windows["numpy"] += 1
        if parsed is None:
            return None
        if planes is None or parsed[1:] != scan[1:]:
            planes = self._take_planes(W, *parsed[1:])
        with device_annotation("rabia.cycle.pack.gather"):
            self._gather_into(parsed[0], *planes)
        return planes

    def _take_planes(self, W: int, ku: int, vu: int) -> tuple:
        """The window's five planes from the pool, holding anything."""
        S = self.S
        specs = (
            ((W, S), np.int8),
            ((W, S), np.int16),
            ((W, S), np.int16),
            ((W, S, ku), np.uint8),
            ((W, S, vu), np.uint8),
        )
        sizes = [int(np.prod(sh)) * np.dtype(dt).itemsize for sh, dt in specs]
        pool = self._planes
        bufs = [pool.idle(nb) for nb in sizes]
        with device_annotation(
            "rabia.cycle.pack.alloc", reused=sum(b is not None for b in bufs)
        ):
            return tuple(
                (pool.fresh(nb) if b is None else b).view(dt).reshape(sh)
                for b, nb, (sh, dt) in zip(bufs, sizes, specs)
            )

    def _native_scan(self, blocks, allow: str) -> Optional[tuple]:
        """The native parse of a grid-shaped window: one C pass
        (``rk_pack_scan``) over each block's ``data``, ``cmd_sizes``,
        ``counts`` and ``shards`` where they lie checks the shape and
        every op against the ``allow`` envelope, all that
        :meth:`_parse_window` checks, and finds the widest key and
        value. Returns ``(pointers, ku, vu)`` for
        :meth:`_native_pack_gather` (``pointers`` keeps alive what it
        points into), or None: not the grid shape, an op outside the
        envelope, the library unavailable or ``RABIA_PY_DEVPACK=1`` —
        the numpy parse then runs on the whole window and decides."""
        # =1 opts out, matching the docstring/tests convention — a plain
        # truthiness test made RABIA_PY_DEVPACK=0 ALSO disable the
        # native path
        if os.environ.get("RABIA_PY_DEVPACK") == "1":
            return None
        from rabia_tpu.native.build import load_hostkernel

        lib = load_hostkernel()
        if lib is None:
            return None
        W = len(blocks)
        if W == 0:
            return None
        # a PayloadBlock holds exact bytes and i64 arrays; an array of
        # another layout is copied for C (contiguous ones come back as
        # they are)
        data = [b.data for b in blocks]
        cols = [
            np.ascontiguousarray(a, np.int64)
            for b in blocks
            for a in (b.cmd_sizes, b.counts, b.shards)
        ]
        try:
            data_p = (ctypes.c_char_p * W)(*data)
        except TypeError:  # bytes-like, not bytes: numpy reads those
            return None
        data_len = np.fromiter(map(len, data), np.int64, W)
        cols_p = np.fromiter(map(_address, cols), np.uintp, 3 * W)
        cols_len = np.fromiter(map(len, cols), np.int64, 3 * W)
        widest = np.zeros(2, np.int64)
        rc = lib.rk_pack_scan(
            W, self.n_shards, _SET_HDR, _ALLOW_OPCODES[allow], self.K,
            self.VW, data_p, data_len.ctypes.data, cols_p.ctypes.data,
            cols_len.ctypes.data, widest.ctypes.data,
        )
        if rc != 0:
            return None
        pointers = _BlockPointers(lib, data, cols, data_p, data_len, cols_p)
        return pointers, _bucket(int(widest[0])), _bucket(int(widest[1]))

    def _native_pack_gather(
        self, scan, kind_w, klen_w, vlen_w, kwin_w, vwin_w
    ) -> bool:
        """One-pass C gather (``rk_pack_gather``) of a window
        :meth:`_native_scan` took into the five planes, every row
        written whole: op ``s`` of block ``t`` is wave t, shard s, its
        bytes read where they lie, its header by the C loop itself. No
        per-op array exists on this path. The C loop checks its own
        bounds; False (they tripped, which leaves the planes half
        written) routes the caller to the numpy path, which writes
        every row again. Byte-equivalence with the numpy path is
        pinned in tests/test_device_kv.py."""
        at = scan[0]
        W, S, ku = kwin_w.shape
        rc = at.lib.rk_pack_gather(
            W, self.n_shards, S, _SET_HDR, ku, vwin_w.shape[2],
            at.data_p, at.data_len.ctypes.data, at.cols_p.ctypes.data,
            kind_w.ctypes.data, klen_w.ctypes.data, vlen_w.ctypes.data,
            kwin_w.ctypes.data, vwin_w.ctypes.data,
        )
        return rc == 0

    def _parse_window(self, blocks, allow: str) -> Optional[tuple]:
        """Parse and validate every block of a window against the
        ``allow`` envelope: ``(parsed blocks, ku, vu)`` with the
        bucketed key/value widths, or None when any op is outside it."""
        parsed = []
        ku = vu = 4
        for b in blocks:
            pb = self._parse_block(b)
            if pb is None:
                return None
            dbuf, off, klen, vlen, opcode = pb
            is_set = opcode == 1
            is_get = opcode == 2
            is_del = opcode == 3
            is_exists = opcode == 4
            kind_ok = {
                "set": is_set,
                "get": is_get,
                # DEL and EXISTS join the mixed envelope: both carry
                # exactly a key (vlen==0 enforced below); DEL clears the
                # matched slot on device, EXISTS is a found-bit read
                "mixed": is_set | is_get | is_del | is_exists,
            }[allow]
            ok = (
                kind_ok
                & (klen > 0)
                & (klen <= self.K)
                & (vlen >= 0)
                & (vlen <= self.VW)
                & (is_set | (vlen == 0))  # GET carries exactly the key
            )
            if not bool(ok.all()):
                return None
            ku = max(ku, _bucket(int(klen.max())))
            vu = max(vu, _bucket(int(vlen.max(initial=0))))
            parsed.append((b, dbuf, off, klen, vlen, opcode))
        return parsed, ku, vu

    def _gather_into(
        self, parsed, kind_w, klen_w, vlen_w, kwin_w, vwin_w
    ) -> None:
        """Fill the ``[W, S, ...]`` planes from the parsed blocks, in
        numpy (the semantics owner of the native gather, and the path
        of every window that one does not take). The planes come from
        the pool and may hold anything: both branches write every byte
        of all five."""
        counts = [len(p[2]) for p in parsed]
        off_all = np.concatenate([p[2] for p in parsed])  # in-block
        klen_all = np.concatenate([p[3] for p in parsed])
        vlen_all = np.concatenate([p[4] for p in parsed])
        op_all = np.concatenate([p[5] for p in parsed])
        sh_all = np.concatenate([p[0].shards for p in parsed])
        W = len(parsed)
        n = self.n_shards
        # full-width sorted blocks (the block lane's shape): op i of a
        # block is shard i's, so the scatter is a contiguous assign —
        # advanced-index scatters on 500k+ rows were ~half the gather cost
        grid = len(off_all) == W * n and bool(
            (sh_all.reshape(W, n) == np.arange(n)[None, :]).all()
        )
        ku, vu = kwin_w.shape[2], vwin_w.shape[2]
        kcols = np.arange(ku)[None, :]
        vcols = np.arange(vu)[None, :]
        # batch the W per-block gathers into ONE: concatenate the block
        # buffers and rebase the offsets — per-window numpy call count
        # drops from ~4W to ~8 (the W-loop was ~40% of the pack cost).
        # A value gather may run past its block's end into the next
        # block's bytes; the vlen mask zeroes those lanes, same as the
        # old per-block end-of-buffer clamp.
        sizes = [len(p[1]) for p in parsed]
        bases = np.zeros(W, np.int64)
        bases[1:] = np.cumsum(sizes[:-1])
        dbuf_all = np.concatenate([p[1] for p in parsed])
        off_all = off_all + np.repeat(bases, counts)
        kw = dbuf_all[(off_all + _SET_HDR)[:, None] + kcols]
        kw = np.where(kcols < klen_all[:, None], kw, 0)
        vidx = np.minimum(
            (off_all + _SET_HDR + klen_all)[:, None] + vcols,
            len(dbuf_all) - 1,
        )
        vw = dbuf_all[vidx]
        vw = np.where(vcols < vlen_all[:, None], vw, 0)
        if grid:
            # whole rows, whatever a native gather left behind
            for plane, vals in (
                (kind_w, op_all), (klen_w, klen_all), (vlen_w, vlen_all),
                (kwin_w, kw), (vwin_w, vw),
            ):
                plane[:, :n] = vals.reshape((W, n) + plane.shape[2:])
                plane[:, n:] = 0
        else:
            for plane in (kind_w, klen_w, vlen_w, kwin_w, vwin_w):
                plane.fill(0)  # the scatter covers only the ops' cells
            t_all = np.repeat(np.arange(W), counts)
            kind_w[t_all, sh_all] = op_all
            klen_w[t_all, sh_all] = klen_all
            vlen_w[t_all, sh_all] = vlen_all
            kwin_w[t_all, sh_all] = kw
            vwin_w[t_all, sh_all] = vw

    def pack_window(self, blocks) -> Optional[DeviceWindowOps]:
        """Pack SET-only ``blocks`` (one per wave, FIFO order) into
        device inputs; None when outside the write lane's envelope —
        the caller demotes. Native code or numpy (see
        :meth:`_gather_window`), no per-op Python loop."""
        g = self._gather_window(blocks, "set")
        if g is None:
            return None
        return self._window_ops(g)

    def pack_get_window(self, blocks) -> Optional[tuple]:
        """Pack GET-only ``blocks`` into the lookup programs' inputs,
        ``(klen i16[W, S], kwin u32[W, S, Ku/4])``; None when outside
        the read envelope — the caller demotes."""
        g = self._gather_window(blocks, "get")
        if g is None:
            return None
        ops = self._window_ops(g)
        return ops.klen, ops.kwin

    def pack_mixed_window(self, blocks) -> Optional[tuple]:
        """Pack blocks whose ops are ANY interleaving of binary SET and
        GET — per op, not per block — into one device window.

        Returns ``(kind i8[W, S], DeviceWindowOps)`` (kind 0 = no op,
        1 = SET, 2 = GET; GET rows carry the key with vlen 0) or None
        when any op is outside the union envelope — the caller demotes.
        This removes the FIFO kind-boundary splits: an interleaved
        SET/GET workload runs full windows instead of
        window-per-kind-run (reference applies a mixed batch in one
        pass too: rabia-kvstore/src/store.rs:313-348). ``ops.vlen`` and
        ``ops.vwin`` are the per-wave value planes the engine's host
        value segments keep."""
        g = self._gather_window(blocks, "mixed")
        if g is None:
            return None
        return g[0], self._window_ops(g)

    @staticmethod
    def _window_ops(g: tuple) -> DeviceWindowOps:
        _kind, klen_w, vlen_w, kwin_w, vwin_w = g
        return DeviceWindowOps(
            klen_w,
            vlen_w,
            np.ascontiguousarray(kwin_w).view(np.uint32),
            np.ascontiguousarray(vwin_w).view(np.uint32),
        )

    # -- the fused programs --------------------------------------------------

    def _place_ops(self, ops):
        """Upload one packed window with the shard-axis placement: the
        per-wave planes ``[W, S, ...]`` each land split over S."""
        return DeviceWindowOps(*(self._put_waves(a) for a in ops))

    def _placing(self, *operands):
        """The span around one dispatch's ``device_put``s: ``operands``
        are the host arrays placed under it, counted into
        ``upload_bytes``."""
        nbytes = sum(a.nbytes for a in operands)
        self.upload_bytes += nbytes
        return device_annotation("rabia.dispatch.place", bytes=nbytes)

    def _program(self, key: tuple, W: int, build, at_rung):
        """The jitted program of signature ``key`` (static window size
        ``W``), built by ``build()`` on a miss (which
        ``compiled_on_last_call`` then says).

        On a table with a ladder a miss builds the whole ladder:
        ``at_rung(w)`` dispatches an EMPTY window (depth 0, no ops) of
        the same kind and widths at rung ``w`` through the caller's own
        dispatch method, once for every other rung, and its outputs are
        dropped. The programs are functional (nothing is donated), so
        the table is untouched; each sibling is traced, compiled and run
        once with operands of exactly the types a real window places,
        so the real window's call is a cache hit. All of it lies inside
        the dispatch that first needed the kind (span
        ``rabia.ladder.build``), which ``compiled_on_last_call`` marks
        as it marks any first call."""
        fn = self._fused_cache.get(key)
        self.compiled_on_last_call = fn is None
        if fn is None:
            fn = self._fused_cache[key] = build()
            if not self._building_ladder:
                self._build_ladder(key, W, at_rung)
        return fn

    def _build_ladder(self, key: tuple, W: int, at_rung) -> None:
        import jax

        if not self._laddered:
            return
        others = [w for w in self.rungs if w != W]
        self._building_ladder = True
        try:
            with device_annotation("rabia.ladder.build", sig=str(key)):
                for w in others:
                    jax.block_until_ready(at_rung(w))
        finally:
            self._building_ladder = False
        self.compiled_on_last_call = True  # the siblings' calls reset it

    def _calling(self, key: tuple):
        """The span around the jitted call that follows ``_program(key,
        ...)``: a program's first call traces, lowers and compiles, and
        carries its own name and signature into the trace."""
        if self.compiled_on_last_call:
            return device_annotation("rabia.jit.first_call", sig=str(key))
        return device_annotation("rabia.dispatch.call")

    def _build_lookup(self, Ku4: int):
        """Jitted GET window: consensus slot window + a read-only match
        over the table (no state mutation, no version advance)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        kernel = self.kernel
        Pc = self.P
        K4 = self.K4
        n = self.n_shards
        I8, I32 = jnp.int8, jnp.int32
        col = jnp.arange(self.S) < n

        def lookup(state, alive, base, depth, klen_t, kwin_t, *, W,
                   max_phases):
            used, keyw, klen, ver, valw, vlen, _sver = state
            with jax.named_scope("consensus"):
                wave = jnp.arange(W, dtype=I32)[:, None] < depth
                present = wave & col[None, :]
                votes = jnp.where(
                    present[:, :, None], I8(V1), I8(V0)
                ) * jnp.ones((1, 1, kernel.R), I8)
                decided = kernel.slot_window(
                    votes, alive, base, n_slots=W, max_phases=max_phases
                )
                all_v1 = jnp.all(jnp.where(present, decided == V1, True))

            def wave_match(_, inp):
                klen_w, kwin_w = inp
                with jax.named_scope("key_match"):
                    klen_w = klen_w.astype(jnp.int32)
                    eq = (
                        used
                        & (klen == klen_w[:, None])
                        & (keyw == kwin_w[:, None, :]).all(-1)
                    )  # [S, P]
                    found = eq.any(1) & (klen_w > 0)
                with jax.named_scope("get_gather"):
                    oh = eq & found[:, None]  # at most one slot matches
                    rver = (ver * oh).sum(1)
                    rvlen = (vlen * oh).sum(1)
                    rval = (valw * oh[:, :, None]).sum(1)  # [S, VW4] u32
                return None, (found, rver, rvlen, rval)

            kwin_full = jnp.pad(kwin_t, ((0, 0), (0, 0), (0, K4 - Ku4)))
            _, (found, rver, rvlen, rval) = lax.scan(
                wave_match, None, (klen_t, kwin_full)
            )
            return all_v1.astype(I32), found, rver, rvlen, rval

        return jax.jit(lookup, static_argnames=("W", "max_phases"))

    def lookup_window(self, alive, base, depth: int, ops, W: int,
                      max_phases: int = 4, state=None):
        """Dispatch one consensus+lookup window against the CURRENT
        table (read-only; ``state`` overrides it so the pipelined lane
        can chain on an in-flight window's output). ``ops`` is
        :meth:`pack_get_window`'s ``(klen i16[W,S], kwin u32[W,S,Ku4])``
        pair. Returns DEVICE handles
        ``(all_v1, found[W,S], ver[W,S], vlen[W,S], val_words[W,S,VW4])``
        — the caller fetches selectively: found+ver are ~5 bytes/op;
        the value planes (~70 bytes/op) only need to be downloaded
        when a version cannot be resolved from the host-side value
        segments (see mesh_engine._dev_resolve), which is the eviction
        edge case, not the steady state."""
        klen, kwin = ops
        if klen.shape[0] < W:
            pad = W - klen.shape[0]
            klen = np.concatenate(
                [klen, np.zeros((pad,) + klen.shape[1:], klen.dtype)]
            )
            kwin = np.concatenate(
                [kwin, np.zeros((pad,) + kwin.shape[1:], kwin.dtype)]
            )
        key = ("get", W, kwin.shape[2])
        fn = self._program(
            key, W, lambda: self._build_lookup(key[2]),
            lambda w: self.lookup_window(
                alive, base, 0, (klen[:0], kwin[:0]), W=w,
                max_phases=max_phases, state=state,
            ),
        )
        with self._placing(alive, base, klen, kwin):
            alive_d = self.kernel.place(alive)
            base_d = self._put_shards(base)
            klen_d = self._put_waves(klen)
            kwin_d = self._put_waves(kwin)
        with self._calling(key):
            return fn(
                self.state if state is None else state,
                alive_d,
                base_d,
                np.int32(depth),
                klen_d,
                kwin_d,
                W=W,
                max_phases=max_phases,
            )

    def _build_lookup_only(self, Ku4: int):
        """Jitted CONSENSUS-FREE read window: the same read-only match
        scan as :meth:`_build_lookup`, with the slot window removed
        entirely — no votes, no phases, no collective. The read-index
        lane dispatches these for probe-covered GETs (the gateway's
        shared quorum probe round already established linearizability;
        the device table only has to answer), so reads consume ZERO
        consensus slots and the program crosses zero ICI bytes on a
        multi-chip mesh (pinned by benchmarks/ici_model.py via jaxpr
        inspection)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        K4 = self.K4

        def lookup_only(state, klen_t, kwin_t, *, W):
            used, keyw, klen, ver, valw, vlen, _sver = state

            def wave_match(_, inp):
                klen_w, kwin_w = inp
                with jax.named_scope("key_match"):
                    klen_w = klen_w.astype(jnp.int32)
                    eq = (
                        used
                        & (klen == klen_w[:, None])
                        & (keyw == kwin_w[:, None, :]).all(-1)
                    )  # [S, P]
                    found = eq.any(1) & (klen_w > 0)
                with jax.named_scope("get_gather"):
                    oh = eq & found[:, None]  # at most one slot matches
                    rver = (ver * oh).sum(1)
                    rvlen = (vlen * oh).sum(1)
                    rval = (valw * oh[:, :, None]).sum(1)  # [S, VW4] u32
                return None, (found, rver, rvlen, rval)

            kwin_full = jnp.pad(kwin_t, ((0, 0), (0, 0), (0, K4 - Ku4)))
            _, (found, rver, rvlen, rval) = lax.scan(
                wave_match, None, (klen_t, kwin_full)
            )
            return found, rver, rvlen, rval

        return jax.jit(lookup_only, static_argnames=("W",))

    def lookup_only(self, ops, W: int, state=None):
        """Dispatch one consensus-free read window (the read-index
        lane's probe serve): ``ops`` exactly as :meth:`lookup_window`
        takes them, padded to the static window size ``W``
        (padding waves carry klen 0 and match nothing). Returns DEVICE
        handles ``(found[W,S], ver[W,S], vlen[W,S], val_words)`` — no
        all_v1 scalar, because nothing was decided. The caller fetches
        meta-only in the steady state, exactly like the slot-consuming
        GET window."""
        klen, kwin = ops
        if klen.shape[0] < W:
            pad = W - klen.shape[0]
            klen = np.concatenate(
                [klen, np.zeros((pad,) + klen.shape[1:], klen.dtype)]
            )
            kwin = np.concatenate(
                [kwin, np.zeros((pad,) + kwin.shape[1:], kwin.dtype)]
            )
        key = ("ro", W, kwin.shape[2])
        fn = self._program(
            key, W, lambda: self._build_lookup_only(key[2]),
            lambda w: self.lookup_only(
                (klen[:0], kwin[:0]), W=w, state=state
            ),
        )
        with self._placing(klen, kwin):
            klen_d = self._put_waves(klen)
            kwin_d = self._put_waves(kwin)
        with self._calling(key):
            return fn(
                self.state if state is None else state,
                klen_d,
                kwin_d,
                W=W,
            )

    @staticmethod
    def _apply_set_wave(carry, ok_w, klen_t, vlen_t, kwin_t, vwin_t, Pc):
        """One SET wave over the table state.

        Match: word compare against all P slots of the shard; stored
        tails beyond the op key are zero, as are the padded op words,
        so prefix equality + length equality IS full-key equality.
        Updates are one-hot word SELECTS, not dynamic-index scatters
        (which lower poorly on TPU)."""
        import jax
        import jax.numpy as jnp

        used, keyw, klen, ver, valw, vlen, sver = carry
        with jax.named_scope("key_match"):
            eq = (
                used
                & (klen == klen_t[:, None])
                & (keyw == kwin_t[:, None, :]).all(-1)
            )  # [S, P]
            found = eq.any(1)
        with jax.named_scope("apply_set"):
            slot = jnp.where(
                found, jnp.argmax(eq, 1), jnp.argmax(~used, 1)
            )
            full = used.all(1)
            apply = ok_w & (found | ~full)
            overflow = jnp.any(ok_w & ~found & full)
            onehot = (
                jnp.arange(Pc)[None, :] == slot[:, None]
            ) & apply[:, None]  # [S, P]
            oh3 = onehot[:, :, None]
            used = used | onehot
            keyw = jnp.where(oh3, kwin_t[:, None, :], keyw)
            klen = jnp.where(onehot, klen_t[:, None], klen)
            new_ver = sver + 1
            ver = jnp.where(onehot, new_ver[:, None], ver)
            valw = jnp.where(oh3, vwin_t[:, None, :], valw)
            vlen = jnp.where(onehot, vlen_t[:, None], vlen)
            sver = jnp.where(apply, new_ver, sver)
        return (used, keyw, klen, ver, valw, vlen, sver), overflow

    def _build_fused(self, Ku4: int, VWu4: int):
        import jax
        import jax.numpy as jnp
        from jax import lax

        kernel = self.kernel
        S, Pc = self.S, self.P
        K4, VW4 = self.K4, self.VW4
        n = self.n_shards
        I8, I32 = jnp.int8, jnp.int32
        col = jnp.arange(S) < n  # real (non-padding) shards

        def fused(state, alive, base, depth, ops, *, W, max_phases):
            # initial votes generated on device: every live replica
            # proposes V1 for the depth in-window waves of real shards
            with jax.named_scope("consensus"):
                wave = jnp.arange(W, dtype=I32)[:, None] < depth  # [W, 1]
                present = wave & col[None, :]  # [W, S]
                votes = jnp.where(
                    present[:, :, None], I8(V1), I8(V0)
                ) * jnp.ones((1, 1, kernel.R), I8)
                decided = kernel.slot_window(
                    votes, alive, base, n_slots=W, max_phases=max_phases
                )  # i8[W, S]
                all_v1 = jnp.all(jnp.where(present, decided == V1, True))

            # pad the op windows to the table widths once, outside the
            # scan (zero tails keep prefix-compare == full-key compare)
            kwin_full = jnp.pad(ops.kwin, ((0, 0), (0, 0), (0, K4 - Ku4)))
            vwin_full = jnp.pad(ops.vwin, ((0, 0), (0, 0), (0, VW4 - VWu4)))

            def wave_step(carry, inp):
                ok_w, klen_t, vlen_t, kwin_t, vwin_t = inp
                # op columns travel as i16 (half the upload bytes);
                # table arithmetic stays i32
                return DeviceKVTable._apply_set_wave(
                    carry,
                    ok_w,
                    klen_t.astype(jnp.int32),
                    vlen_t.astype(jnp.int32),
                    kwin_t,
                    vwin_t,
                    Pc,
                )

            new_state, over_w = lax.scan(
                wave_step,
                state,
                (present, ops.klen, ops.vlen, kwin_full, vwin_full),
            )
            with jax.named_scope("flags"):
                flags = jnp.stack(
                    [
                        all_v1.astype(I32),
                        jnp.any(over_w).astype(I32),
                        jnp.any(
                            new_state[6] >= jnp.int32(2**31 - 2)
                        ).astype(I32),
                    ]
                )
            return new_state, flags

        return jax.jit(fused, static_argnames=("W", "max_phases"))

    def decide_apply(self, alive, base, depth: int, ops: DeviceWindowOps,
                     W: int, max_phases: int = 4, state=None):
        """Dispatch one fused decide+apply window. Returns device handles
        ``(new_state, flags)`` where ``flags`` is i32[3]:
        ``[all_v1, overflow, ver_overflow]`` — 12 bytes of readback.
        The caller ADOPTS ``new_state`` only when the flags are clean
        (and then derives version responses from its host-side counter
        mirror); otherwise it keeps the old state object (purely
        functional program — nothing was donated) and demotes."""
        if ops.klen.shape[0] < W:
            # pack_window covers only the depth in-flight waves; pad to
            # the static window size (filler waves are masked out by the
            # in-program depth gate)
            pad = W - ops.klen.shape[0]
            ops = DeviceWindowOps(
                *(
                    np.concatenate(
                        [a, np.zeros((pad,) + a.shape[1:], a.dtype)]
                    )
                    for a in ops
                )
            )
        key = (W, ops.kwin.shape[2], ops.vwin.shape[2])
        fn = self._program(
            key, W, lambda: self._build_fused(key[1], key[2]),
            lambda w: self.decide_apply(
                alive, base, 0, DeviceWindowOps(*(a[:0] for a in ops)),
                W=w, max_phases=max_phases, state=state,
            ),
        )
        with self._placing(alive, base, *ops):
            alive_d = self.kernel.place(alive)
            base_d = self._put_shards(base)
            ops_d = self._place_ops(ops)
        with self._calling(key):
            return fn(
                self.state if state is None else state,
                alive_d,
                base_d,
                np.int32(depth),
                ops_d,
                W=W,
                max_phases=max_phases,
            )

    @staticmethod
    def _resolve_values(valw0, gslot, gwave, wslot, vwin):
        """A mixed window's value reads and writes, resolved once from
        what its scan recorded: ``(gval u32[G, S, VW4], valw_new)``.

        ``valw0 u32[S, P, VW4]`` is the value plane as the window found
        it, ``vwin u32[W, S, VW4]`` the ops' padded value rows,
        ``wslot i32[W, S]`` the slot each wave's SET wrote (-1: none) and
        ``gslot i32[G, S]`` the slot the GET of wave ``gwave[g]`` reads
        (-1: none, a zero row). One op a shard a wave, so a wave never
        reads what it writes: a GET answers with the row of the latest
        earlier wave that wrote its slot, else with the plane's; a slot
        ends the window holding its last writer's row, else its own.

        The plane's rows are fetched, and the SETs' rows laid into the
        plane, by a one-hot contraction with the shard as the batch axis
        (so a table split over shards needs no collective): the words go
        through the matrix unit a byte at a time, as int8 with an int32
        accumulator, where a sum with one non-zero term cannot overflow
        or round. A row forwarded from an earlier wave of the window is
        picked from the ops' own rows by a select over the waves."""
        import jax.numpy as jnp
        from jax import lax

        W, P = wslot.shape[0], valw0.shape[1]
        I8, I32, U32 = jnp.int8, jnp.int32, jnp.uint32

        def to_bytes(a):  # u32[..., V] -> i8[..., 4V]: byte k of word v at kV + v
            a = lax.bitcast_convert_type(a, I32)
            return jnp.concatenate(
                [((a << (24 - 8 * k)) >> 24).astype(I8) for k in range(4)], -1
            )

        def to_words(b):  # i8[..., 4V] -> u32[..., V]
            V = b.shape[-1] // 4
            w = 0
            for k in range(4):
                w |= (b[..., k * V : (k + 1) * V].astype(I32) & 0xFF) << (8 * k)
            return lax.bitcast_convert_type(w, U32)

        def rows(spec, onehot, src):
            picked = jnp.einsum(
                spec, onehot.astype(I8), to_bytes(src),
                preferred_element_type=I32,
            )
            # each sum is one byte: handing it on as int8 lets the
            # compiler write a quarter of the accumulator's bytes
            return to_words(picked.astype(I8))

        t = jnp.arange(W, dtype=I32)
        # reads: the latest wave before the GET's that wrote its slot
        wrote = (
            (wslot[None] == gslot[:, None])
            & (gslot[:, None] >= 0)
            & (t[None, :, None] < gwave[:, None, None])
        )  # [G, W, S]
        src = jnp.where(wrote, t[None, :, None], -1).max(1)  # [G, S]
        forwarded = jnp.where(
            (src[:, None] == t[None, :, None])[..., None], vwin[None], U32(0)
        ).sum(1, dtype=U32)
        from_plane = jnp.where(src < 0, gslot, -1)
        gval = forwarded | rows(
            "gsp,spb->gsb", from_plane[:, :, None] == jnp.arange(P), valw0
        )
        # writes: each slot's last writer
        later = (wslot[None] == wslot[:, None]) & (
            t[None, :, None] > t[:, None, None]
        )  # [W (writer), W (a later wave), S]
        last = jnp.where(later.any(1), -1, wslot)  # [W, S]
        lands = last[:, :, None] == jnp.arange(P)  # [W, S, P]
        valw = jnp.where(
            lands.any(0)[:, :, None], rows("wsp,wsb->spb", lands, vwin), valw0
        )
        return gval, valw

    def _build_mixed(self, Ku4: int, VWu4: int, Gp: int):
        """Jitted MIXED window: consensus + per-op kind mask over the
        same table — SET ops mutate (identical update rules to
        :meth:`_build_fused`), GET ops read the wave-entry state (reads
        in wave t observe every apply from waves < t — the host store's
        FIFO semantics), all in ONE scan over the waves.

        The scan carries ``(used, key words, key len, version, value
        len, shard_ver)`` and decides everything from them: match, found
        bits, versions, DEL, slot choice, overflow. The value plane is
        not in it. Each wave emits the slot its GET reads and the slot
        its SET writes, and :meth:`_resolve_values` fetches and writes
        the value rows once, after the scan, from the plane as the
        window found it (scope ``value_resolve``).

        ``Gp`` (static) is the padded count of GET-bearing waves; the
        program gathers those waves' lookup outputs ON DEVICE (the host
        knows the wave indices at pack time) and packs found/ver/vlen
        into one two-plane i32 tensor, so the readback is two transfers,
        not four take-dispatch round trips."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        kernel = self.kernel
        S, Pc = self.S, self.P
        K4, VW4 = self.K4, self.VW4
        n = self.n_shards
        I8, I32 = jnp.int8, jnp.int32
        col = jnp.arange(S) < n

        def mixed(state, alive, base, depth, kind_w, gidx, ops, *, W,
                  max_phases):
            with jax.named_scope("consensus"):
                wave = jnp.arange(W, dtype=I32)[:, None] < depth
                present = wave & col[None, :]
                votes = jnp.where(
                    present[:, :, None], I8(V1), I8(V0)
                ) * jnp.ones((1, 1, kernel.R), I8)
                decided = kernel.slot_window(
                    votes, alive, base, n_slots=W, max_phases=max_phases
                )
                all_v1 = jnp.all(jnp.where(present, decided == V1, True))

            def wave_step(carry, inp):
                ok_w, kind_t, klen_t, vlen_t, kwin_t = inp
                used, keyw, klen, ver, vlen, sver = carry
                klen_t = klen_t.astype(jnp.int32)
                vlen_t = vlen_t.astype(jnp.int32)
                kind_t = kind_t.astype(jnp.int32)
                with jax.named_scope("key_match"):
                    eq = (
                        used
                        & (klen == klen_t[:, None])
                        & (keyw == kwin_t[:, None, :]).all(-1)
                    )  # [S, P]
                    found = eq.any(1)
                    hit = jnp.argmax(eq, 1)
                # reads (GET/DEL/EXISTS found bits) are against the
                # wave-entry state, before this wave's applies touch the
                # table; gver/gslot carry data for GET ops only (a DEL's
                # response is its found bit, an EXISTS's is a boolean)
                with jax.named_scope("get_gather"):
                    rsel = (kind_t >= 2) & (klen_t > 0)
                    gsel = found & rsel
                    is_get = found & (kind_t == 2)
                    oh_get = eq & is_get[:, None]
                    gver = (ver * oh_get).sum(1)
                    gvlen = (vlen * oh_get).sum(1)
                    # the slot whose value this GET answers with, as the
                    # table stood when the wave began (-1: no value)
                    gslot = jnp.where(is_get, hit, -1)
                with jax.named_scope("apply_set"):
                    # DEL applies: clear the matched slot (the table is
                    # compare-all associative — no probe chains to
                    # repair, unlike the host twin's open addressing) and
                    # bump the shard version exactly like the host
                    # store's delete() does on a successful delete
                    del_hit = ok_w & (kind_t == 3) & found
                    used = used & ~(eq & del_hit[:, None])
                    sver = sver + del_hit
                    # SET applies: same one-hot word-select update as the
                    # pure-SET program, gated on this op BEING a SET
                    is_set = ok_w & (kind_t == 1)
                    slot = jnp.where(found, hit, jnp.argmax(~used, 1))
                    full = used.all(1)
                    apply = is_set & (found | ~full)
                    overflow = jnp.any(is_set & ~found & full)
                    onehot = (
                        jnp.arange(Pc)[None, :] == slot[:, None]
                    ) & apply[:, None]
                    oh3 = onehot[:, :, None]
                    used = used | onehot
                    keyw = jnp.where(oh3, kwin_t[:, None, :], keyw)
                    klen = jnp.where(onehot, klen_t[:, None], klen)
                    new_ver = sver + 1
                    ver = jnp.where(onehot, new_ver[:, None], ver)
                    vlen = jnp.where(onehot, vlen_t[:, None], vlen)
                    sver = jnp.where(apply, new_ver, sver)
                    # the slot this wave's SET writes its value row to
                    # (-1: writes none)
                    wslot = jnp.where(apply, slot, -1)
                return (used, keyw, klen, ver, vlen, sver), (
                    overflow,
                    gsel,
                    gver,
                    gvlen,
                    gslot,
                    wslot,
                )

            used0, keyw0, klen0, ver0, valw0, vlen0, sver0 = state
            kwin_full = jnp.pad(ops.kwin, ((0, 0), (0, 0), (0, K4 - Ku4)))
            vwin_full = jnp.pad(ops.vwin, ((0, 0), (0, 0), (0, VW4 - VWu4)))
            xs = (present, kind_w, ops.klen, ops.vlen, kwin_full)
            (
                (used, keyw, klen, ver, vlen, sver),
                (over_w, gfound, gver, gvlen, gslot, wslot),
            ) = lax.scan(
                wave_step, (used0, keyw0, klen0, ver0, vlen0, sver0), xs
            )
            with jax.named_scope("flags"):
                flags = jnp.stack(
                    [
                        all_v1.astype(I32),
                        jnp.any(over_w).astype(I32),
                        jnp.any(sver >= jnp.int32(2**31 - 2)).astype(I32),
                    ]
                )
            # device-side gather of the GET-bearing waves + two-plane
            # meta pack: [0]=version, [1]=(vlen<<1)|found
            with jax.named_scope("get_gather"):
                gfound_g = jnp.take(gfound, gidx, axis=0).astype(I32)
                gver_g = jnp.take(gver, gidx, axis=0)
                gvlen_g = jnp.take(gvlen, gidx, axis=0)
                meta = jnp.stack([gver_g, (gvlen_g << 1) | gfound_g])
            with jax.named_scope("value_resolve"):
                gval_g, valw = DeviceKVTable._resolve_values(
                    valw0, jnp.take(gslot, gidx, axis=0), gidx, wslot,
                    vwin_full,
                )
            new_state = (used, keyw, klen, ver, valw, vlen, sver)
            return new_state, flags, meta, gval_g

        return jax.jit(mixed, static_argnames=("W", "max_phases"))

    def mixed_apply(self, alive, base, depth: int, kind: np.ndarray,
                    get_waves: np.ndarray, ops: DeviceWindowOps, W: int,
                    max_phases: int = 4, state=None):
        """Dispatch one mixed decide+apply+lookup window. Returns device
        handles ``(new_state, flags, meta, gval)`` where ``meta`` is
        i32[2, Gp, S] ([0]=version, [1]=(vlen<<1)|found) and ``gval``
        u32[Gp, S, VW4], both gathered to the ``get_waves`` rows (padded
        to a power of two; the caller maps real rows). The caller reads
        the 12-byte flags first and fetches meta/gval only on a clean
        window. ``state`` overrides the table state to run against (the
        pipelined lane chains on the previous in-flight window's
        unresolved output, same as :meth:`decide_apply`)."""
        if ops.klen.shape[0] < W:
            pad = W - ops.klen.shape[0]
            ops = DeviceWindowOps(
                *(
                    np.concatenate(
                        [a, np.zeros((pad,) + a.shape[1:], a.dtype)]
                    )
                    for a in ops
                )
            )
        if kind.shape[0] < W:
            kind = np.concatenate(
                [kind, np.zeros((W - kind.shape[0], kind.shape[1]), kind.dtype)]
            )
        if self._laddered:
            # a table with a ladder fixes Gp at W: a window of rung W
            # then has one mixed signature whatever share of its waves
            # bears a GET and however full it is, so the ladder holds
            # one program a rung and not one for each (W, Gp) pair
            Gp = W
        else:
            Gp = 1
            while Gp < max(1, len(get_waves)):
                Gp <<= 1
        gidx = np.zeros(Gp, np.int32)
        gidx[: len(get_waves)] = get_waves
        key = ("mix", W, ops.kwin.shape[2], ops.vwin.shape[2], Gp)
        fn = self._program(
            key, W, lambda: self._build_mixed(key[2], key[3], Gp),
            lambda w: self.mixed_apply(
                alive, base, 0, kind[:0], get_waves[:0],
                DeviceWindowOps(*(a[:0] for a in ops)), W=w,
                max_phases=max_phases, state=state,
            ),
        )
        with self._placing(alive, base, kind, *ops):
            alive_d = self.kernel.place(alive)
            base_d = self._put_shards(base)
            kind_d = self._put_waves(kind)
            ops_d = self._place_ops(ops)
        with self._calling(key):
            return fn(
                self.state if state is None else state,
                alive_d,
                base_d,
                np.int32(depth),
                kind_d,
                gidx,
                ops_d,
                W=W,
                max_phases=max_phases,
            )

    def adopt(self, new_state) -> None:
        self.state = new_state

    # -- sync down (demotion / checkpoint) -----------------------------------

    def dump(self) -> "TableDump":
        """Materialize the table on host: the live entries as arrays in
        shard-major slot order, plus the per-shard counters. Each plane
        is fetched once (gathered from every device of the mesh) and no
        Python runs per row."""
        # the flags first: they say which slots hold a row
        used = self._fetch(self.state[0])[: self.n_shards]
        s_idx, p_idx = np.nonzero(used)
        n = len(s_idx)
        with device_annotation("rabia.sync.dump"):
            keyw, klen, ver, valw, vlen, sver = map(
                self._fetch, self.state[1:]
            )
            klens = klen[s_idx, p_idx].astype(np.int64)
            vlens = vlen[s_idx, p_idx].astype(np.int64)
            keys = keyw.view(np.uint8).reshape(self.S, self.P, self.K)[
                s_idx, p_idx
            ]
            # zero tails whatever a slot holds past its key: the host
            # store hashes and compares whole zero-padded lanes
            keys[np.arange(self.K) >= klens[:, None]] = 0
            vals = valw.view(np.uint8).reshape(self.S, self.P, self.VW)[
                s_idx, p_idx
            ]
            self.sync_rows += n
            return TableDump(
                shards=s_idx.astype(np.int64),
                keys=keys,
                klens=klens,
                # the values back to back: one buffer that every replica
                # store references by offset and length
                vbuf=vals[np.arange(self.VW) < vlens[:, None]].tobytes(),
                vlens=vlens,
                versions=ver[s_idx, p_idx].astype(np.int64),
                shard_version=sver[: self.n_shards].astype(np.int64),
            )

    @staticmethod
    def _fetch(a) -> np.ndarray:
        """One device array on the host, whole and contiguous: a fetched
        sharded array can come back with a non-contiguous layout, which
        ``.view(uint8)`` rejects."""
        return np.ascontiguousarray(np.asarray(a))

    def upload_from(self, sm, seed_cache: Optional[dict] = None) -> bool:
        """Rebuild the device table from one host replica store
        (``dump``'s inverse — the re-promotion path after a demotion).

        ``seed_cache`` (optional): a ``(shard, version) -> value bytes``
        dict populated with every uploaded entry, so the engine's GET
        meta-only read path can resolve pre-promotion versions without
        downloading values (mesh_engine._dev_resolve).

        Returns False, leaving the device state untouched, when the host
        content is outside the lane's envelope: an overflow side-store
        entry, a key over ``K`` bytes, a value over ``VW`` bytes, more
        than ``P`` live entries in one shard, or a version past i32.
        Placement is order-free: the fused program's match compares the
        op key against ALL ``P`` slots of a shard, so any assignment of
        entries to distinct slots is a valid table.
        """

        from rabia_tpu.apps.vector_kv import _USED

        store = sm.store
        if store._overflow:
            return False  # long keys live outside the inline table
        idx = np.nonzero(store.state == _USED)[0]
        shards = store.shard_col[idx]
        if idx.size:
            if int(store.key_len[idx].max()) > self.K:
                return False
            if int(store.val_len[idx].max()) > self.VW:
                return False
            if int(store.version[idx].max()) >= 2**31 - 2:
                return False
            counts = np.bincount(shards, minlength=self.n_shards)
            if int(counts.max()) > self.P:
                return False
        if int(store.shard_version[: self.n_shards].max(initial=0)) >= (
            2**31 - 2
        ):
            return False

        S, Pc = self.S, self.P
        used = np.zeros((S, Pc), bool)
        keyb = np.zeros((S, Pc, self.K), np.uint8)
        klen = np.zeros((S, Pc), np.int32)
        ver = np.zeros((S, Pc), np.int32)
        valb = np.zeros((S, Pc, self.VW), np.uint8)
        vlen = np.zeros((S, Pc), np.int32)
        # stable per-shard slot assignment: entries sorted by shard, slot
        # p = running index within the shard — columnar scatters for the
        # fixed-width planes; only the ragged value buffers loop
        order = np.argsort(shards, kind="stable")
        if idx.size:
            sh_sorted = shards[order]
            starts = np.searchsorted(sh_sorted, np.arange(self.n_shards))
            pos = np.arange(idx.size) - starts[sh_sorted]
            src = idx[order]
            used[sh_sorted, pos] = True
            kls = store.key_len[src].astype(np.int64)
            klen[sh_sorted, pos] = kls
            ver[sh_sorted, pos] = store.version[src]
            vlen[sh_sorted, pos] = store.val_len[src]
            key_bytes_all = store.key_lanes[src].view(np.uint8)  # [n, L*8]
            kb_w = min(self.K, key_bytes_all.shape[1])
            # zero-padded lanes guarantee zero tails, so one 2-D copy is
            # exact (no per-row tail clearing needed)
            keyb[sh_sorted, pos, :kb_w] = key_bytes_all[:, :kb_w]
            for j in range(idx.size):
                i = src[j]
                buf = store.val_buf[i]
                a = int(store.val_off[i])
                b = a + int(store.val_len[i])
                v = buf[a:b] if buf is not None else b""
                valb[sh_sorted[j], pos[j], : len(v)] = np.frombuffer(
                    v, np.uint8
                )
                if seed_cache is not None:
                    seed_cache[
                        (int(sh_sorted[j]), int(store.version[i]))
                    ] = bytes(v)
        sver = np.zeros(S, np.int32)
        sver[: self.n_shards] = store.shard_version[: self.n_shards]

        put = self._put_shards
        self.state = (
            put(used),
            put(np.ascontiguousarray(keyb).view(np.uint32)),
            put(klen),
            put(ver),
            put(np.ascontiguousarray(valb).view(np.uint32)),
            put(vlen),
            put(sver),
        )
        return True

    def sync_into(self, sm, dump: Optional[dict] = None) -> None:
        """Rebuild one host replica store (VectorShardedKV) from the
        device table. The host store is reset first — in device mode the
        host replicas saw none of the device lane's applies. Pass a
        precomputed ``dump()`` when syncing several replicas: the table
        materialization (a device->host transfer) then happens once.
        A dump that carries ``"rows"`` is rebuilt from those rows."""
        from rabia_tpu.apps.vector_kv import VectorKVStore

        d = dump if dump is not None else self.dump()
        if "rows" in d:
            d = TableDump.from_rows(d["rows"], d["shard_version"], self.K)
        n = len(d["shards"])
        with device_annotation("rabia.sync.rebuild"):
            store = VectorKVStore(
                self.n_shards, capacity=max(1 << 10, 2 * n)
            )
            if n:
                # one bulk insert for the whole table (distinct keys in
                # shard-major order), then pin the real versions over
                # the provisional ones bulk_set assigned
                shards, klens, vlens = d["shards"], d["klens"], d["vlens"]
                if int(klens.max()) > store.K:
                    raise ValueError(
                        f"a table key of {int(klens.max())} B does not "
                        f"fit the host store's {store.K} B of lanes"
                    )
                mat = np.zeros((n, store.K), np.uint8)
                w = min(self.K, store.K)
                mat[:, :w] = d["keys"][:, :w]
                lanes = mat.view(np.uint64)
                voffs = np.cumsum(vlens) - vlens
                store.bulk_set(
                    shards, lanes, klens, (d["vbuf"], voffs, vlens)
                )
                slot = store._lookup(shards, lanes, klens)
                store.version[slot] = d["versions"]
            store.shard_version[:] = 0
            store.shard_version[: self.n_shards] = d["shard_version"]
            sm.store = store

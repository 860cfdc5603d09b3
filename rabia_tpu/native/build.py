"""Lazy g++ build + loaders for the native libraries.

Four artifacts, all digest-keyed and built on first use:
- ``transport.cpp``   -> ctypes CDLL (the TCP data plane)
- ``codec.cpp``       -> CPython extension module (the binary message
  codec, SURVEY §2 C9's native component)
- ``hostkernel.cpp``  -> ctypes CDLL (the engine's per-activation
  consensus step; numpy twin in kernel/host_driver.py stays the
  semantics owner)
- ``statekernel.cpp`` -> ctypes CDLL (the native apply plane: the
  binary-op KV state machine; the Python apply path in
  apps/kvstore.py stays the semantics owner, RABIA_PY_APPLY=1
  forces it)
- ``runtime.cpp``     -> ctypes CDLL (the native engine runtime: a
  GIL-free io/tick thread gluing transport -> hostkernel ->
  statekernel; the asyncio orchestration stays the semantics owner,
  RABIA_PY_RUNTIME=1 forces it)
- ``sessionkernel.cpp`` -> ctypes CDLL (the native gateway plane: the
  client session/dedup table; the Python SessionTable in
  gateway/session.py stays the semantics owner, RABIA_PY_GATEWAY=1
  forces it)

The two the device lane loads (codec, hostkernel) build and load under the
span ``rabia.setup.native`` (``RABIA_TRACE=1``: set-up by part).
"""

from __future__ import annotations

import ctypes
import hashlib
import importlib.util
import os
import subprocess
import sysconfig
import threading
from pathlib import Path

from rabia_tpu.core.errors import InternalError
from rabia_tpu.core.tracing import device_annotation

_HERE = Path(__file__).parent
_SRC = _HERE / "transport.cpp"
_CODEC_SRC = _HERE / "codec.cpp"
_HK_SRC = _HERE / "hostkernel.cpp"
_SK_SRC = _HERE / "statekernel.cpp"
_LOCK = threading.Lock()
_CACHED: ctypes.CDLL | None = None
_CODEC_CACHED = None
_CODEC_FAILED: str | None = None
_HK_CACHED: ctypes.CDLL | None = None
_HK_FAILED: str | None = None
_SK_CACHED: ctypes.CDLL | None = None
_SK_FAILED: str | None = None


# Every kernel includes the annotations header; its digest keys rebuilds
# exactly like the kernel's own source (a changed macro or lock wrapper
# must invalidate every cached .so).
_ANNOT = _HERE / "annotations.h"


def _flavor() -> tuple[str, list[str]]:
    """(digest-suffix, extra flags) of the current build FLAVOR.

    ``RABIA_NATIVE_DEBUG=1`` selects the debug flavor: the lock-order
    checker in annotations.h compiles in (acquisition-order inversions
    and non-recursive double locks abort with both lock names), plus
    debug symbols. The suffix keeps flavors side by side in the cache —
    switching the env back and forth never rebuilds."""
    if os.environ.get("RABIA_NATIVE_DEBUG") == "1":
        return "-dbg", ["-DRABIA_NATIVE_DEBUG=1", "-g"]
    return "", []


def _digest_of(*srcs: Path) -> str:
    h = hashlib.blake2s(digest_size=8)
    for s in srcs:
        h.update(s.read_bytes())
    h.update(_flavor()[0].encode())
    return h.hexdigest()


def _src_digest() -> str:
    return _digest_of(_SRC, _ANNOT)


def lib_path() -> Path:
    """Target .so path, keyed by source digest so edits force rebuilds."""
    return _HERE / f"_transport_{_src_digest()}{_flavor()[0]}.so"


def _compile(
    src: Path, target: Path, extra_args: list[str], stale_glob: str,
    what: str, link_args: list[str] | None = None,
) -> None:
    # compile to a private temp path, then atomically rename: an
    # interrupted or concurrent build (the lock is per-process only) must
    # never leave a truncated .so at the digest-keyed path, which would be
    # trusted forever by the exists() fast path
    tmp = target.with_suffix(f".tmp{os.getpid()}")
    cmd = [
        "g++",
        "-O2",
        "-std=c++17",
        "-shared",
        "-fPIC",
        *_flavor()[1],
        *extra_args,
        str(src),
        "-o",
        str(tmp),
        # libraries must follow the objects that use them (GNU ld
        # resolves left to right)
        *(link_args or []),
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        tmp.unlink(missing_ok=True)
        raise InternalError(
            f"native {what} build failed:\n{proc.stderr[-2000:]}"
        )
    os.replace(tmp, target)
    # clean up stale builds of older source versions (same flavor only:
    # regular and -dbg artifacts coexist, keyed by their suffix)
    dbg = _flavor()[0] == "-dbg"
    for old in _HERE.glob(stale_glob):
        if old != target and old.name.endswith("-dbg.so") == dbg:
            try:
                old.unlink()
            except OSError:
                pass


def _build(target: Path) -> None:
    _compile(_SRC, target, ["-pthread"], "_transport_*.so", "transport")


def _codec_path() -> Path:
    return _HERE / f"_codec_{_digest_of(_CODEC_SRC)}{_flavor()[0]}.so"


def _build_codec(target: Path) -> None:
    import numpy as np

    _compile(
        _CODEC_SRC,
        target,
        [
            f"-I{sysconfig.get_paths()['include']}",
            f"-I{np.get_include()}",
        ],
        "_codec_*.so",
        "codec",
        link_args=["-lz"],  # SyncResponse snapshot (de)compression
    )


def load_codec():
    """Build (if needed) and import the codec extension module.

    Returns the module, or None when unavailable (no compiler, build
    failure) — callers fall back to the Python codec. The failure is
    remembered so a broken toolchain costs one build attempt, not one
    per serializer construction. ``RABIA_PY_CODEC=1`` forces the Python
    codec (debug/differential testing)."""
    global _CODEC_CACHED, _CODEC_FAILED
    if os.environ.get("RABIA_PY_CODEC"):
        return None
    with _LOCK:
        if _CODEC_CACHED is not None:
            return _CODEC_CACHED
        if _CODEC_FAILED is not None:
            return None
        try:
            with device_annotation("rabia.setup.native"):
                target = _codec_path()
                if not target.exists():
                    _build_codec(target)
                spec = importlib.util.spec_from_file_location(
                    "rabia_native_codec", target
                )
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
        except Exception as e:  # noqa: BLE001 - any failure means fallback
            _CODEC_FAILED = str(e)
            return None
        _CODEC_CACHED = mod
        return mod


def _hk_path() -> Path:
    return _HERE / f"_hostkernel_{_digest_of(_HK_SRC)}{_flavor()[0]}.so"


def load_hostkernel() -> ctypes.CDLL | None:
    """Build (if needed) and dlopen the host-kernel step library.

    Returns the CDLL with prototypes set, or None when unavailable —
    callers fall back to the numpy step, which stays the semantics
    owner. ``RABIA_PY_HOSTKERNEL=1`` forces the numpy step
    (debug/differential testing)."""
    global _HK_CACHED, _HK_FAILED
    if os.environ.get("RABIA_PY_HOSTKERNEL"):
        return None
    with _LOCK:
        if _HK_CACHED is not None:
            return _HK_CACHED
        if _HK_FAILED is not None:
            return None
        try:
            with device_annotation("rabia.setup.native"):
                target = _hk_path()
                if not target.exists():
                    _compile(
                        _HK_SRC, target, ["-O3"], "_hostkernel_*.so",
                        "hostkernel",
                    )
                lib = ctypes.CDLL(os.fspath(target))
        except Exception as e:  # noqa: BLE001 - any failure means fallback
            _HK_FAILED = str(e)
            return None
        # pointer args are c_void_p: callers pass raw ndarray.ctypes.data
        # ints (cheapest ctypes marshalling on the per-activation path)
        p = ctypes.c_void_p
        lib.rk_node_step.restype = None
        lib.rk_node_step.argtypes = [
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32,
            ctypes.c_uint32, ctypes.c_uint32,
            p, p, p, p, p, p, p, p, p, p, p,
            p, p, p, p,
        ]
        if hasattr(lib, "rk_node_step_ex"):
            # rk_node_step + coin-flip accounting (chaos-plane telemetry)
            lib.rk_node_step_ex.restype = None
            lib.rk_node_step_ex.argtypes = [
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32,
                ctypes.c_uint32, ctypes.c_uint32,
                p, p, p, p, p, p, p, p, p, p, p,
                p, p, p, p, p,
            ]
        lib.rk_start_slots.restype = None
        lib.rk_start_slots.argtypes = [
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            p, p, p,
            p, p, p, p, p, p, p, p, p, p,
        ]
        lib.rk_open_scan.restype = ctypes.c_int32
        lib.rk_open_scan.argtypes = [
            ctypes.c_int32, p, p, p, p, p, p, p, p, p, p,
        ]
        lib.rk_pack_scan.restype = ctypes.c_int32
        lib.rk_pack_scan.argtypes = [
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_uint64, ctypes.c_int64, ctypes.c_int64,
            p, p, p, p, p,
        ]
        lib.rk_pack_gather.restype = ctypes.c_int32
        lib.rk_pack_gather.argtypes = [
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            p, p, p,
            p, p, p, p, p,
        ]
        lib.rk_stall_scan.restype = ctypes.c_int32
        lib.rk_stall_scan.argtypes = [
            ctypes.c_int32, p, p, ctypes.c_double, ctypes.c_double,
        ]
        # native per-tick fast path (the rk tick context)
        lib.rk_ctx_create.restype = ctypes.c_void_p
        lib.rk_ctx_create.argtypes = [p, p, p, p]
        lib.rk_ctx_destroy.restype = None
        lib.rk_ctx_destroy.argtypes = [p]
        # shard-group range (thread-per-shard-group runtime)
        lib.rk_set_range.restype = None
        lib.rk_set_range.argtypes = [
            p,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_uint32,
        ]
        lib.rk_rows_seen.restype = ctypes.c_uint64
        lib.rk_rows_seen.argtypes = [p]
        lib.rk_dropped.restype = ctypes.c_uint64
        lib.rk_dropped.argtypes = [p]
        lib.rk_carry_count.restype = ctypes.c_int64
        lib.rk_carry_count.argtypes = [p]
        lib.rk_drain_stale.restype = ctypes.c_int64
        lib.rk_drain_stale.argtypes = [p, p, p, p, ctypes.c_int64]
        lib.rk_ingest.restype = ctypes.c_int32
        lib.rk_ingest.argtypes = [
            p, p, ctypes.c_int64, ctypes.c_int32, ctypes.c_double,
        ]
        lib.rk_tick.restype = None
        lib.rk_tick.argtypes = [
            p, ctypes.c_double, p, ctypes.c_int64, ctypes.c_int32,
            p, p, p, p,
        ]
        lib.rk_retransmit.restype = None
        lib.rk_retransmit.argtypes = [
            p, ctypes.c_double, ctypes.c_double, p, ctypes.c_int64, p,
        ]
        # observability counter block (versioned, append-only)
        lib.rk_counters_version.restype = ctypes.c_int32
        lib.rk_counters_version.argtypes = []
        lib.rk_counters_count.restype = ctypes.c_int32
        lib.rk_counters_count.argtypes = []
        lib.rk_counters.restype = ctypes.c_void_p
        lib.rk_counters.argtypes = [p]
        if hasattr(lib, "rk_phase_hist"):
            # phases-to-decide histogram (chaos-plane telemetry, v2)
            lib.rk_phase_hist_len.restype = ctypes.c_int32
            lib.rk_phase_hist_len.argtypes = []
            lib.rk_phase_hist.restype = ctypes.c_void_p
            lib.rk_phase_hist.argtypes = [p]
        # flight recorder (fixed-size binary event ring, versioned ABI)
        lib.rk_flight_version.restype = ctypes.c_int32
        lib.rk_flight_version.argtypes = []
        lib.rk_flight_cap.restype = ctypes.c_int32
        lib.rk_flight_cap.argtypes = []
        lib.rk_flight_record_size.restype = ctypes.c_int32
        lib.rk_flight_record_size.argtypes = []
        lib.rk_flight.restype = ctypes.c_void_p
        lib.rk_flight.argtypes = [p]
        lib.rk_flight_head.restype = ctypes.c_uint64
        lib.rk_flight_head.argtypes = [p]
        if hasattr(lib, "rk_dwell"):
            # per-phase consensus dwell histograms (RTH-style geometry)
            lib.rk_dwell_version.restype = ctypes.c_int32
            lib.rk_dwell_version.argtypes = []
            lib.rk_dwell_phases.restype = ctypes.c_int32
            lib.rk_dwell_phases.argtypes = []
            lib.rk_dwell_buckets.restype = ctypes.c_int32
            lib.rk_dwell_buckets.argtypes = []
            lib.rk_dwell_sub_bits.restype = ctypes.c_int32
            lib.rk_dwell_sub_bits.argtypes = []
            lib.rk_dwell_min_exp.restype = ctypes.c_int32
            lib.rk_dwell_min_exp.argtypes = []
            lib.rk_dwell.restype = ctypes.c_void_p
            lib.rk_dwell.argtypes = [p]
        _HK_CACHED = lib
        return lib


def _sk_path() -> Path:
    return (
        _HERE / f"_statekernel_{_digest_of(_SK_SRC, _ANNOT)}{_flavor()[0]}.so"
    )


def load_statekernel() -> ctypes.CDLL | None:
    """Build (if needed) and dlopen the native apply-plane library.

    Returns the CDLL with prototypes set, or None when unavailable —
    callers fall back to the Python binary-op apply in apps/kvstore.py,
    which stays the semantics owner. ``RABIA_PY_APPLY=1`` forces the
    Python path (debug/differential testing, the conformance gate's
    second leg)."""
    global _SK_CACHED, _SK_FAILED
    if os.environ.get("RABIA_PY_APPLY") == "1":
        return None
    with _LOCK:
        if _SK_CACHED is not None:
            return _SK_CACHED
        if _SK_FAILED is not None:
            return None
        try:
            target = _sk_path()
            if not target.exists():
                _compile(
                    _SK_SRC, target, ["-O3"], "_statekernel_*.so",
                    "statekernel",
                )
            lib = ctypes.CDLL(os.fspath(target))
        except Exception as e:  # noqa: BLE001 - any failure means fallback
            _SK_FAILED = str(e)
            return None
        p = ctypes.c_void_p
        i64 = ctypes.c_int64
        lib.sk_plane_create.restype = ctypes.c_void_p
        lib.sk_plane_create.argtypes = [i64, i64, i64, i64]
        lib.sk_plane_destroy.restype = None
        lib.sk_plane_destroy.argtypes = [p]
        lib.sk_counters_version.restype = ctypes.c_int32
        lib.sk_counters_version.argtypes = []
        lib.sk_counters_count.restype = ctypes.c_int32
        lib.sk_counters_count.argtypes = []
        lib.sk_counters.restype = ctypes.c_void_p
        lib.sk_counters.argtypes = [p]
        lib.sk_flight_version.restype = ctypes.c_int32
        lib.sk_flight_version.argtypes = []
        lib.sk_flight_cap.restype = ctypes.c_int32
        lib.sk_flight_cap.argtypes = []
        lib.sk_flight_record_size.restype = ctypes.c_int32
        lib.sk_flight_record_size.argtypes = []
        lib.sk_flight.restype = ctypes.c_void_p
        lib.sk_flight.argtypes = [p]
        lib.sk_flight_head.restype = ctypes.c_uint64
        lib.sk_flight_head.argtypes = [p]
        lib.sk_store_count.restype = i64
        lib.sk_store_count.argtypes = [p]
        lib.sk_store_size.restype = i64
        lib.sk_store_size.argtypes = [p, i64]
        lib.sk_store_version.restype = ctypes.c_uint64
        lib.sk_store_version.argtypes = [p, i64]
        lib.sk_set_version.restype = None
        lib.sk_set_version.argtypes = [p, i64, ctypes.c_uint64]
        lib.sk_store_stats.restype = None
        lib.sk_store_stats.argtypes = [p, i64, p]
        lib.sk_add_stats.restype = None
        lib.sk_add_stats.argtypes = [
            p, i64, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
        ]
        lib.sk_get.restype = i64
        lib.sk_get.argtypes = [
            p, i64, p, i64,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.sk_export_size.restype = i64
        lib.sk_export_size.argtypes = [p, i64]
        lib.sk_export.restype = i64
        lib.sk_export.argtypes = [p, i64, p, i64]
        lib.sk_clear_store.restype = None
        lib.sk_clear_store.argtypes = [p, i64]
        lib.sk_delete_raw.restype = ctypes.c_int32
        lib.sk_delete_raw.argtypes = [p, i64, p, i64]
        lib.sk_insert_raw.restype = ctypes.c_int32
        lib.sk_insert_raw.argtypes = [
            p, i64, p, i64, p, i64,
            ctypes.c_uint64, ctypes.c_double, ctypes.c_double,
        ]
        lib.sk_apply_wave.restype = i64
        lib.sk_apply_wave.argtypes = [
            p, p, p, p, p, p, i64, ctypes.c_double, ctypes.c_int32,
        ]
        lib.sk_apply_ops.restype = i64
        lib.sk_apply_ops.argtypes = [
            p, i64, p, p, i64, ctypes.c_double, ctypes.c_int32,
        ]
        lib.sk_out_buf.restype = ctypes.c_void_p
        lib.sk_out_buf.argtypes = [p]
        lib.sk_out_offs.restype = ctypes.c_void_p
        lib.sk_out_offs.argtypes = [p]
        lib.sk_out_count.restype = i64
        lib.sk_out_count.argtypes = [p]
        # thread-per-shard-group apply lanes (runtime workers > 1)
        lib.sk_set_groups.restype = ctypes.c_int32
        lib.sk_set_groups.argtypes = [p, ctypes.c_int32]
        lib.sk_apply_wave_lane.restype = i64
        lib.sk_apply_wave_lane.argtypes = [
            p, ctypes.c_int32, p, p, p, p, p, i64,
            ctypes.c_double, ctypes.c_int32,
        ]
        lib.sk_out_buf_lane.restype = ctypes.c_void_p
        lib.sk_out_buf_lane.argtypes = [p, ctypes.c_int32]
        lib.sk_out_offs_lane.restype = ctypes.c_void_p
        lib.sk_out_offs_lane.argtypes = [p, ctypes.c_int32]
        # incremental snapshots (durability plane)
        lib.sk_snapshot_delta_size.restype = i64
        lib.sk_snapshot_delta_size.argtypes = [p, i64]
        lib.sk_snapshot_delta.restype = i64
        lib.sk_snapshot_delta.argtypes = [p, i64, p, i64]
        lib.sk_snapshot_mark.restype = None
        lib.sk_snapshot_mark.argtypes = [p, i64]
        # read-side critical-section brackets (native-runtime hook)
        lib.sk_plane_lock.restype = None
        lib.sk_plane_lock.argtypes = [p]
        lib.sk_plane_unlock.restype = None
        lib.sk_plane_unlock.argtypes = [p]
        _SK_CACHED = lib
        return lib


def load_library() -> ctypes.CDLL:
    """Build (if needed) and dlopen the transport library; sets prototypes.

    ``RABIA_NATIVE_LIB`` points at a prebuilt .so (container runtime
    images ship one so they need no toolchain)."""
    global _CACHED
    with _LOCK:
        if _CACHED is not None:
            return _CACHED
        prebuilt = os.environ.get("RABIA_NATIVE_LIB")
        if prebuilt:
            target = Path(prebuilt)
            if not target.exists():
                # an explicitly configured path that is missing must fail
                # loudly — falling back to a source build would mask the
                # misconfiguration (and runtime images ship no compiler)
                raise InternalError(
                    f"RABIA_NATIVE_LIB points at a missing file: {prebuilt}"
                )
        else:
            target = lib_path()
            if not target.exists():
                _build(target)
        lib = ctypes.CDLL(os.fspath(target))
        if prebuilt:
            # a prebuilt library bypasses the source-digest keying: probe
            # the newest exported symbol so a stale .so fails fast with a
            # clear message instead of a cryptic AttributeError later
            try:
                lib.rt_counters
                lib.rt_flight_copy
            except AttributeError:
                raise InternalError(
                    f"RABIA_NATIVE_LIB library {prebuilt} is stale "
                    "(missing rt_counters/rt_flight_copy); rebuild it "
                    "from transport.cpp"
                ) from None

        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.rt_create.restype = ctypes.c_void_p
        lib.rt_create.argtypes = [
            u8p,
            ctypes.c_char_p,
            ctypes.c_uint16,
            ctypes.POINTER(ctypes.c_uint16),
        ]
        lib.rt_add_peer.restype = ctypes.c_int
        lib.rt_add_peer.argtypes = [
            ctypes.c_void_p,
            u8p,
            ctypes.c_char_p,
            ctypes.c_uint16,
        ]
        lib.rt_remove_peer.restype = ctypes.c_int
        lib.rt_remove_peer.argtypes = [ctypes.c_void_p, u8p]
        if hasattr(lib, "rt_set_shaping"):
            # chaos shaping layer (a prebuilt RABIA_NATIVE_LIB may
            # predate it; TcpNetwork.set_peer_shaping raises then)
            lib.rt_set_shaping.restype = ctypes.c_int
            lib.rt_set_shaping.argtypes = [
                ctypes.c_void_p, u8p,
                ctypes.c_uint32, ctypes.c_uint32,
                ctypes.c_double, ctypes.c_uint64,
            ]
            lib.rt_clear_shaping.restype = ctypes.c_int
            lib.rt_clear_shaping.argtypes = [ctypes.c_void_p]
        lib.rt_send.restype = ctypes.c_int
        lib.rt_send.argtypes = [
            ctypes.c_void_p,
            u8p,
            ctypes.c_char_p,
            ctypes.c_uint32,
        ]
        lib.rt_broadcast.restype = ctypes.c_int
        lib.rt_broadcast.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_uint32,
        ]
        # batch-staged broadcast of the native tick's outbound buffer
        # ([u32 record_len][frame]... records, one lock + one kick)
        lib.rt_broadcast_frames.restype = ctypes.c_int
        lib.rt_broadcast_frames.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_int64,
        ]
        lib.rt_recv.restype = ctypes.c_int
        lib.rt_recv.argtypes = [
            ctypes.c_void_p,
            u8p,
            u8p,
            ctypes.c_uint32,
            ctypes.c_int,
        ]
        lib.rt_recv_borrow.restype = ctypes.c_int64
        lib.rt_recv_borrow.argtypes = [
            ctypes.c_void_p,
            u8p,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_int,
        ]
        lib.rt_recv_release.restype = None
        lib.rt_recv_release.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        # thread-per-shard-group routing (runtime workers > 1)
        lib.rt_set_groups.restype = ctypes.c_int
        lib.rt_set_groups.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int32,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        lib.rt_recv_borrow_group.restype = ctypes.c_int64
        lib.rt_recv_borrow_group.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int32,
            u8p,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_int,
        ]
        lib.rt_connected.restype = ctypes.c_int
        lib.rt_connected.argtypes = [ctypes.c_void_p, u8p, ctypes.c_int]
        lib.rt_port.restype = ctypes.c_uint16
        lib.rt_port.argtypes = [ctypes.c_void_p]
        lib.rt_dropped.restype = ctypes.c_uint64
        lib.rt_dropped.argtypes = [ctypes.c_void_p]
        lib.rt_pool_stats.restype = None
        lib.rt_pool_stats.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.rt_out_pool_stats.restype = None
        lib.rt_out_pool_stats.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        # observability counter block (versioned, append-only)
        lib.rt_counters_version.restype = ctypes.c_int32
        lib.rt_counters_version.argtypes = []
        lib.rt_counters_count.restype = ctypes.c_int32
        lib.rt_counters_count.argtypes = []
        lib.rt_counters.restype = ctypes.c_void_p
        lib.rt_counters.argtypes = [ctypes.c_void_p]
        # flight recorder (frame in/out ring, consistent copy under mu)
        lib.rt_flight_version.restype = ctypes.c_int32
        lib.rt_flight_version.argtypes = []
        lib.rt_flight_record_size.restype = ctypes.c_int32
        lib.rt_flight_record_size.argtypes = []
        lib.rt_flight_copy.restype = ctypes.c_int64
        lib.rt_flight_copy.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_int64,
        ]
        lib.rt_inbox_kick.restype = None
        lib.rt_inbox_kick.argtypes = [ctypes.c_void_p]
        lib.rt_stop.restype = None
        lib.rt_stop.argtypes = [ctypes.c_void_p]
        lib.rt_close.restype = None
        lib.rt_close.argtypes = [ctypes.c_void_p]

        _CACHED = lib
        return lib


_GWS_CACHED: ctypes.CDLL | None = None
_GWS_FAILED: str | None = None


def _gws_path() -> Path:
    digest = _digest_of(_HERE / "sessionkernel.cpp", _ANNOT)
    return _HERE / f"_sessionkernel_{digest}{_flavor()[0]}.so"


def load_sessionkernel() -> ctypes.CDLL | None:
    """Build (if needed) and dlopen the native gateway-plane library
    (sessionkernel.cpp: the client session/dedup table). Returns the
    CDLL with prototypes set, or None when unavailable — the gateway
    falls back to the Python :class:`~rabia_tpu.gateway.session.
    SessionTable`, which stays the semantics owner
    (``RABIA_PY_GATEWAY=1`` forces it; the conformance gate's second
    leg)."""
    global _GWS_CACHED, _GWS_FAILED
    if os.environ.get("RABIA_PY_GATEWAY") == "1":
        return None
    with _LOCK:
        if _GWS_CACHED is not None:
            return _GWS_CACHED
        if _GWS_FAILED is not None:
            return None
        try:
            target = _gws_path()
            if not target.exists():
                _compile(
                    (_HERE / "sessionkernel.cpp"), target, ["-O3"],
                    "_sessionkernel_*.so", "sessionkernel",
                )
            lib = ctypes.CDLL(os.fspath(target))
        except Exception as e:  # noqa: BLE001 - any failure means fallback
            _GWS_FAILED = str(e)
            return None
        p = ctypes.c_void_p
        i64 = ctypes.c_int64
        u64 = ctypes.c_uint64
        lib.gws_create.restype = ctypes.c_void_p
        lib.gws_create.argtypes = [i64, ctypes.c_double, i64,
                                   ctypes.c_double]
        lib.gws_destroy.restype = None
        lib.gws_destroy.argtypes = [p]
        lib.gws_counters_version.restype = ctypes.c_int32
        lib.gws_counters_version.argtypes = []
        lib.gws_counters_count.restype = ctypes.c_int32
        lib.gws_counters_count.argtypes = []
        lib.gws_counters.restype = ctypes.c_void_p
        lib.gws_counters.argtypes = [p]
        lib.gws_len.restype = i64
        lib.gws_len.argtypes = [p]
        lib.gws_clear.restype = None
        lib.gws_clear.argtypes = [p]
        lib.gws_stats.restype = None
        lib.gws_stats.argtypes = [p, p]
        lib.gws_hello.restype = i64
        lib.gws_hello.argtypes = [
            p, p, i64, ctypes.c_double, ctypes.POINTER(u64),
        ]
        lib.gws_submit.restype = ctypes.c_int32
        lib.gws_submit.argtypes = [
            p, p, u64, u64, ctypes.c_double,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(i64),
        ]
        lib.gws_complete.restype = ctypes.c_int32
        lib.gws_complete.argtypes = [
            p, p, u64, ctypes.c_int32, u64, p, i64, ctypes.c_double,
        ]
        lib.gws_abort.restype = None
        lib.gws_abort.argtypes = [p, p, u64]
        lib.gws_gc.restype = i64
        lib.gws_gc.argtypes = [p, u64, ctypes.c_double]
        lib.gws_session_info.restype = ctypes.c_int32
        lib.gws_session_info.argtypes = [
            p, p, ctypes.POINTER(i64), ctypes.POINTER(u64),
            ctypes.POINTER(u64), ctypes.POINTER(i64), ctypes.POINTER(i64),
        ]
        lib.gws_get_result.restype = ctypes.c_int32
        lib.gws_get_result.argtypes = [
            p, p, u64, ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(u64),
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(i64),
        ]
        lib.gws_session_ids.restype = i64
        lib.gws_session_ids.argtypes = [p, p, i64]
        lib.gws_result_seqs.restype = i64
        lib.gws_result_seqs.argtypes = [p, p, p, i64]
        lib.gws_inflight_seqs.restype = i64
        lib.gws_inflight_seqs.argtypes = [p, p, p, i64]
        _GWS_CACHED = lib
        return lib


_WAL_CACHED: ctypes.CDLL | None = None
_WAL_FAILED: str | None = None


def _wal_path() -> Path:
    digest = _digest_of(_HERE / "walkernel.cpp", _ANNOT)
    return _HERE / f"_walkernel_{digest}{_flavor()[0]}.so"


def load_walkernel() -> ctypes.CDLL | None:
    """Build (if needed) and dlopen the native durability-plane library
    (walkernel.cpp: the group-commit write-ahead log). Returns the CDLL
    with prototypes set, or None when unavailable — WalPersistence falls
    back to the pure-Python writer, which stays the semantics owner of
    the byte format (``RABIA_PY_WAL=1`` forces it; the conformance
    gate's second leg)."""
    global _WAL_CACHED, _WAL_FAILED
    if os.environ.get("RABIA_PY_WAL") == "1":
        return None
    with _LOCK:
        if _WAL_CACHED is not None:
            return _WAL_CACHED
        if _WAL_FAILED is not None:
            return None
        try:
            target = _wal_path()
            if not target.exists():
                _compile(
                    (_HERE / "walkernel.cpp"), target, ["-O2", "-pthread"],
                    "_walkernel_*.so", "walkernel", link_args=["-lz"],
                )
            lib = ctypes.CDLL(os.fspath(target))
        except Exception as e:  # noqa: BLE001 - any failure means fallback
            _WAL_FAILED = str(e)
            return None
        p = ctypes.c_void_p
        i64 = ctypes.c_int64
        u64 = ctypes.c_uint64
        lib.wal_create.restype = ctypes.c_void_p
        lib.wal_create.argtypes = [
            ctypes.c_char_p, i64, i64, i64, u64, u64,
        ]
        lib.wal_start.restype = ctypes.c_int32
        lib.wal_start.argtypes = [p]
        lib.wal_stop.restype = None
        lib.wal_stop.argtypes = [p]
        lib.wal_destroy.restype = None
        lib.wal_destroy.argtypes = [p]
        lib.wal_append.restype = i64
        lib.wal_append.argtypes = [p, p, i64]
        lib.wal_durable.restype = u64
        lib.wal_durable.argtypes = [p]
        lib.wal_staged.restype = u64
        lib.wal_staged.argtypes = [p]
        lib.wal_io_error.restype = ctypes.c_int32
        lib.wal_io_error.argtypes = [p]
        lib.wal_event_fd.restype = ctypes.c_int
        lib.wal_event_fd.argtypes = [p]
        lib.wal_sync.restype = ctypes.c_int32
        lib.wal_sync.argtypes = [p, ctypes.c_double]
        lib.wal_barrier_covered.restype = i64
        lib.wal_barrier_covered.argtypes = [p, i64, i64]
        lib.wal_set_barrier.restype = None
        lib.wal_set_barrier.argtypes = [p, p, i64]
        lib.wal_get_barrier.restype = None
        lib.wal_get_barrier.argtypes = [p, p, i64]
        lib.wal_counters_version.restype = ctypes.c_int32
        lib.wal_counters_version.argtypes = []
        lib.wal_counters_count.restype = ctypes.c_int32
        lib.wal_counters_count.argtypes = []
        lib.wal_counters.restype = ctypes.c_void_p
        lib.wal_counters.argtypes = [p]
        lib.wal_hist_version.restype = ctypes.c_int32
        lib.wal_hist_version.argtypes = []
        lib.wal_hist_buckets.restype = ctypes.c_int32
        lib.wal_hist_buckets.argtypes = []
        lib.wal_hist_sub_bits.restype = ctypes.c_int32
        lib.wal_hist_sub_bits.argtypes = []
        lib.wal_hist_min_exp.restype = ctypes.c_int32
        lib.wal_hist_min_exp.argtypes = []
        lib.wal_hist.restype = ctypes.c_void_p
        lib.wal_hist.argtypes = [p]
        lib.wal_segment_index.restype = i64
        lib.wal_segment_index.argtypes = [p]
        lib.wal_segment_bytes.restype = i64
        lib.wal_segment_bytes.argtypes = [p]
        _WAL_CACHED = lib
        return lib


_RTM_CACHED: ctypes.CDLL | None = None
_RTM_FAILED: str | None = None


def _rtm_path() -> Path:
    digest = _digest_of(_HERE / "runtime.cpp", _ANNOT)
    return _HERE / f"_runtime_{digest}{_flavor()[0]}.so"


def load_runtime() -> ctypes.CDLL | None:
    """Build (if needed) and dlopen the native engine runtime library
    (runtime.cpp: the GIL-free io/tick thread). Returns the CDLL with
    prototypes set, or None when unavailable — the engine falls back to
    the asyncio orchestration, which stays the semantics owner
    (``RABIA_PY_RUNTIME=1`` forces it)."""
    global _RTM_CACHED, _RTM_FAILED
    if os.environ.get("RABIA_PY_RUNTIME") == "1":
        return None
    with _LOCK:
        if _RTM_CACHED is not None:
            return _RTM_CACHED
        if _RTM_FAILED is not None:
            return None
        try:
            target = _rtm_path()
            if not target.exists():
                _compile(
                    (_HERE / "runtime.cpp"), target, ["-O2", "-pthread"],
                    "_runtime_*.so", "runtime", link_args=["-lz"],
                )
            lib = ctypes.CDLL(os.fspath(target))
        except Exception as e:  # noqa: BLE001 - any failure means fallback
            _RTM_FAILED = str(e)
            return None
        p = ctypes.c_void_p
        i64 = ctypes.c_int64
        lib.rtm_create.restype = ctypes.c_void_p
        lib.rtm_create.argtypes = [p, p, p, p, p]
        lib.rtm_start.restype = ctypes.c_int32
        lib.rtm_start.argtypes = [p]
        lib.rtm_stop.restype = None
        lib.rtm_stop.argtypes = [p]
        lib.rtm_destroy.restype = None
        lib.rtm_destroy.argtypes = [p]
        lib.rtm_state.restype = ctypes.c_int32
        lib.rtm_state.argtypes = [p]
        lib.rtm_pause.restype = None
        lib.rtm_pause.argtypes = [p]
        lib.rtm_resume.restype = None
        lib.rtm_resume.argtypes = [p]
        lib.rtm_event_fd.restype = ctypes.c_int
        lib.rtm_event_fd.argtypes = [p]
        lib.rtm_cmd_push.restype = ctypes.c_int32
        lib.rtm_cmd_push.argtypes = [p, p, i64]
        lib.rtm_ev_drain.restype = i64
        lib.rtm_ev_drain.argtypes = [p, p, i64]
        lib.rtm_counters_version.restype = ctypes.c_int32
        lib.rtm_counters_version.argtypes = []
        lib.rtm_counters_count.restype = ctypes.c_int32
        lib.rtm_counters_count.argtypes = []
        lib.rtm_counters.restype = ctypes.c_void_p
        lib.rtm_counters.argtypes = [p]
        # stage profiler block (RTS_*: cumulative ns per loop stage)
        lib.rtm_stages_version.restype = ctypes.c_int32
        lib.rtm_stages_version.argtypes = []
        lib.rtm_stages_count.restype = ctypes.c_int32
        lib.rtm_stages_count.argtypes = []
        lib.rtm_stages.restype = ctypes.c_void_p
        lib.rtm_stages.argtypes = [p]
        # SLO latency histogram block (RTH_*: log-bucketed, fixed size)
        lib.rtm_hist_version.restype = ctypes.c_int32
        lib.rtm_hist_version.argtypes = []
        lib.rtm_hist_stages.restype = ctypes.c_int32
        lib.rtm_hist_stages.argtypes = []
        lib.rtm_hist_buckets.restype = ctypes.c_int32
        lib.rtm_hist_buckets.argtypes = []
        lib.rtm_hist_sub_bits.restype = ctypes.c_int32
        lib.rtm_hist_sub_bits.argtypes = []
        lib.rtm_hist_min_exp.restype = ctypes.c_int32
        lib.rtm_hist_min_exp.argtypes = []
        lib.rtm_hist.restype = ctypes.c_void_p
        lib.rtm_hist.argtypes = [p]
        lib.rtm_flight_version.restype = ctypes.c_int32
        lib.rtm_flight_version.argtypes = []
        lib.rtm_flight_cap.restype = ctypes.c_int32
        lib.rtm_flight_cap.argtypes = []
        lib.rtm_flight_record_size.restype = ctypes.c_int32
        lib.rtm_flight_record_size.argtypes = []
        lib.rtm_flight.restype = ctypes.c_void_p
        lib.rtm_flight.argtypes = [p]
        lib.rtm_flight_head.restype = ctypes.c_uint64
        lib.rtm_flight_head.argtypes = [p]
        # thread-per-shard-group workers: geometry + per-worker blocks
        lib.rtm_workers.restype = ctypes.c_int32
        lib.rtm_workers.argtypes = [p]
        lib.rtm_group_chunk.restype = ctypes.c_int64
        lib.rtm_group_chunk.argtypes = [p]
        lib.rtm_frame_group_mask.restype = ctypes.c_uint64
        lib.rtm_frame_group_mask.argtypes = [p, p, ctypes.c_uint32]
        lib.rtm_counters_w.restype = ctypes.c_void_p
        lib.rtm_counters_w.argtypes = [p, ctypes.c_int32]
        lib.rtm_stages_w.restype = ctypes.c_void_p
        lib.rtm_stages_w.argtypes = [p, ctypes.c_int32]
        lib.rtm_hist_w.restype = ctypes.c_void_p
        lib.rtm_hist_w.argtypes = [p, ctypes.c_int32]
        lib.rtm_flight_w.restype = ctypes.c_void_p
        lib.rtm_flight_w.argtypes = [p, ctypes.c_int32]
        lib.rtm_flight_head_w.restype = ctypes.c_uint64
        lib.rtm_flight_head_w.argtypes = [p, ctypes.c_int32]
        _RTM_CACHED = lib
        return lib


# ---------------------------------------------------------------------------
# static-analysis plane: sanitizer toolchains + the native stress suite
# (docs/STATIC_ANALYSIS.md; scripts/sanitize_gate.py is the driver)
# ---------------------------------------------------------------------------

STRESS_DIR = _HERE / "stress"
_STRESS_BUILD = STRESS_DIR / "_build"

# The gcc-10 libtsan on this container does not intercept
# pthread_cond_clockwait (libstdc++'s timed condvar path on glibc >= 2.30),
# so the unlock/relock inside a wait is invisible to TSan — the root cause
# of the retired probe-SKIP's false "double lock of a mutex". The shim
# routes clockwait to the intercepted pthread_cond_timedwait; linking it
# into every TSan stress binary makes gcc a VIABLE TSan toolchain (the
# kernels themselves wait via rabia::CondVar, which never emits
# clockwait — the shim covers libstdc++ internals and test scaffolding).
_TSAN_COMPAT = STRESS_DIR / "tsan_compat.cpp"

SAN_FLAGS: dict[str, list[str]] = {
    "tsan": ["-fsanitize=thread", "-O1", "-g"],
    "asan": [
        "-fsanitize=address", "-fno-omit-frame-pointer", "-O1", "-g",
    ],
    "ubsan": [
        "-fsanitize=undefined", "-fno-sanitize-recover=undefined",
        "-O1", "-g",
    ],
}


def stress_env(flavor: str) -> dict[str, str]:
    """Runtime env for a `flavor` stress binary: halt_on_error so any
    finding is a nonzero exit (an enforced gate, not a log line), plus
    the vetted suppression file for TSan (each entry justified inline)."""
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin")}
    if flavor == "tsan":
        env["TSAN_OPTIONS"] = (
            f"halt_on_error=1:suppressions={STRESS_DIR / 'tsan.supp'}"
        )
    elif flavor == "asan":
        env["ASAN_OPTIONS"] = "halt_on_error=1:detect_leaks=1"
        env["LSAN_OPTIONS"] = f"suppressions={STRESS_DIR / 'lsan.supp'}"
    elif flavor == "ubsan":
        env["UBSAN_OPTIONS"] = "halt_on_error=1:print_stacktrace=1"
    return env


# name -> kernel sources linked into stress/stress_<name>.cpp. Each
# program hammers one cross-thread seam the thread-per-shard-group
# runtime (ROADMAP item 1) will multiply.
STRESS_PROGRAMS: dict[str, dict] = {
    "transport": {"srcs": ["transport.cpp"], "libs": []},
    "wal": {"srcs": ["walkernel.cpp"], "libs": ["-lz"]},
    "session": {"srcs": ["sessionkernel.cpp"], "libs": []},
    "statekernel": {"srcs": ["statekernel.cpp"], "libs": []},
    "runtime": {"srcs": ["runtime.cpp", "transport.cpp"], "libs": ["-lz"]},
    # thread-per-shard-group seams: 2 workers vs per-group inbox
    # routing, per-lane statekernel applies, shared WAL staging lanes,
    # cross-worker result staging and the multi-worker pause barrier
    "runtime_mt": {
        "srcs": [
            "runtime.cpp", "transport.cpp", "statekernel.cpp",
            "walkernel.cpp",
        ],
        "libs": ["-lz"],
    },
}

# deliberately-broken probes: the test suite builds these and asserts the
# gate EXITS NONZERO — proof the matrix is red-on-failure, not
# green-by-silence
SELFCHECK_PROGRAMS: dict[str, str] = {
    "tsan": "selfcheck_race",
    "asan": "selfcheck_uaf",
}

_PROBE_CLEAN = r"""
// race-free by construction: mutex churn + TIMED condvar waits (the
// exact primitives the kernels use; a toolchain that flags this is not
// viable and the gate skips with this program's own output)
#include <chrono>
#include <condition_variable>
#include <cstdio>
#include <mutex>
#include <thread>
#include <vector>
int main() {
  std::mutex mu;
  std::condition_variable cv;
  bool done = false;
  long shared = 0;
  std::vector<std::thread> ts;
  for (int t = 0; t < 3; t++) {
    ts.emplace_back([&] {
      for (int i = 0; i < 20000; i++) {
        std::lock_guard<std::mutex> lk(mu);
        shared++;
        if ((shared & 1023) == 0) cv.notify_all();
      }
    });
  }
  {
    std::unique_lock<std::mutex> lk(mu);
    while (!cv.wait_for(lk, std::chrono::milliseconds(2),
                        [&] { return shared >= 60000; })) {
    }
    done = true;
  }
  for (auto& t : ts) t.join();
  std::printf("probe ok %ld %d\n", shared, (int)done);
  return shared == 60000 ? 0 : 3;
}
"""

_PROBE_BROKEN = {
    # a real data race: the sanitizer must catch it or it cannot be
    # trusted to gate anything
    "tsan": r"""
#include <cstdio>
#include <thread>
long shared = 0;
int main() {
  std::thread a([] { for (int i = 0; i < 200000; i++) shared++; });
  std::thread b([] { for (int i = 0; i < 200000; i++) shared++; });
  a.join();
  b.join();
  std::printf("done %ld\n", shared);
  return 0;
}
""",
    "asan": r"""
#include <cstdio>
#include <cstdlib>
int main() {
  volatile int* p = (volatile int*)malloc(32);
  p[0] = 7;
  free((void*)p);
  std::printf("uaf %d\n", p[0]);  // heap-use-after-free
  return 0;
}
""",
    "ubsan": r"""
#include <cstdio>
int main(int argc, char**) {
  volatile int s = 40 + argc;
  volatile int v = 1 << s;  // shift exponent out of range
  std::printf("ub %d\n", v);
  return 0;
}
""",
}

_TOOLCHAIN_CACHE: dict[str, dict | None] = {}


def _compiler_candidates() -> list[str]:
    import shutil as _sh

    out = []
    for name in (
        "clang++", "clang++-20", "clang++-19", "clang++-18", "clang++-17",
        "clang++-16", "clang++-15", "clang++-14", "g++",
    ):
        if _sh.which(name):
            out.append(name)
    return out


def find_sanitizer_toolchain(flavor: str) -> dict | None:
    """Find a compiler whose `flavor` sanitizer is VIABLE here.

    Viable means BOTH halves hold, probed with real binaries:
      - the clean probe (mutex + timed-condvar churn) runs clean three
        times — a toolchain that false-positives on it (gcc-10 libtsan
        without the clockwait shim) would make every stress verdict
        noise;
      - the broken probe (a planted race / use-after-free / UB shift)
        exits NONZERO — a sanitizer that cannot catch the planted bug
        cannot be trusted to gate the real ones.

    clang is preferred; gcc's TSan qualifies via the clockwait shim.
    Returns {"cxx", "flags", "extra_sources", "reason"} or None (the
    last probe failure lands in find_sanitizer_toolchain.reason for the
    one-line SKIP)."""
    import subprocess as sp
    import tempfile

    if flavor in _TOOLCHAIN_CACHE:
        return _TOOLCHAIN_CACHE[flavor]
    reasons = []
    result = None
    for cxx in _compiler_candidates():
        extra = []
        if flavor == "tsan":
            extra = [str(_TSAN_COMPAT)]
        with tempfile.TemporaryDirectory() as td:
            probe = Path(td) / "probe.cpp"
            probe.write_text(_PROBE_CLEAN)
            exe = Path(td) / "probe"
            cmd = [
                cxx, "-std=c++17", *SAN_FLAGS[flavor], "-pthread",
                str(probe), *extra, "-o", str(exe),
            ]
            rc = sp.run(cmd, capture_output=True, text=True, timeout=180)
            if rc.returncode != 0:
                reasons.append(f"{cxx}: probe build failed")
                continue
            env = stress_env(flavor)
            ok = True
            for _ in range(3):
                run = sp.run(
                    [str(exe)], capture_output=True, text=True,
                    timeout=120, env=env,
                )
                if run.returncode != 0 or "probe ok" not in run.stdout:
                    reasons.append(
                        f"{cxx}: clean probe flagged "
                        f"(rc={run.returncode}): "
                        + (run.stderr or run.stdout)[-300:].replace(
                            "\n", " | "
                        )
                    )
                    ok = False
                    break
            if not ok:
                continue
            broken = Path(td) / "broken.cpp"
            broken.write_text(_PROBE_BROKEN[flavor])
            bexe = Path(td) / "broken"
            rc = sp.run(
                [
                    cxx, "-std=c++17", *SAN_FLAGS[flavor], "-pthread",
                    str(broken), *extra, "-o", str(bexe),
                ],
                capture_output=True, text=True, timeout=180,
            )
            if rc.returncode != 0:
                reasons.append(f"{cxx}: broken probe build failed")
                continue
            caught = False
            for _ in range(5):
                run = sp.run(
                    [str(bexe)], capture_output=True, text=True,
                    timeout=120, env=env,
                )
                if run.returncode != 0:
                    caught = True
                    break
            if not caught:
                reasons.append(f"{cxx}: planted bug not detected")
                continue
            result = {
                "cxx": cxx,
                "flags": list(SAN_FLAGS[flavor]),
                "extra_sources": [str(p) for p in extra],
                "reason": "",
            }
            break
    if result is None:
        find_sanitizer_toolchain.reason = (  # type: ignore[attr-defined]
            "; ".join(reasons) or "no C++ compiler found"
        )
    _TOOLCHAIN_CACHE[flavor] = result
    return result


def build_stress(name: str, flavor: str) -> Path:
    """Build stress/stress_<name>.cpp + its kernel sources under
    `flavor`; returns the binary path (digest-cached like the .so
    builds). Raises InternalError on build failure — a kernel edit that
    breaks the sanitizer build must FAIL the gate, never skip it."""
    import subprocess as sp

    spec = STRESS_PROGRAMS[name]
    tc = find_sanitizer_toolchain(flavor)
    if tc is None:
        raise InternalError(
            f"no viable {flavor} toolchain: "
            + getattr(find_sanitizer_toolchain, "reason", "")
        )
    main_src = STRESS_DIR / f"stress_{name}.cpp"
    # every header an included source can pull in participates in the
    # digest — a header-only ABI edit must never reuse a stale cached
    # stress binary (the silent-stale-artifact class this gate exists
    # to kill)
    srcs = [
        main_src, STRESS_DIR / "stress_common.h", _ANNOT,
        _HERE / "transport.h",
    ]
    srcs += [_HERE / s for s in spec["srcs"]]
    h = hashlib.blake2s(digest_size=8)
    for s in srcs:
        h.update(s.read_bytes())
    for p in tc["extra_sources"]:
        h.update(Path(p).read_bytes())
    h.update((tc["cxx"] + flavor).encode())
    _STRESS_BUILD.mkdir(parents=True, exist_ok=True)
    out = _STRESS_BUILD / f"{name}-{flavor}-{h.hexdigest()}"
    if out.exists():
        return out
    # compile to a private temp path, then atomically rename (the
    # _compile pattern): a build killed mid-link must never leave a
    # truncated binary at the digest-keyed path, which the exists()
    # fast path would trust forever
    tmp = out.with_suffix(f".tmp{os.getpid()}")
    cmd = [
        tc["cxx"], "-std=c++17", *tc["flags"], "-pthread",
        f"-I{_HERE}",
        str(main_src),
        *[str(_HERE / s) for s in spec["srcs"]],
        *tc["extra_sources"],
        "-o", str(tmp),
        *spec["libs"],
    ]
    proc = sp.run(cmd, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        tmp.unlink(missing_ok=True)
        raise InternalError(
            f"{flavor} build of stress_{name} failed:\n"
            + proc.stderr[-2000:]
        )
    os.replace(tmp, out)
    for old in _STRESS_BUILD.glob(f"{name}-{flavor}-*"):
        if old != out:
            try:
                old.unlink()
            except OSError:
                pass
    return out


def build_selfcheck(flavor: str) -> Path:
    """Build the deliberately-broken probe for `flavor` (the gate's
    red-on-failure proof)."""
    import subprocess as sp

    tc = find_sanitizer_toolchain(flavor)
    if tc is None:
        raise InternalError(f"no viable {flavor} toolchain")
    _STRESS_BUILD.mkdir(parents=True, exist_ok=True)
    src = _STRESS_BUILD / f"selfcheck_{flavor}.cpp"
    src.write_text(_PROBE_BROKEN[flavor])
    out = _STRESS_BUILD / f"selfcheck_{flavor}"
    cmd = [
        tc["cxx"], "-std=c++17", *tc["flags"], "-pthread", str(src),
        *tc["extra_sources"], "-o", str(out),
    ]
    proc = sp.run(cmd, capture_output=True, text=True, timeout=180)
    if proc.returncode != 0:
        raise InternalError(
            f"selfcheck build failed:\n{proc.stderr[-1000:]}"
        )
    return out

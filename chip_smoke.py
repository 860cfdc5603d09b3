#!/usr/bin/env python3
"""On-chip smoke: the device-plane SMR path, once, at real size, on a TPU.

    python chip_smoke.py [--seed N]

What it drives (the entry points a user calls, nothing private on the
hot path):

1. **Engine leg** — ``MeshEngine(device_store=True)`` at BASELINE config
   3's geometry (4096 shards x 5 replicas, ``window=64``,
   ``VectorShardedKV`` replicas, the default 64-slot ``DeviceKVTable``):
   the table is loaded to capacity with 262,144 distinct records drawn
   from ``--seed``, then overwrite-SET windows, GET windows that read
   every key back, mixed SET/GET/DEL/EXISTS windows, and (second engine,
   ``device_read_lane=True``) consensus-free read windows. Every
   response is compared, value for value and version for version, with
   ``rabia_tpu.apps.kvstore.KVStore`` fed the same op sequence; after
   ``sync_to_host()`` all replica stores must equal it. The lane must
   never leave the device: ``device_lane_active`` after every phase,
   ``divergences == 0``, and host replicas still EMPTY before the sync.
2. **Kernel leg** — scanned ``slot_pipeline``, the Pallas replica-major
   window, the XLA closed form and the packed window on random votes
   over all four codes and a random crash mask at S=4096, R=5: bit for
   bit equal at bench.py's depths (T=32768 i8, T=393216 packed) and at
   a ragged T, compiled (never ``interpret=True``).
3. **With >= 4 devices** — the engine leg runs over the shard-axis mesh
   of all of them under a device-to-device transfer guard (a window
   operand landed on device 0 and re-sharded per dispatch trips it),
   the table's placement is checked, and ``__graft_entry__``'s
   replica-axis modes run on the real chips.

There is no CPU fallback: the script exits non-zero before doing any
work unless ``jax.devices()[0].platform == "tpu"``, and any phase's
failure is fatal (nothing is caught and continued past). One process
holds the chip. Lines starting ``obs:`` are observations (compile
seconds, window wall time, dispatch/readback latency), not results; the
last stdout line is the one JSON result object.

``tests/test_chip_smoke.py`` runs the same leg functions at a tiny size
on the CPU (Pallas in interpret mode).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import statistics
import sys
import time

import numpy as np

# BASELINE config 3 geometry + the default DeviceKVTable envelope
N_SHARDS = 4096
N_REPLICAS = 5
WINDOW = 64
SLOTS_PER_SHARD = 64  # DeviceKVTable per_shard_capacity default
KEY_BYTES = 32  # key_lanes * 8
VALUE_BYTES = 64  # value_width

_ALNUM = np.frombuffer(
    b"0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ", np.uint8
)


class SmokeFailure(AssertionError):
    """A leg's check failed (raised, never caught: any failure is fatal)."""


def _require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring): programs, seconds, cache hits
# ---------------------------------------------------------------------------


class CompileLog:
    """Counts backend compiles and persistent-cache hits/misses for the
    whole process. Registered once (jax.monitoring has no unregister)."""

    def __init__(self) -> None:
        from jax import monitoring

        self.programs: dict[str, list] = {}  # fun_name -> [count, seconds]
        self.hits = 0
        self.misses = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            rec = self.programs.setdefault(kw.get("fun_name", "?"), [0, 0.0])
            rec[0] += 1
            rec[1] += secs

    @property
    def n_programs(self) -> int:
        return sum(c for c, _ in self.programs.values())

    @property
    def seconds(self) -> float:
        return sum(s for _, s in self.programs.values())

    def report(self, top: int = 12) -> None:
        _log(
            f"obs: compile: {self.n_programs} programs, "
            f"{self.seconds:.2f}s backend compile (or cache load); "
            f"persistent cache hits={self.hits} misses={self.misses}"
        )
        ranked = sorted(self.programs.items(), key=lambda kv: -kv[1][1])
        for name, (count, secs) in ranked[:top]:
            _log(f"obs: compile:   {secs:8.2f}s  x{count:<3d} {name}")


# ---------------------------------------------------------------------------
# the workload: distinct keys per shard, values from the seed
# ---------------------------------------------------------------------------


class Workload:
    """``n_shards x slots`` distinct keys (<= KEY_BYTES, a share at the
    full width) and seed-drawn values (1..VALUE_BYTES, a share at the
    full width). One op per shard per wave, the device lane's shape."""

    def __init__(self, seed: int, n_shards: int, slots: int) -> None:
        self.rng = np.random.default_rng(seed)
        self.n = n_shards
        self.slots = slots
        filler = self._text(n_shards * slots, KEY_BYTES)
        klen = self.rng.integers(1, KEY_BYTES + 1, n_shards * slots)
        klen[self.rng.random(n_shards * slots) < 0.125] = KEY_BYTES
        self.keys: list[list[str]] = []
        i = 0
        for s in range(n_shards):
            row = []
            for j in range(slots):
                stem = f"{s:x}.{j:x}."
                width = max(int(klen[i]), len(stem))
                row.append((stem + filler[i])[:width])
                i += 1
            self.keys.append(row)
        # one key per shard that is never written (not-found reads)
        self.ghost = [f"{s:x}.ghost" for s in range(n_shards)]

    def _text(self, n: int, width: int) -> list[str]:
        raw = _ALNUM[self.rng.integers(0, len(_ALNUM), (n, width))]
        return [row.tobytes().decode("ascii") for row in raw]

    def values(self, n: int) -> list[str]:
        vlen = self.rng.integers(1, VALUE_BYTES + 1, n)
        vlen[self.rng.random(n) < 0.125] = VALUE_BYTES
        return [t[:w] for t, w in zip(self._text(n, VALUE_BYTES), vlen)]

    # each wave is a list of (opcode, key, value-or-None), one per shard

    def load_waves(self) -> list[list[tuple]]:
        """``slots`` waves: wave t SETs key t of every shard — the table
        ends at capacity with distinct keys."""
        waves = []
        for t in range(self.slots):
            vals = self.values(self.n)
            waves.append(
                [("set", self.keys[s][t], vals[s]) for s in range(self.n)]
            )
        return waves

    def overwrite_waves(self, n_waves: int) -> list[list[tuple]]:
        """SETs of seed-drawn existing keys with fresh values."""
        waves = []
        for _ in range(n_waves):
            pick = self.rng.integers(0, self.slots, self.n)
            vals = self.values(self.n)
            waves.append(
                [
                    ("set", self.keys[s][int(pick[s])], vals[s])
                    for s in range(self.n)
                ]
            )
        return waves

    def get_waves(self) -> list[list[tuple]]:
        """``slots`` waves reading every key of every shard back (wave
        order rotated per shard so a wave is not one table column)."""
        return [
            [
                ("get", self.keys[s][(t + s) % self.slots], None)
                for s in range(self.n)
            ]
            for t in range(self.slots)
        ]

    def mixed_waves(self, n_waves: int) -> list[list[tuple]]:
        """Per-op draw over SET/GET/DEL/EXISTS. Writes stay inside each
        shard's own key universe (so the table never exceeds capacity);
        reads also hit the never-written ghost key."""
        waves = []
        for _ in range(n_waves):
            kind = self.rng.choice(
                ["set", "get", "del", "exists"], self.n,
                p=[0.4, 0.3, 0.15, 0.15],
            )
            pick = self.rng.integers(0, self.slots, self.n)
            ghost = self.rng.random(self.n) < 0.1
            vals = self.values(self.n)
            wave = []
            for s in range(self.n):
                k = str(kind[s])
                if k == "set":
                    wave.append((k, self.keys[s][int(pick[s])], vals[s]))
                elif ghost[s] and k != "del":
                    wave.append((k, self.ghost[s], None))
                else:
                    wave.append((k, self.keys[s][int(pick[s])], None))
            waves.append(wave)
        return waves


def _encode_wave(wave: list[tuple]) -> list[list[bytes]]:
    from rabia_tpu.apps.kvstore import (
        KVOperation,
        encode_op_bin,
        encode_set_bin,
    )

    ctor = {
        "get": KVOperation.get,
        "del": KVOperation.delete,
        "exists": KVOperation.exists,
    }
    return [
        [encode_set_bin(key, val)]
        if kind == "set"
        else [encode_op_bin(ctor[kind](key))]
        for kind, key, val in wave
    ]


def _check_wave(phase: str, t: int, wave: list[tuple], frames: list,
                ref: list, digest) -> None:
    """Apply one wave to the reference stores and hold every device
    response to it: SET -> version, GET -> found/value/version, DEL ->
    found, EXISTS -> boolean."""
    from rabia_tpu.apps.kvstore import KVResultKind, decode_result_bin

    for s, (kind, key, val) in enumerate(wave):
        frame = bytes(frames[s][0])
        digest.update(frame)
        got = decode_result_bin(frame)
        store = ref[s]
        if kind == "set":
            want = store.set(key, val)
            ok = got.ok and got.version == want.version
        elif kind == "get":
            want = store.get(key)
            ok = (
                got.kind == want.kind
                and got.value == want.value
                and (got.version or 0) == (want.version or 0)
            )
        elif kind == "del":
            want = store.delete(key)
            ok = got.ok == want.ok and (
                got.ok or got.kind == KVResultKind.NotFound
            )
        else:
            want = store.exists(key)
            ok = got.ok and got.value == want.value
        if not ok:
            raise SmokeFailure(
                f"{phase}: wave {t} shard {s} {kind} {key!r}: device "
                f"answered {got}, reference {want}"
            )


def _require_in_lane(eng, phase: str) -> None:
    """The lane never left the device: the flag, the divergence counter,
    and the fact that in device mode the host replicas see no applies."""
    _require(eng.device_lane_active, f"{phase}: device lane demoted")
    _require(eng.divergences == 0, f"{phase}: {eng.divergences} divergences")
    _require(
        all(len(sm.store) == 0 for sm in eng.sms),
        f"{phase}: a host replica store saw applies (the lane demoted "
        "and re-promoted)",
    )


def _run_phase(eng, phase: str, waves: list[list[tuple]], ref: list,
               digest, compiles: CompileLog | None) -> dict:
    """Submit ``waves`` as full-width blocks, flush, check every
    response against the reference, and prove the lane stayed put."""
    from rabia_tpu.core.blocks import build_block

    shards = list(range(eng.n_shards))
    blocks = [build_block(shards, _encode_wave(w)) for w in waves]
    compiled_before = compiles.n_programs if compiles else 0
    t0 = time.perf_counter()
    futs = [eng.submit_block(b) for b in blocks]
    applied = eng.flush()
    wall = time.perf_counter() - t0
    _require(all(f.done() for f in futs), f"{phase}: unsettled futures")
    _require(
        applied == len(waves) * eng.n_shards,
        f"{phase}: applied {applied} of {len(waves) * eng.n_shards}",
    )
    _require_in_lane(eng, phase)
    for t, (wave, fut) in enumerate(zip(waves, futs)):
        _check_wave(phase, t, wave, fut.result(), ref, digest)
    windows = -(-len(waves) // eng.window)
    compiled = (compiles.n_programs - compiled_before) if compiles else None
    _log(
        f"obs: {phase}: {len(waves)} waves x {eng.n_shards} shards in "
        f"{windows} window(s), {wall:.3f}s wall "
        f"({wall / windows * 1e3:.1f} ms/window, programs compiled in "
        f"phase: {compiled}); responses match the reference"
    )
    return {"phase": phase, "windows": windows, "wall_s": wall,
            "compiled": compiled}


def _require_replicas_equal(eng, ref: list) -> None:
    """After sync_to_host: every replica store holds exactly the
    reference's key -> (value, version) map and shard versions (row
    counts equal, so nothing beyond it), hence one another's."""
    shards, keys, want_vals, want_vers = [], [], [], []
    for s, rstore in enumerate(ref):
        for key in rstore.keys():
            e = rstore.get(key)
            shards.append(s)
            keys.append(key.encode())
            want_vals.append(e.value.encode())
            want_vers.append(e.version)
    shards = np.asarray(shards, np.int64)
    want_vers = np.asarray(want_vers, np.int64)
    want_sver = np.asarray([rstore.version for rstore in ref], np.int64)
    for r, sm in enumerate(eng.sms):
        store = sm.store
        _require(
            len(store) == len(keys),
            f"replica {r}: {len(store)} rows, reference {len(keys)}",
        )
        _require(
            np.array_equal(store.shard_version[: len(ref)], want_sver),
            f"replica {r}: shard versions differ from the reference",
        )
        vers, vals = store.bulk_get(shards, *store._lanes_from_keys(keys))
        bad = np.nonzero(vers != want_vers)[0]
        if bad.size:
            i = int(bad[0])
            raise SmokeFailure(
                f"replica {r}: {bad.size} keys missing or at another "
                f"version, first: shard {shards[i]} key {keys[i]!r}"
            )
        _require(vals == want_vals, f"replica {r}: value bytes differ")


def _require_shard_axis_placement(eng) -> dict:
    """Each table array has S/devices rows on each of the mesh's
    distinct devices, and the per-window operand placement does too."""
    dev = eng._dev
    n_dev = eng.mesh.shape["shard"]
    rows = dev.S // n_dev
    for i, a in enumerate(dev.state):
        shards = a.addressable_shards
        _require(
            len({sh.device for sh in shards}) == n_dev
            and all(sh.data.shape[0] == rows for sh in shards),
            f"table array {i} {a.shape}: not {rows} rows on each of "
            f"{n_dev} devices ({a.sharding})",
        )
    probe = dev._put_waves(np.zeros((eng.window, dev.S), np.int16))
    _require(
        len({sh.device for sh in probe.addressable_shards}) == n_dev
        and all(
            sh.data.shape == (eng.window, rows)
            for sh in probe.addressable_shards
        ),
        f"window operand not split over the shard axis ({probe.sharding})",
    )
    return {"devices": n_dev, "rows_per_device": rows}


def engine_leg(seed: int, *, n_shards: int = N_SHARDS,
               n_replicas: int = N_REPLICAS, window: int = WINDOW,
               slots: int = SLOTS_PER_SHARD, mesh=None,
               compiles: CompileLog | None = None) -> dict:
    """The main path once: load to capacity, overwrite, read back,
    mixed, read lane, final sync — every response and the final state
    held to the KVStore reference. Returns observations + a digest of
    every response frame (equal across device counts for one seed)."""
    import contextlib

    import jax

    from rabia_tpu.apps.kvstore import KVStore
    from rabia_tpu.apps.vector_kv import VectorShardedKV
    from rabia_tpu.native import build as native_build
    from rabia_tpu.parallel import MeshEngine, make_mesh

    # a machine without a working g++ must fail here, not silently take
    # the numpy pack path
    _require(
        native_build.load_hostkernel() is not None,
        "native host kernel (rk_pack_gather) failed to build/load",
    )
    _require(
        native_build.load_codec() is not None,
        "native codec failed to build/load",
    )

    mesh = mesh if mesh is not None else make_mesh()
    n_dev = mesh.shape["shard"] * mesh.shape["replica"]
    # more than one device: an operand that lands on device 0 and is
    # re-sharded by the dispatch is an implicit device-to-device copy
    guard = (
        jax.transfer_guard_device_to_device("disallow")
        if n_dev > 1
        else contextlib.nullcontext()
    )

    def make_engine(**extra):
        # the host replicas stay empty until the final sync (which
        # rebuilds them at the table's size), so they start small
        return MeshEngine(
            lambda: VectorShardedKV(n_shards, capacity=1 << 12),
            n_shards=n_shards,
            n_replicas=n_replicas,
            mesh=mesh,
            window=window,
            device_store=True,
            device_store_kw={"per_shard_capacity": slots},
            **extra,
        )

    digest = hashlib.sha256()
    phases = []
    out: dict = {"devices": n_dev}
    with guard:
        wl = Workload(seed, n_shards, slots)
        ref = [KVStore() for _ in range(n_shards)]
        eng = make_engine()
        if n_dev > 1:
            out["placement"] = _require_shard_axis_placement(eng)

        def run(name, waves):
            phases.append(_run_phase(eng, name, waves, ref, digest, compiles))

        run("load", wl.load_waves())
        run("overwrite", wl.overwrite_waves(2 * window))
        run("get", wl.get_waves())
        run("get-warm", wl.get_waves())
        run("mixed", wl.mixed_waves(window))
        run("mixed-warm", wl.mixed_waves(window))
        # by now the retained value segments have outgrown their cap, so
        # this read-back pays the value-plane download instead of
        # resolving host-side
        run("get-evicted", wl.get_waves())
        out["value_plane_fallback_ops"] = eng.read_lane_stats()["fallback"]
        _log(
            "obs: get-evicted: ops answered through the value-plane "
            f"download: {out['value_plane_fallback_ops']}"
        )
        stats = eng.governor_stats()
        out["settle_p99_ms"] = stats["settle_p99_ms"]
        out["inflight"] = stats["inflight"]
        if n_dev > 1:
            _require_shard_axis_placement(eng)
        t0 = time.perf_counter()
        eng.sync_to_host()
        out["sync_to_host_s"] = time.perf_counter() - t0
        _require(eng.divergences == 0, "sync: divergences")
        _require_replicas_equal(eng, ref)
        eng.close()
        _log(
            f"obs: sync_to_host: {out['sync_to_host_s']:.2f}s; all "
            f"{n_replicas} replica stores equal the reference "
            f"({sum(len(s) for s in ref)} records)"
        )

        # read lane: a second engine (the lane is a constructor choice),
        # its own table loaded the same way, then consensus-free reads
        wl = Workload(seed + 1, n_shards, slots)
        ref = [KVStore() for _ in range(n_shards)]
        eng = make_engine(device_read_lane=True)
        run("read-lane-load", wl.load_waves())
        run("read-lane", wl.get_waves())
        run("read-lane-warm", wl.get_waves())
        rs = eng.read_lane_stats()
        _require(
            rs["probe"] == 2 * slots * n_shards and rs["slot"] == 0,
            f"read lane: GETs rode consensus slots ({rs})",
        )
        eng.close()
    out["phases"] = phases
    out["digest"] = digest.hexdigest()
    _log(f"obs: engine leg response digest {out['digest']}")
    return out


# ---------------------------------------------------------------------------
# kernel leg
# ---------------------------------------------------------------------------


def kernel_leg(seed: int, *, S: int = N_SHARDS, R: int = N_REPLICAS,
               t_scan: int = 1024, t_i8: int = 32768,
               t_packed: int = 393216, t_ragged: int = 1000,
               interpret: bool = False) -> dict:
    """Scanned owner, Pallas replica-major, XLA closed form and packed
    window agree bit for bit on random votes (all four codes) and a
    random crash mask. ``interpret`` exists for the CPU test only."""
    import jax
    import jax.numpy as jnp

    from rabia_tpu.kernel import ClusterKernel, packed_window

    _require(t_scan <= t_i8 and t_packed % t_i8 == 0, "bad kernel-leg sizes")
    kernel = ClusterKernel(S, R, seed=seed)
    key = jax.random.key(seed)
    k_alive, k_i8, k_rag, k_pk = jax.random.split(key, 4)
    alive_rm = jax.random.bernoulli(k_alive, 0.8, (R, S))
    alive_p = packed_window.pack_alive(alive_rm)
    eq = lambda a, b: bool(jnp.array_equal(a, b))
    obs = {}

    def votes_i8(k, T):
        # all four codes, skewed so V1, V0 and undecided slots all occur
        # often: V1 45%, V0 35%, V? 10%, ABSENT 10%
        b = jax.random.bits(k, (R, T, S), jnp.uint8)
        return jnp.where(
            b < 115, 1, jnp.where(b < 205, 0, jnp.where(b < 230, 2, 3))
        ).astype(jnp.int8)

    def four_way(name: str, votes_rm, T: int, scan_T: int) -> None:
        t0 = time.perf_counter()
        pal_d, pal_p = kernel.slot_pipeline_fused_rmajor(
            votes_rm, alive_rm, T, use_pallas=True, interpret=interpret
        )
        xla_d, xla_p = kernel.slot_pipeline_fused_rmajor(
            votes_rm, alive_rm, T, use_pallas=False
        )
        pk_d = packed_window.unpack_codes(
            kernel.slot_pipeline_fused_packed(
                packed_window.pack_codes(votes_rm), alive_p, T
            ),
            S,
        )
        scan_d, scan_p = kernel.slot_pipeline(
            jnp.transpose(votes_rm[:, :scan_T], (1, 2, 0)),
            alive_rm.T,
            scan_T,
        )
        _require(eq(pal_d, xla_d), f"{name}: Pallas != XLA closed form")
        _require(eq(pal_p, xla_p), f"{name}: Pallas phase != XLA phase")
        _require(eq(pk_d, xla_d), f"{name}: packed != XLA closed form")
        _require(
            eq(scan_d, xla_d[:scan_T]) and eq(scan_p, xla_p[:scan_T]),
            f"{name}: scanned slot_pipeline != closed form",
        )
        decided = float(jnp.mean(xla_d != 3))
        _require(0.05 < decided < 0.95, f"{name}: degenerate votes")
        obs[name] = time.perf_counter() - t0
        _log(
            f"obs: kernel {name}: T={T} S={S} R={R}, scanned T={scan_T}: "
            f"scan == pallas == xla == packed ({decided:.1%} of slots "
            f"decide), {obs[name]:.2f}s incl. compile"
        )

    four_way("i8-depth", votes_i8(k_i8, t_i8), t_i8, t_scan)
    four_way("ragged", votes_i8(k_rag, t_ragged), t_ragged, t_ragged)

    # what a default caller gets: on TPU at S % 128 == 0 the selection
    # names the Pallas kernel, and the program it builds must carry it
    if not interpret:
        for T in (t_i8, t_ragged):
            jaxpr = jax.make_jaxpr(
                lambda v, a, T=T: kernel.slot_pipeline_fused_rmajor(v, a, T)
            )(jax.ShapeDtypeStruct((R, T, S), jnp.int8), alive_rm)
            _require(
                "pallas_call" in str(jaxpr),
                f"default selection at T={T} did not build the Pallas "
                "kernel it names",
            )

    # packed at bench.py's depth: one dispatch, checked chunk by chunk
    # against the XLA closed form (an i8 plane of that depth is 8 GB)
    t0 = time.perf_counter()
    packed = jax.random.bits(
        k_pk, (R, t_packed, packed_window.packed_width(S)), jnp.uint32
    )
    pk = kernel.slot_pipeline_fused_packed(packed, alive_p, t_packed)
    pk.block_until_ready()
    for at in range(0, t_packed, t_i8):
        chunk = packed_window.unpack_codes(packed[:, at : at + t_i8], S)
        want = kernel.slot_pipeline_fused_rmajor(
            chunk, alive_rm, t_i8, use_pallas=False, want_phase=False
        )
        _require(
            eq(pk[at : at + t_i8], packed_window.pack_codes(want)),
            f"packed depth: window rows {at}..{at + t_i8} != closed form",
        )
    obs["packed-depth"] = time.perf_counter() - t0
    _log(
        f"obs: kernel packed-depth: T={t_packed} one dispatch == XLA "
        f"closed form in {t_packed // t_i8} chunks, "
        f"{obs['packed-depth']:.2f}s incl. compile"
    )
    return obs


# ---------------------------------------------------------------------------
# link observations
# ---------------------------------------------------------------------------


def link_observations(window: int = WINDOW, n_shards: int = N_SHARDS) -> dict:
    """Dispatch, readback and upload latency of this host<->device link
    (observations; medians of 50 readings on the host clock)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros(3, jnp.int32)
    f(x).block_until_ready()
    disp, back = [], []
    for _ in range(50):
        t0 = time.perf_counter()
        y = f(x)
        y.block_until_ready()
        t1 = time.perf_counter()
        np.asarray(y)  # the 12-byte flags readback of a SET window
        t2 = time.perf_counter()
        disp.append(t1 - t0)
        back.append(t2 - t1)
    # one SET window's value plane at the full value width
    plane = np.zeros((window, n_shards, VALUE_BYTES // 4), np.uint32)
    up = []
    for _ in range(10):
        t0 = time.perf_counter()
        jax.device_put(plane).block_until_ready()
        up.append(time.perf_counter() - t0)
    out = {
        "dispatch_us": statistics.median(disp) * 1e6,
        "readback_12B_us": statistics.median(back) * 1e6,
        "upload_ms": statistics.median(up) * 1e3,
        "upload_MBps": plane.nbytes / statistics.median(up) / 1e6,
    }
    _log(
        f"obs: link: dispatch+sync {out['dispatch_us']:.0f} us, 12-byte "
        f"readback {out['readback_12B_us']:.0f} us, {plane.nbytes >> 20} "
        f"MiB upload {out['upload_ms']:.1f} ms "
        f"({out['upload_MBps']:.0f} MB/s) — medians"
    )
    return out


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for keys, values, votes and crash masks")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU (jax found platform={devs[0].platform!r}); "
            "this script has no CPU fallback — run it on the chip",
            file=sys.stderr,
        )
        return 2
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }

    from rabia_tpu.core.compile_cache import place_compile_cache

    t_start = time.perf_counter()
    cache_dir = place_compile_cache()
    compiles = CompileLog()
    import jaxlib

    _log(
        f"chip_smoke: platform={device['platform']} "
        f"device_kind={device['kind']} devices={device['count']} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"seed={args.seed} compile_cache={cache_dir}"
    )
    link_observations()
    engine_leg(args.seed, compiles=compiles)
    kernel_leg(args.seed)
    if len(devs) >= 4:
        import __graft_entry__

        __graft_entry__.dryrun_multichip(len(devs))
    compiles.report()
    _log(f"obs: total {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

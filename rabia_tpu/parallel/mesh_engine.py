"""MeshEngine: the full SMR stack on the device plane.

The deployment shape for a TPU pod slice (SURVEY.md §5.8 device plane):
consensus replicas live on a mesh axis and a round's vote exchange is a
collective, so deciding a window of slots is ONE device dispatch
(:meth:`MeshPhaseKernel.slot_window`) instead of the transport engine's
per-round message exchange (contrast the reference's broadcast-as-loop,
rabia-engine/src/network/tcp.rs:771-789). Around that core this module
adds everything the transport engine has and the bare kernel lacks:
payload binding, ordered state-machine apply on every replica, client
futures, per-shard decision logs, and crash-fault injection.

Colocated lockstep model
------------------------
All R replicas of the cluster run in ONE process over one mesh: payload
"dissemination" is shared host memory (on a real pod slice the block
payloads ride an all_gather over the same axis the votes use), and every
live replica votes V1 for a slot whose payload exists — disagreement
comes only from injected faults (crash masks). Consensus math is
bit-identical to the transport plane: same ``_coin_bits`` stream keyed by
(seed, shard, slot, phase), same quorum/f+1 thresholds, which is what the
engine-level conformance gate in ``tests/test_mesh_engine.py`` checks
against :class:`~rabia_tpu.engine.RabiaEngine`.

Slot semantics match the transport engine's: a slot decides V1 (batch
applies, future settles) or V0 (null slot — the batch retries in the next
window). An undecided slot (quorum of replicas crashed) parks the shard;
the whole window re-runs deterministically after heal.

Multi-host (DCN)
----------------
Pass a mesh spanning every process's devices (built after
``jax.distributed.initialize()``) and the SAME engine code runs as a
multi-controller SPMD program: consensus windows execute across hosts
(collectives ride ICI within a slice, DCN across), vote/alive inputs are
assembled per-process (`make_array_from_callback`), and the decided plane
is re-replicated to every host (`process_allgather`). The host side
follows the standard JAX multi-controller discipline: every process must
run the same submissions in the same order (each holds the full replica
SM set and applies identically). ``scripts/dcn_dryrun.py`` runs this
end-to-end across two OS processes.
"""

from __future__ import annotations

import logging
import time
from collections import deque
from dataclasses import replace
from typing import Callable, Optional, Sequence, Union

import numpy as np

from rabia_tpu.core.errors import RabiaError, ValidationError
from rabia_tpu.core.state_machine import StateMachine
from rabia_tpu.core.tracing import device_annotation, tracer
from rabia_tpu.core.types import (
    ABSENT,
    V0,
    V1,
    CommandBatch,
    ShardId,
    quorum_size,
)
from rabia_tpu.parallel.mesh import MeshPhaseKernel, make_mesh

__all__ = ["MeshBlockFuture", "MeshEngine", "MeshFuture"]

logger = logging.getLogger(__name__)


class _RowSeg:
    """Value segment for a pure-SET window packed as per-op rows:
    version v at shard s is wave ``t = v - start[s] - 1``.

    ``provisional`` marks a segment whose window is still in flight
    behind a data-dependent version bump (a DEL-bearing window earlier
    in the pipe): its ``start``/``end`` (and, for mixed segments,
    ``svers``) are placeholders until settlement patches them — such
    segments are never evicted (their exact version range is unknown)
    and never match a resolver range check (placeholder range is
    empty)."""

    __slots__ = ("start", "end", "vlen", "vwin8", "nbytes", "provisional")

    def __init__(self, start, end, vlen, vwin) -> None:
        self.start = start
        self.end = end
        self.vlen = vlen
        self.vwin8 = vwin.view(np.uint8)
        self.nbytes = vlen.nbytes + self.vwin8.nbytes
        self.provisional = False

    def value(self, s: int, ver: int) -> Optional[bytes]:
        t = ver - int(self.start[s]) - 1
        return self.vwin8[t, s, : int(self.vlen[t, s])].tobytes()


class _MixedSeg:
    """Value segment for a mixed window: per-(wave, shard) derived
    versions locate the SET wave by binary search (``svers`` columns
    are nondecreasing; the first wave reaching v is the SET that
    assigned it)."""

    __slots__ = (
        "start", "end", "vlen", "vwin8", "svers", "kind", "nbytes",
        "provisional",
    )

    def __init__(self, start, end, vlen, vwin, svers, kind) -> None:
        self.start = start
        self.end = end
        self.vlen = vlen
        self.vwin8 = vwin.view(np.uint8)
        self.svers = svers
        self.kind = kind
        self.nbytes = vlen.nbytes + self.vwin8.nbytes + svers.nbytes
        self.provisional = False

    def value(self, s: int, ver: int) -> Optional[bytes]:
        col = self.svers[:, s]
        t = int(np.searchsorted(col, ver))
        if t >= len(col) or col[t] != ver or self.kind[t, s] != 1:
            return None
        return self.vwin8[t, s, : int(self.vlen[t, s])].tobytes()


class _SegResolver:
    """Snapshot (shard, version) -> value-bytes resolver handed to
    settled GET views: pins exactly the segments and seed epoch live at
    settle time, so later engine-side evictions or re-promotions cannot
    invalidate an already-settled response — and the view holds no
    reference back to the engine (a client retaining results must not
    pin the whole engine)."""

    __slots__ = ("segs", "seed")

    def __init__(self, segs: tuple, seed: dict) -> None:
        self.segs = segs
        self.seed = seed

    def __call__(self, s: int, ver: int) -> bytes:
        v = self.seed.get((s, ver))
        if v is not None:
            return v
        for seg in reversed(self.segs):
            if not (seg.start[s] < ver <= seg.end[s]):
                continue
            v = seg.value(s, ver)
            if v is not None:
                return v
        raise KeyError((s, ver))


def _block_op_kind(block) -> Optional[int]:
    """The uniform opcode of a one-op-per-shard block (1=SET, 2=GET),
    or None when ops are mixed/absent — the device lanes dispatch by
    kind; the pack functions re-validate everything else."""
    if len(block.cmd_sizes) == 0 or not bool((block.counts == 1).all()):
        return None
    raw = np.frombuffer(block.data, np.uint8)
    off = block.cmd_offsets[:-1]
    if len(raw) == 0 or int(off.max(initial=0)) >= len(raw):
        return None
    codes = raw[off]
    first = int(codes[0])
    return first if bool((codes == first).all()) else None


_VALUE_FETCH_OUTCOMES = ("prefetched", "inline", "unused")
# Value planes smaller than this are never handed to a readback worker:
# the hand-over (a pool task, a thread woken, the interpreter lock passed
# back and forth) costs the window's thread more than downloading them
# itself does (262 KB a window: 0.19 ms inline; PERF.md §6, PR 33)
_PREFETCH_MIN_BYTES = 1 << 20


def _prefetch_values(planes: tuple) -> tuple:
    """A window's value planes on the host, as a readback worker fetches
    them: each whole and C-contiguous, so that a wave's row of one is a
    view and the settle copies nothing. Takes device handles, returns
    arrays and touches nothing of the engine. The span lies on the
    worker's thread and outside ``rabia.devkv.*``: no reader of the
    window's thread sees it."""
    from rabia_tpu.apps.device_kv import DeviceKVTable

    with device_annotation(
        "rabia.fetch.values", bytes=sum(int(p.nbytes) for p in planes)
    ):
        return tuple(map(DeviceKVTable._fetch, planes))


def _fetch_readback(name: str, *handles):
    """A window's flags or meta on the host, as a readback worker fetches
    them: the array of one handle, a tuple of several. ``name`` is the
    span (``rabia.fetch.flags`` / ``rabia.fetch.meta``), which lies on
    the worker's thread like ``rabia.fetch.values`` and on the same
    clock as the device's ops: a trace viewer shows each fetch against
    the window program that produced it and the span the window's
    thread was in when the worker woke."""
    with device_annotation(name, bytes=sum(int(h.nbytes) for h in handles)):
        got = tuple(map(np.asarray, handles))
    return got[0] if len(got) == 1 else got


class MeshFuture:
    """Synchronously settled result holder for one submitted batch.

    ``run_cycle`` settles futures inline (no event loop in the device
    plane's host driver); ``result()`` raises if called before the batch's
    slot decided.
    """

    __slots__ = ("_value", "_done")

    def __init__(self) -> None:
        self._value = None
        self._done = False

    def _settle(self, value) -> None:
        self._value = value
        self._done = True

    def done(self) -> bool:
        return self._done

    def result(self):
        if not self._done:
            raise RabiaError("batch not yet decided (run flush()/run_cycle())")
        if isinstance(self._value, Exception):
            raise self._value
        return self._value


class MeshBlockFuture:
    """Result holder for one submitted :class:`PayloadBlock`: one entry
    per covered shard (response list, or an Exception), like the
    transport engine's submit_block future."""

    __slots__ = ("_results", "_pending")

    def __init__(self, k: int) -> None:
        # the per-entry list is built by the first per-entry settle: a
        # block the bulk lane settles at once never needs one
        self._results: Optional[list] = None if k else []
        self._pending = k

    def _settle(self, i: int, value) -> None:
        if self._pending == 0:
            # already bulk-settled (results may be a lazy view); a settle
            # landing here is dropped — log it so a misrouted late settle
            # (e.g. a future error path re-settling an entry) is
            # observable rather than silently swallowed
            logger.debug(
                "ignoring post-bulk settle of entry %d (%r)", i, value
            )
            return
        if self._results is None:
            self._results = [None] * self._pending  # nothing settled yet
        if self._results[i] is None:
            self._pending -= 1
        self._results[i] = value

    def _settle_bulk(self, results) -> None:
        """Settle every entry at once (full-width fast lane). A lazy
        response view (e.g. vector_kv.FrameGroups) is stored AS the
        result — per-shard response lists materialize when the client
        reads them, not on the commit path."""
        self._results = list(results) if isinstance(results, list) else results
        self._pending = 0

    def done(self) -> bool:
        return self._pending == 0

    def result(self) -> list:
        if self._pending:
            raise RabiaError(
                f"{self._pending} block entries not yet decided "
                "(run flush()/run_cycle())"
            )
        return list(self._results)


class _Pending:
    """One queued consensus unit: a scalar batch OR one covered-shard
    slice of a submitted block (``block``/``bidx``/``bfut`` set)."""

    __slots__ = ("batch", "future", "block", "bidx", "bfut")

    def __init__(
        self,
        batch: Optional[CommandBatch],
        future: Optional[MeshFuture],
        block=None,
        bidx: int = -1,
        bfut: Optional[MeshBlockFuture] = None,
    ) -> None:
        self.batch = batch
        self.future = future
        self.block = block
        self.bidx = bidx
        self.bfut = bfut

    def materialize(self) -> CommandBatch:
        if self.batch is None:
            self.batch = self.block.materialize_batch(self.bidx)
        return self.batch

    def settle(self, value) -> None:
        if self.future is not None:
            self.future._settle(value)
        else:
            self.bfut._settle(self.bidx, value)


class MeshEngine:
    """R-replica SMR over a device mesh: consensus by collective.

    Parameters
    ----------
    sm_factory:
        Zero-arg callable producing one replica's state machine; called R
        times (each replica applies the committed log independently —
        replica-state equality IS the replication test).
    n_shards, n_replicas:
        Consensus geometry. Shards are padded up to the mesh's shard-axis
        size internally.
    mesh:
        A 2D (shard × replica) mesh from :func:`make_mesh`; default puts
        every local device on the shard axis (replicas vmapped — the
        single-host simulation mode; pass a replica-axis mesh on a pod).
    window:
        Slots decided per shard per device dispatch (the amortization
        lever — SURVEY.md §7.4.4).
    latency_target_ms:
        When set, a governor replaces the manual window knob: measured
        per-window wall time walks ``window`` along a power-of-two
        ladder within [min_window, max_window] to keep the p99 window
        latency under the target (see :meth:`run_cycle`).

    State machines implementing
    :class:`~rabia_tpu.core.state_machine.VectorStateMachine` get the
    bulk-apply path: each window position's decided batches are packed
    into ONE :class:`PayloadBlock` and applied per replica in one
    `apply_block` call (follower replicas skip response materialization).
    The per-batch replica-divergence check only runs on the scalar path —
    bulk followers return no responses to compare.
    """

    def __init__(
        self,
        sm_factory: Callable[[], StateMachine],
        n_shards: int,
        n_replicas: int,
        mesh=None,
        *,
        window: int = 16,
        max_phases: int = 4,
        coin_p1: float = 0.5,
        seed: int = 0,
        max_decision_history: int = 4096,
        device_store: bool = False,
        device_store_kw: Optional[dict] = None,
        device_store_repromote: int = 64,
        device_store_inflight: Optional[int] = None,
        device_read_lane: bool = False,
        latency_target_ms: Optional[float] = None,
        min_window: int = 1,
        max_window: int = 256,
    ) -> None:
        if n_shards < 1 or n_replicas < 1:
            raise ValidationError("need at least 1 shard and 1 replica")
        self.mesh = mesh if mesh is not None else make_mesh()
        axis = self.mesh.shape["shard"]
        self.n_shards = int(n_shards)
        self.S = ((self.n_shards + axis - 1) // axis) * axis  # padded
        self.R = int(n_replicas)
        self.window = int(window)
        self.max_phases = int(max_phases)
        self.kernel = MeshPhaseKernel(
            self.S, self.R, self.mesh, coin_p1=coin_p1, seed=seed
        )
        import jax

        self._multi = jax.process_count() > 1
        self.sms: list[StateMachine] = [sm_factory() for _ in range(self.R)]
        self._vector = all(
            callable(getattr(sm, "apply_block", None)) for sm in self.sms
        )
        self.queues: list[deque[_Pending]] = [
            deque() for _ in range(self.n_shards)
        ]
        self._queued_entries = 0  # total entries across self.queues
        # staged full-width blocks (the vectorized fast lane): only used
        # while NO per-shard entries are pending, else demoted in order
        self._full_blocks: deque = deque()
        # range-compressed decision log for full-width waves:
        # (start_slots i64[n], wave_offset, block, shard->bidx inv)
        self._bulk_log: deque = deque()
        # the block every deployment sends covers each shard once, in
        # order: submit_block recognises it by one compare against this
        # array, which is also the shared (read-only) inv of such a block
        self._shard_ids = np.arange(self.n_shards, dtype=np.int64)
        self._shard_ids.flags.writeable = False
        self._submit_blocks = {"identity": 0, "checked": 0}
        self.next_slot = np.zeros(self.n_shards, np.int64)
        self.alive = np.ones((self.S, self.R), bool)
        # per-shard decision log: slot -> (value, batch or None); bounded
        # (insertion order is slot order, so trimming drops the oldest)
        self.max_decision_history = int(max_decision_history)
        self.decisions: list[dict[int, tuple[int, Optional[CommandBatch]]]] = [
            {} for _ in range(self.n_shards)
        ]
        self.decided_v1 = 0
        self.decided_v0 = 0
        self.divergences = 0  # replicas disagreeing on an apply outcome
        self.cycles = 0
        # latency governor (see run_cycle/_govern): auto-tunes `window`
        # against a p99 wall-time target instead of the manual knob
        if latency_target_ms is not None and latency_target_ms <= 0:
            raise ValidationError("latency_target_ms must be positive")
        self.latency_target_ms = (
            float(latency_target_ms) if latency_target_ms is not None else None
        )
        self.min_window = max(1, int(min_window))
        self.max_window = max(self.min_window, int(max_window))
        if self.latency_target_ms is not None:
            # the governor walks W within [min_window, max_window]; the
            # starting size must already be on that ladder
            self.window = min(self.max_window, max(self.min_window, self.window))
        self.window_resizes = 0
        self._lat_samples: deque[float] = deque(maxlen=64)
        # dispatch->settle wall time of resolved device windows (ms):
        # the latency a CLIENT observes through the pipelined commit —
        # at pipe depth d a window settles ~d cycles after dispatch,
        # which per-cycle samples cannot see. Collected in device mode
        # regardless of governing; reported via governor_stats
        self._lat_settle: deque[float] = deque(maxlen=64)
        # observability (rabia_tpu/obs): the mesh plane's slice of the
        # commit-pipeline breakdown — window dispatch→settle histogram
        # plus pull gauges; same registry shape as RabiaEngine.metrics
        from rabia_tpu.obs import MetricsRegistry

        m = self.metrics = MetricsRegistry()
        m.attach_tracer(tracer)  # RABIA_TRACE=1: the rabia.* spans below
        self._h_window_settle = m.histogram(
            "commit_stage_seconds",
            "Device window dispatch→settle latency (the mesh plane's "
            "propose→apply span)",
            {"stage": "window_settle"},
        )
        m.gauge("mesh_window", "Current window size", fn=lambda: self.window)
        m.counter(
            "mesh_window_resizes_total", "Governor window resizes",
            fn=lambda: self.window_resizes,
        )
        # device windows dispatched, by the rung (static window size)
        # each ran at; the same event as the rabia.window.w<W> marker
        self._dev_windows = dict.fromkeys(self._ladder(), 0)
        for _w in self._dev_windows:
            m.counter(
                "devkv_windows_total",
                "Device windows dispatched by the window size (rung) they "
                "ran at (the rabia.window.w<W> markers)",
                {"w": str(_w)},
                fn=lambda w=_w: self._dev_windows[w],
            )
        m.counter(
            "engine_decided_total", "Slots decided (bulk device lane)",
            {"value": "v1"}, fn=lambda: self.decided_v1,
        )
        m.gauge(
            "mesh_device_lane_active",
            "1 while the device-resident KV lane is serving windows",
            fn=lambda: 1 if self._dev_active else 0,
        )
        self._lat_saturated = False
        # set by _govern when the target is below the measured floor at
        # min_window (no window size can meet it); see governor_stats()
        self.latency_target_unachievable = False
        self._lat_floor_ms: Optional[float] = None
        # anti-oscillation: last window size that overshot the target
        # (upsizing will not re-enter it until the ceiling ages out)
        self._lat_ceiling: Optional[int] = None
        self._lat_ceiling_age = 0
        # host time of cycles that only drained a device window since
        # the last dispatching cycle: part of that window's sample
        self._lat_drain_ms = 0.0
        # windows to leave untimed: on the host lanes the first cycle at
        # any window size pays that size's jit compile (seconds), which
        # must not read as latency or the governor ratchets W down one
        # compile at a time. The device lane's rungs are built together
        # (DeviceKVTable's ladder), so a resize there skips nothing
        self._lat_skip = 1
        # set by lane demotions DURING a timed cycle: that sample is void
        self._lat_invalidate = False
        self._lat_timing = False  # a governed cycle is being timed now
        # speculative next-window dispatch (full-width lane): (key, device
        # plane) issued before the current window's readback so device
        # compute overlaps the host apply; used only when the engine state
        # it assumed (depth, base slots, alive mask) still holds
        self._spec: Optional[tuple[tuple, object]] = None
        # device-resident KV lane (apps/device_kv.py): decide + apply
        # fused in one program per window, only responses come back to
        # the host. Active until any work outside its envelope arrives —
        # then the device table syncs down into the host replica stores
        # ONCE and the engine continues on the host path permanently.
        self._dev = None
        self._dev_active = False
        # device READ-INDEX lane (opt-in): full-width GET blocks skim
        # out of the consensus stream at submit time and batch into
        # consensus-free lookup_only probe windows (zero slots, zero
        # collectives) — see _dev_serve_reads. Off by default: probe
        # reads may legally observe writes dispatched AFTER them
        # (concurrent-invocation freedom), which the byte-identical
        # device-vs-host conformance gates cannot tolerate.
        self._dev_read_lane = bool(device_read_lane)
        # skimmed GETs awaiting service: (block, bfut, barrier) where
        # barrier is the _dev_wseq stamp at submit — the read becomes
        # eligible once every write block staged before it has
        # DISPATCHED (chained state then contains those writes)
        self._read_pending: deque = deque()
        self._dev_wseq = 0  # full-width blocks staged (write barrier)
        self._dev_wdisp = 0  # full-width blocks dispatched
        # rabia_devkv_read_* sources: ops served off-consensus (probe),
        # ops that consumed slots (slot), value-plane download events
        # (fallback), probe windows dispatched
        self._read_stats = {
            "probe": 0, "slot": 0, "fallback": 0, "probe_windows": 0,
        }
        for _path in ("probe", "slot", "fallback"):
            m.counter(
                "devkv_read_total",
                "Device-lane GET ops by serving path: probe = "
                "off-consensus lookup_only windows (zero slots), slot = "
                "consensus-window GETs, fallback = value-plane download "
                "events (eviction edge; overlaps the other two)",
                {"path": _path},
                fn=(lambda p=_path: self._read_stats[p]),
            )
        m.counter(
            "devkv_read_probe_windows_total",
            "Consensus-free lookup_only probe windows dispatched",
            fn=lambda: self._read_stats["probe_windows"],
        )
        m.gauge(
            "devkv_table_bytes",
            "Bytes of the device table's seven state planes, from their "
            "shapes (every chip's share together; 0 without a device "
            "store)",
            fn=lambda: self._dev.table_bytes if self._dev is not None else 0,
        )
        m.counter(
            "devkv_upload_bytes_total",
            "Host bytes placed on the device for window dispatches (the "
            "bytes= of the rabia.dispatch.place spans)",
            fn=lambda: self._dev.upload_bytes if self._dev is not None else 0,
        )
        for _outcome in ("reused", "fresh"):
            m.counter(
                "devkv_pack_buffers_total",
                "Window-plane buffers the pack took from the table's "
                "pool by outcome (the reused= of the "
                "rabia.cycle.pack.alloc spans): reused = a buffer of an "
                "earlier window that nothing else still referenced, "
                "fresh = a new allocation because every pooled buffer "
                "of that size was still held",
                {"outcome": _outcome},
                fn=(
                    lambda o=_outcome: self._dev.pack_buffers[o]
                    if self._dev is not None
                    else 0
                ),
            )
        for _path in ("native", "numpy"):
            m.counter(
                "devkv_pack_windows_total",
                "Windows the pack was asked for by the path that packed "
                "them (the path= of the rabia.cycle.pack.parse spans): "
                "native = one C scan and one C gather read the blocks "
                "where they lie (full-width blocks, one op a shard, "
                "shards in order, every op inside the envelope), numpy "
                "= the numpy parse and gather (any other window, the "
                "ones it refuses included, and RABIA_PY_DEVPACK=1)",
                {"path": _path},
                fn=(
                    lambda p=_path: self._dev.pack_windows[p]
                    if self._dev is not None
                    else 0
                ),
            )
        for _path in ("identity", "checked"):
            m.counter(
                "mesh_submit_blocks_total",
                "Blocks submit_block was given by the path that validated "
                "them (the rabia.submit.validate spans): identity = every "
                "shard once and in order, proven by one compare, routed "
                "with the engine's one shared inv; checked = any other "
                "block (partial-width, permuted, refused): the range "
                "check and a sort",
                {"path": _path},
                fn=lambda p=_path: self._submit_blocks[p],
            )
        m.counter(
            "devkv_sync_rows_total",
            "Device table rows materialized on the host by dump() (the "
            "rows= of the rabia.sync.dump spans): a sync_to_host, a "
            "demotion or a checkpoint each read the whole table once",
            fn=lambda: self._dev.sync_rows if self._dev is not None else 0,
        )
        self._dev_value_download_bytes = 0
        m.counter(
            "devkv_value_download_bytes_total",
            "Value-plane bytes a settle took from the device and used, "
            "because a read's version had left the host segments (the "
            "rabia.cycle.settle.download spans; a prefetched plane that "
            "the settle did not need is not counted)",
            fn=lambda: self._dev_value_download_bytes,
        )
        # does a GET-bearing window fetch its value planes on a readback
        # worker at dispatch? Iff the newest such window to settle had to
        # download them (see _dev_settle_values)
        self._dev_prefetch = False
        self._dev_value_fetch = dict.fromkeys(_VALUE_FETCH_OUTCOMES, 0)
        for _outcome in _VALUE_FETCH_OUTCOMES:
            m.counter(
                "devkv_value_fetch_total",
                "Settled GET-bearing device windows by where their value "
                "planes came from (the outcome= of the "
                "rabia.cycle.settle.download spans): prefetched = a "
                "readback worker fetched them from dispatch on and the "
                "settle picked them up, inline = the settle downloaded "
                "them on the window's thread, unused = prefetched, but "
                "every read resolved from the host segments. A window that "
                "neither prefetched nor downloaded counts nowhere",
                {"outcome": _outcome},
                fn=lambda o=_outcome: self._dev_value_fetch[o],
            )
        m.counter(
            "devkv_program_builds_total",
            "Window programs built, one per distinct signature (each "
            "one's first call is a rabia.jit.first_call span)",
            fn=lambda: (
                len(self._dev._fused_cache) if self._dev is not None else 0
            ),
        )
        self._h_read_batch = m.histogram(
            "devkv_read_batch_ops",
            "GET blocks coalesced per probe window (batching factor of "
            "the read-index lane)",
            buckets=tuple(float(1 << i) for i in range(11)),
        )
        # randomized-termination evidence (chaos/runner.collect_evidence
        # reads this family from every engine): the colocated lockstep
        # mesh decides every counted slot unanimously in its first
        # phase — a theorem of the model, not a measurement, so the
        # curve is a spike at 1 sourced from the decision counter
        _phase_bounds = tuple(float(b) for b in range(1, 33))

        def _mesh_phase_curve():
            d = int(self.decided_v1)
            return [d] + [0] * 31, d, float(d)

        m.histogram(
            "phases_to_decide",
            "Weak-MVC phases per decided slot (colocated lockstep: "
            "every decided slot is unanimous, phase 1 by construction)",
            buckets=_phase_bounds,
            fn=_mesh_phase_curve,
        )
        if device_store:
            from rabia_tpu.apps.device_kv import DeviceKVTable

            if self._multi:
                # the device lane dispatches host-local inputs against
                # the global sharding; multi-controller runs need the
                # make_array_from_callback/allgather discipline of the
                # host lane (_run_window_multihost)
                raise ValidationError(
                    "device_store is single-controller only; multi-host "
                    "runs use the host-apply lane"
                )
            if not all(
                hasattr(sm, "store") and callable(getattr(sm, "apply_block", None))
                for sm in self.sms
            ):
                raise ValidationError(
                    "device_store requires VectorShardedKV replica SMs "
                    "(the demotion target)"
                )
            # set-up by part (RABIA_TRACE=1): the table's seven planes
            # placed on the device, the engine's ladder handed to the table
            with device_annotation("rabia.setup.engine"):
                self._dev = DeviceKVTable(
                    self.n_shards, self.kernel, rungs=self._ladder(),
                    **(device_store_kw or {}),
                )
            self._dev_active = True
            # host mirror of the device per-shard version counters:
            # response versions derive from it (no per-op readback)
            self._dev_sver = np.zeros(self.S, np.int64)
            # host-side value segments: every committed device window's
            # (vlen, value bytes) retained keyed by version range, plus
            # a (shard, version) -> bytes seed filled at re-promotion —
            # together they resolve ANY version a device GET can return,
            # so the read lane downloads found+version only (~5 B/op),
            # not value planes (~70 B/op)
            # pipelined-commit records: dispatched-but-unresolved
            # windows (flags unread); see _run_cycle_fullwidth_device.
            # Flag/meta fetches, and the value planes' where a window
            # prefetches them, run on a worker pool (3 per allowed
            # in-flight window — see _dev_fetcher): issued from the
            # main thread they would queue BEHIND the just-dispatched
            # next window on the single-stream device and wait out a
            # full window per cycle, and on a single worker the fetches
            # serialize one readback apart.
            self._dev_pipe: list = []
            # in-flight windows whose version derivation is DEFERRED to
            # settlement (DEL bumps the shard version only when found —
            # a data-dependent bump the mirror can't derive until the
            # meta readback; any window dispatched behind one inherits
            # the stale mirror and defers too)
            self._dev_defer = 0
            self._dev_fetcher_pool = None  # lazy: first pipelined window
            self._dev_vseg: deque = deque()
            self._dev_vseg_bytes = 0
            self._dev_vseg_cap = 64 << 20  # evictions raise _dev_floor
            self._dev_seed: dict = {}
            self._dev_seed_keys = np.empty(0, np.int64)
            # versions <= floor[s] are resolvable only via the seed
            # (raised by segment eviction and at re-promotion)
            self._dev_floor = np.zeros(self.S, np.int64)
        # full-width cycles between re-promotion attempts after a
        # demotion (0 disables climbing back onto the device lane)
        self._dev_repromote = max(0, int(device_store_repromote))
        self._dev_cooldown = 0
        # max dispatched-but-unresolved windows (pipe depth): the extra
        # windows keep the device busy while earlier windows' readbacks
        # are in flight. Default: 3 for throughput mode; 1 under a
        # latency target (each extra window delays future settlement by
        # one more window, which a p99 target cannot absorb). On one
        # TPU v5e at 4096 shards x 5 replicas, window 64, a saturating
        # closed loop of three windows commits the same at depth 1, 2
        # and 3 (7.25M / 7.35M / 7.29M ops/s, single runs): the host
        # packs window N+1 before it needs window N resolved, so depth 1
        # hides the device as depth 3 does, and depth 3 holds 187 MB
        # more on the chip. What costs throughput is the CLIENT's depth:
        # one window outstanding runs host and device in lockstep (4.55M
        # ops/s), which a smaller window, two of which pipeline, cures
        # (PERF.md, PR 37 and PR 38).
        if device_store_inflight is None:
            device_store_inflight = 1 if latency_target_ms is not None else 3
        self._dev_inflight = max(1, int(device_store_inflight))

    # -- client surface ------------------------------------------------------

    def submit(
        self,
        commands: Union[CommandBatch, Sequence[Union[str, bytes]]],
        shard: int = 0,
    ) -> MeshFuture:
        """Queue a batch for consensus on ``shard``; settled by run_cycle."""
        if not (0 <= shard < self.n_shards):
            raise ValidationError(f"shard {shard} out of range")
        if isinstance(commands, CommandBatch):
            batch = commands
            if int(batch.shard) != shard:
                # the shard argument wins (transport-engine submit_batch
                # semantics); rebind WITHOUT changing the batch identity
                batch = replace(batch, shard=ShardId(shard))
        else:
            batch = CommandBatch.new(list(commands), shard=ShardId(shard))
        if self._full_blocks:
            self._demote_full_blocks()  # preserve submission order
        fut = MeshFuture()
        self.queues[shard].append(_Pending(batch, fut))
        self._queued_entries += 1
        return fut

    def submit_many(
        self, per_shard: dict[int, Sequence[Union[str, bytes]]]
    ) -> dict[int, MeshFuture]:
        """Bulk submission: one batch per shard in a single call."""
        return {s: self.submit(cmds, s) for s, cmds in per_shard.items()}

    def submit_block(self, block) -> MeshBlockFuture:
        """Bulk lane: one consensus slot per covered shard of a columnar
        :class:`~rabia_tpu.core.blocks.PayloadBlock` (the transport
        engine's submit_block analog). Decided entries apply with ZERO
        repacking — the submitted block IS the apply input — so per-slot
        Python overhead drops to a queue pop and a future index."""
        # the client's call: two spans a call and none per op, which the
        # one check a disabled span costs makes affordable (core/tracing)
        with device_annotation("rabia.submit.validate", n=len(block.shards)):
            shards = np.asarray(block.shards, np.int64)
            identity = self._is_identity(shards)
            if identity:
                # every shard once, in order: in range and unique by
                # construction, so the checks below could only pass
                self._submit_blocks["identity"] += 1
            else:
                self._submit_blocks["checked"] += 1
                if len(shards) == 0:
                    raise ValidationError("empty block")
                if int(shards.min()) < 0 or int(shards.max()) >= self.n_shards:
                    raise ValidationError("block shard out of range")
                if len(np.unique(shards)) != len(shards):
                    # build_block enforces this, but a hand-constructed or
                    # codec-decoded PayloadBlock may not have been through
                    # it — a duplicate shard would corrupt slot accounting
                    raise ValidationError("block shards must be unique")
        with device_annotation("rabia.submit.route") as span:
            bfut = MeshBlockFuture(len(shards))
            if len(shards) == self.n_shards and self._queued_entries == 0:
                if (
                    self._dev_read_lane
                    and self._dev_active
                    and _block_op_kind(block) == 2
                ):
                    # read-index lane: the GET never enters the consensus
                    # stream — it parks with a write barrier (every block
                    # staged so far) and serves from a consensus-free probe
                    # window once those writes have dispatched
                    lane = "read"
                    self._read_pending.append((block, bfut, self._dev_wseq))
                else:
                    # full-width block with nothing queued: the vectorized
                    # lane
                    lane = "full"
                    self._full_blocks.append(
                        (block, bfut, self._block_inv(shards, identity))
                    )
                    self._dev_wseq += 1
            else:
                lane = "queue"
                if self._full_blocks:
                    self._demote_full_blocks()
                for i, s in enumerate(shards.tolist()):
                    self.queues[s].append(
                        _Pending(None, None, block=block, bidx=i, bfut=bfut)
                    )
                    self._queued_entries += 1
            if span is not None:
                span.set_metadata(lane=lane)
        return bfut

    def _is_identity(self, shards: np.ndarray) -> bool:
        """Whether a block's shards are ``0 .. n_shards - 1`` in order:
        the two ends first, then one compare."""
        n = self.n_shards
        return (
            len(shards) == n
            and shards[0] == 0
            and shards[-1] == n - 1
            and bool((shards == self._shard_ids).all())
        )

    def _block_inv(self, shards: np.ndarray, identity: bool) -> np.ndarray:
        """A full-width block's shard -> entry map: the engine's one
        read-only identity array for a block in shard order, a fresh
        permutation for any other."""
        if identity:
            return self._shard_ids
        inv = np.empty(self.n_shards, np.int64)
        inv[shards] = np.arange(len(shards))
        return inv

    # -- fault injection -----------------------------------------------------

    def crash_replica(self, r: int) -> None:
        """Mask replica ``r`` out of every shard's tally (fail-stop)."""
        self.alive[:, r] = False
        self._spec = None  # speculated under the old mask

    def heal_replica(self, r: int) -> None:
        self.alive[:, r] = True
        self._spec = None

    @property
    def has_quorum(self) -> bool:
        return int(self.alive[0].sum()) >= quorum_size(self.R)

    # -- the cycle -----------------------------------------------------------

    def run_cycle(self) -> int:
        """Decide up to ``window`` queued slots per shard in ONE device
        dispatch, then apply + settle on the host. Returns batches applied.

        With ``latency_target_ms`` set, each working cycle's wall time
        feeds the window governor (see :meth:`_govern`), which walks
        ``window`` up and down a power-of-two ladder to keep the p99
        window latency under the target — the same measure-and-step
        pattern as the adaptive batcher (core/batching.py), on the
        latency axis instead of the flush-cause axis."""
        if self.latency_target_ms is None:
            return self._run_cycle_inner()
        self._lat_saturated = False
        self._lat_invalidate = False
        self._lat_timing = True
        cycles_before = self.cycles
        t0 = time.perf_counter()
        try:
            applied = self._run_cycle_inner()
        finally:
            self._lat_timing = False
        dt_ms = (time.perf_counter() - t0) * 1e3
        if self.cycles > cycles_before:
            # a sample is a cycle that consumed a window (an idle probe
            # costs ~µs and would drown the window samples), with the
            # cycles before it that dispatched nothing and only drained
            # an in-flight device window: a client that keeps one
            # window outstanding makes the engine resolve each window in
            # a cycle of its own, and that wait and settle are the
            # window's cost as much as its pack. A lane
            # demotion mid-cycle (device -> host, block -> scalar) runs
            # a second dispatch plus that path's jit compile inside this
            # one sample — one-off machinery, not steady-state latency
            invalid = self._lat_invalidate
            self._lat_invalidate = False
            dt_ms += self._lat_drain_ms
            self._lat_drain_ms = 0.0
            if self._lat_skip:
                self._lat_skip -= 1  # compile warmup, not latency
            elif not invalid:
                self._lat_samples.append(dt_ms)
                self._govern(dt_ms)
        elif applied:
            self._lat_drain_ms += dt_ms
        return applied

    def _rung_below(self, w: int) -> int:
        return max(self.min_window, w // 2)

    def _rung_above(self, w: int) -> int:
        return min(self.max_window, w * 2)

    def _ladder(self) -> tuple[int, ...]:
        """Every window size this engine can dispatch at: without a
        latency target its ``window`` alone; with one, every size the
        governor's steps (:meth:`_govern`: halve, double, or drop to
        ``min_window``) reach from the starting one — the powers of two
        from ``min_window`` to ``max_window`` when all three are powers
        of two. The device table builds each program for all of them at
        once (``DeviceKVTable.rungs``)."""
        if self.latency_target_ms is None:
            return (self.window,)
        seen: set[int] = set()
        todo = [self.window]
        while todo:
            w = todo.pop()
            if w not in seen:
                seen.add(w)
                todo += [self._rung_below(w), self._rung_above(w)]
        return tuple(sorted(seen))

    def _p99(self) -> float:
        """Interpolated empirical p99 over the current samples.

        Unlike the round-4 max-of-window proxy, a single load spike
        does not pin the estimate: with n samples the estimate
        sits between the two top order statistics, weighted toward the
        max only as n grows past ~100 (numpy linear interpolation) —
        so one 2.3x outlier among 30 quiet samples reads as "p99 near
        the second-worst", which is what a latency SLO actually
        tracks."""
        return float(np.percentile(np.asarray(self._lat_samples), 99))

    def _p99_decision(self) -> float:
        """The p99 estimate the governor acts on: one-outlier-trimmed.

        With the ≤64 samples a resize decision ever sees, any
        interpolated p99 is dominated by the top order statistic — so a
        single glitch (one window several times slower than its
        neighbours: a host stall, a shared-core hiccup) pins the raw
        estimate above ANY target until the spike leaves the deque, and
        the round-4 governor dutifully halved W on it. At n≥8 the decision estimate drops the single worst
        sample: a lone glitch reads as "p99 near the second-worst",
        while genuine overload (where the second-worst is also over
        target) still trips it one sample later. Reporting
        (:meth:`governor_stats`, :meth:`_p99`) stays untrimmed — the
        SLO view must not hide outliers; only the control loop is
        robustified."""
        a = np.asarray(self._lat_samples)
        if a.size >= 8:
            a = np.sort(a)[:-1]
        return float(np.percentile(a, 99))

    def _govern(self, dt_ms: float) -> None:
        """Latency-target window control (multiplicative ladder).

        Downsize: two corroborating >2× overshoots among the last 8
        samples, or the trimmed p99 decision estimate
        (:meth:`_p99_decision`) exceeding the target after 8 samples of
        evidence (8 so the one-outlier trim is engaged — below that an
        untrimmed "p99" is just the glitch itself). A downsize drops
        one rung when the breach is shallow, but fast-descends straight
        to ``min_window`` when the trimmed p99 is itself >2× target —
        which is the common case for the spike path, since two >2×
        samples among ≥4 pull the trimmed estimate over 2× too. Round 4 halved on a SINGLE 2× overshoot —
        where lone 5–10× glitches occur, that evicts a healthy window
        size and the resulting ceiling parks the engine below its
        sustainable throughput for the rest of the run.
        Genuine overload produces a
        second overshoot within a sample or two; a glitch does not.
        Upsize: with trimmed p99 ≤ 0.7×target AND demand that would
        fill the next rung (a deeper window amortizes more only when
        there is work to put in it: a window dispatched half empty runs
        the larger program on the smaller window's ops),
        W doubles after 8 samples — headroom-based, so an occasional
        spike below the target no longer vetoes growth the way the old
        max-proxy did. Samples clear on every resize so each decision
        is measured at the current W (:meth:`_resize`). On the host
        lanes each ladder size jit-compiles once per process, where it
        first lands; the device lane's table builds a program for every
        rung of :meth:`_ladder` at once, with the first window of its
        kind.

        Anti-oscillation: a downsize records the size that failed as a
        CEILING; upsizing never re-enters a size at or above a live
        ceiling (the 128↔256 limit cycle would otherwise trade ~25% of
        throughput for repeated overshoots). The ceiling ages out after
        256 governed samples, and — new in round 5 — is PROBED early
        when the current size shows sustained deep headroom (trimmed
        p99 ≤ 0.5×target over ≥16 samples): the ceiling clears and W
        re-enters the evicted size; if it genuinely can't hold the
        target, the downsize path re-establishes the ceiling within a
        few samples. A ceiling set by real overload keeps failing its
        probes; one set by a transient stops costing throughput in ~16
        windows instead of 256.

        Unachievability: when W is already ``min_window`` and the
        trimmed p99 — the statistic this governor is chartered to keep
        under the target — still exceeds the target, no window size can
        meet it (the floor is dispatch + readback, not window
        depth). That state is surfaced instead of silently parking:
        ``latency_target_unachievable`` flips True, a warning logs once
        with the measured floor, and :meth:`governor_stats` reports it.
        It clears when the p99 at min_window comes back under target
        (e.g. a competing load subsided)."""
        s = self._lat_samples
        t = self.latency_target_ms
        p99d = self._p99_decision()
        if self._lat_ceiling is not None:
            self._lat_ceiling_age += 1
            if self._lat_ceiling_age > 256:
                self._lat_ceiling = None
        if self.window == self.min_window and len(s) >= 8:
            if p99d > t:
                self._lat_floor_ms = p99d
                if not self.latency_target_unachievable:
                    self.latency_target_unachievable = True
                    logger.warning(
                        "latency target %.3gms is unachievable: p99 at "
                        "min_window=%d is %.3gms (dispatch floor); "
                        "governor parked",
                        t,
                        self.min_window,
                        p99d,
                    )
            elif self.latency_target_unachievable:
                self.latency_target_unachievable = False
                self._lat_floor_ms = None
        # corroboration is RECENT: two >2x overshoots among the last 8
        # samples. Counting over the whole 64-deep deque would let a
        # stale glitch corroborate a fresh one in the n<8 regime where
        # the p99 path is still off; genuine overload produces its
        # second overshoot within a few windows. The p99 path waits for
        # n>=8 so the one-outlier trim in _p99_decision is always
        # engaged by the time it can fire — at n<8 an untrimmed
        # estimate IS the glitch. (Two glitches within one >=8-sample
        # window DO trip the p99 path even after the trim: 2 of 64
        # samples over 2x target is a >1% exceedance — a genuine p99
        # breach, not noise. The recovery story for a glitchy link is
        # the ceiling probe and the unachievable report, not pretending
        # the tail isn't there.)
        spikes = sum(1 for x in list(s)[-8:] if x > 2.0 * t)
        if (
            (len(s) >= 2 and spikes >= 2)
            or (len(s) >= 8 and p99d > t)
        ) and self.window > self.min_window:
            self._lat_ceiling = self.window  # this size failed
            self._lat_ceiling_age = 0
            if p99d > 2.0 * t and len(s) >= 4:
                # fast descent: overshooting by 2x even on the trimmed
                # estimate means the target is at or below the dispatch
                # floor — walking the ladder rung by rung would spend
                # eight overshooting windows on every intermediate size
                # on the way down. Jump to the floor; if the target is
                # achievable there, the upsize path climbs back with
                # evidence.
                self._resize(self.min_window, p99d)
            else:
                self._resize(self._rung_below(self.window), p99d)
        elif (
            len(s) >= 8
            and p99d <= 0.7 * t
            and self._lat_saturated
            and self.window < self.max_window
        ):
            blocked = (
                self._lat_ceiling is not None
                and self.window * 2 >= self._lat_ceiling
            )
            if blocked and len(s) >= 16 and p99d <= 0.5 * t:
                self._lat_ceiling = None  # probe the evicted size
                blocked = False
            if not blocked:
                self._resize(self._rung_above(self.window), p99d)

    def _resize(self, to: int, p99d: float) -> None:
        """Move the governor to rung ``to``: every decision is measured
        at the current W, so the samples start again. On the host lanes
        the next cycle compiles the new size's program and goes untimed;
        the device lane's ladder built it with the first window of its
        kind, so there the next cycle is a sample like any other."""
        with device_annotation(
            "rabia.governor.resize",
            **{"from": self.window, "to": to, "p99_ms": round(p99d, 3)},
        ):
            self.window = to
            self._lat_samples.clear()
            self._lat_skip = 0 if self._dev_active else 1
            self.window_resizes += 1

    def governor_stats(self) -> dict:
        """Observable governor state: current window, resize count, the
        p99 estimate over recent samples, and whether the configured
        target is below the measured hardware floor."""
        return {
            "window": self.window,
            "resizes": self.window_resizes,
            "samples": len(self._lat_samples),
            "p99_ms": (
                round(self._p99(), 3) if self._lat_samples else None
            ),
            # what the control loop acts on (one-outlier-trimmed; see
            # _p99_decision) — diverges from p99_ms when a lone glitch
            # is in the sample window
            "p99_decision_ms": (
                round(self._p99_decision(), 3)
                if self._lat_samples
                else None
            ),
            "target_ms": self.latency_target_ms,
            "unachievable": self.latency_target_unachievable,
            "floor_ms": (
                round(self._lat_floor_ms, 3)
                if self._lat_floor_ms is not None
                else None
            ),
            "ceiling_window": self._lat_ceiling,
            # client-observed dispatch->settle latency through the
            # pipelined commit (~inflight x window time when
            # saturated — the p99 a settle-latency SLO would see).
            # Both report None while the device lane is inactive: no
            # pipelined commit exists then, and frozen device-era
            # samples must not read as live latency
            "inflight": (
                self._dev_inflight
                if self._dev is not None and self._dev_active
                else None
            ),
            "settle_p99_ms": (
                round(
                    float(
                        np.percentile(np.asarray(self._lat_settle), 99)
                    ),
                    3,
                )
                if self._lat_settle
                and self._dev is not None
                and self._dev_active
                else None
            ),
        }

    def _run_cycle_inner(self) -> int:
        # read-index lane first: every eligible skimmed GET (its write
        # barrier has dispatched) batches into one consensus-free probe
        # window before the consensus stream runs — mixed workloads
        # then dispatch SET-mostly windows
        served = 0
        if (
            self._dev is not None
            and self._dev_active
            and self._read_pending
            and self._read_pending[0][2] <= self._dev_wdisp
        ):
            # a probe window outside the read envelope demotes inside
            # this call; the flushed blocks then re-enter through the
            # host path in the body below — same-cycle continuation
            served = self._dev_serve_reads()
        return served + self._run_cycle_body()

    def _run_cycle_body(self) -> int:
        if (
            self._dev_active
            and self._dev_pipe
            and not self._full_blocks
        ):
            # no new device work: drain one in-flight window so flush
            # converges (its applied count is this cycle's progress)
            return self._dev_resolve_one()
        if self._full_blocks:
            if self._vector and self._queued_entries == 0:
                if (
                    not self._dev_active
                    and self._dev is not None
                    and self._dev_repromote > 0
                ):
                    # demoted device lane: periodically try to climb back
                    # (the host stores are quiescent between cycles, so
                    # the upload captures an exact snapshot)
                    if self._dev_cooldown > 0:
                        self._dev_cooldown -= 1
                    else:
                        self._try_repromote_device_store()
                if self._dev_active:
                    return self._run_cycle_fullwidth_device()
                return self._run_cycle_fullwidth()
            self._demote_full_blocks()  # non-vector SMs materialize per batch
        if self._dev_active and self._queued_entries:
            # per-shard / scalar work is outside the device lane's
            # envelope: hand the authoritative state back to the host
            # replicas before applying anything there. (An IDLE cycle —
            # nothing queued at all — must NOT demote.)
            self._demote_device_store()
        W = self.window
        depth = np.zeros(self.S, np.int64)
        saturated = False
        for s in range(self.n_shards):
            q = len(self.queues[s])
            depth[s] = min(q, W)
            saturated |= q >= self._rung_above(W)
        self._lat_saturated |= saturated  # the next rung had demand
        if not depth.any():
            return 0
        # initial votes: every live replica proposes/accepts V1 for a slot
        # whose payload exists (colocated dissemination); filler entries
        # beyond a shard's queue depth vote V0 unanimously — they decide V0
        # in phase 0, are never recorded, and their slot numbers are reused
        # by the next cycle (deterministic => harmless re-decide)
        votes = np.zeros((W, self.S, self.R), np.int8)
        for s in np.nonzero(depth)[0]:
            votes[: depth[s], s, :] = V1
        decided = self._decide_window(votes, W)
        applied = 0
        # collect (pop + record) first, apply after in window-position
        # order. Per-shard apply order is slot order (the SMR guarantee);
        # ACROSS shards the order is wave-major — deterministic and
        # replica-consistent, and it lets the vector path pack each window
        # position's commits into ONE PayloadBlock
        waves: list[list[tuple[int, int, _Pending]]] = [[] for _ in range(W)]
        for s in np.nonzero(depth)[0]:
            s = int(s)
            q = self.queues[s]
            for t in range(int(depth[s])):
                v = int(decided[t, s])
                if v == ABSENT:
                    # quorum lost mid-window: park the shard; the window
                    # re-runs (deterministically) after heal
                    break
                slot = int(self.next_slot[s])
                if v == V1:
                    pend = q.popleft()
                    self._queued_entries -= 1
                    waves[t].append((s, slot, pend))
                    # block-lane entries log a lazy (block, bidx) ref —
                    # decisions_for materializes on access, so the bulk
                    # hot path never builds per-slot CommandBatch objects
                    self._record(
                        s,
                        slot,
                        V1,
                        pend.batch
                        if pend.batch is not None
                        else (pend.block, pend.bidx),
                    )
                    applied += 1
                else:
                    # null slot: batch not committed here; retries next
                    # window at a fresh slot number
                    self._record(s, slot, V0, None)
                self.next_slot[s] = slot + 1
        if self._vector:
            self._apply_waves_bulk(waves)
        else:
            self._apply_waves_scalar(waves)
        return applied

    def _run_cycle_fullwidth_device(self) -> int:
        """Full-width lane with the device-resident KV table: consensus
        window + every decided SET + response versions in ONE fused
        program; the host does bookkeeping only. Any outcome outside the
        fast-lane envelope (non-SET ops, key/value over width, table
        overflow, a fault) demotes to the host path — state is adopted
        only on a clean all-V1 window, so demotion always re-runs from a
        consistent table."""
        from rabia_tpu.apps.vector_kv import FrameGroups, VectorShardedKV

        W = self.window
        n = self.n_shards
        self._lat_saturated |= len(self._full_blocks) >= self._rung_above(W)
        # uniform-kind runs use the lean programs (SET windows carry no
        # GET readback planes, GET windows mutate nothing); a kind
        # boundary INSIDE the window — or a block interleaving SET and
        # GET ops — runs the MIXED program over the full window instead
        # of splitting at the boundary (round-4 behavior), so
        # interleaved workloads no longer pay window quantization
        count = min(len(self._full_blocks), W)
        with device_annotation("rabia.cycle.kinds", blocks=count):
            kinds = [
                _block_op_kind(self._full_blocks[i][0]) for i in range(count)
            ]
            head_kind = kinds[0] if kinds else None
            depth = 0
            for k in kinds:
                if k != head_kind:
                    break
                depth += 1
        # mixed and GET windows PIPELINE like SET windows: they dispatch
        # chained on the newest in-flight window's output state and join
        # _dev_pipe. (They used to drain the pipe and read their
        # flags/meta synchronously here, serializing a full readback
        # round trip per window.)
        if (
            head_kind is None
            or depth < len(kinds)
            or head_kind in (3, 4)  # DEL/EXISTS runs ride the mixed program
        ):
            return self._run_cycle_fullwidth_device_mixed(count)
        if head_kind == 2:
            return self._run_cycle_fullwidth_device_get(depth)
        entries = [self._full_blocks[i] for i in range(depth)]  # peek
        with device_annotation("rabia.cycle.pack"):
            ops = self._dev.pack_window([e[0] for e in entries])
        if ops is None:
            applied = self._dev_drain_pipe()
            self._demote_device_store()
            return applied + self._run_cycle_inner()
        base = np.zeros(self.S, np.int32)
        base[:n] = self.next_slot
        # PIPELINED COMMIT: dispatch window k chained on the UNRESOLVED
        # previous window's output state, advance the bookkeeping
        # optimistically, and only then read the previous window's
        # 12-byte flags — the flag round-trip overlaps this window's
        # upload + device compute instead of serializing every cycle.
        # Futures settle one window late (at resolution); a dirty flag
        # rolls back every optimistic window (the programs are
        # functional — nothing was adopted) and demotes.
        state_base = self._dev_chain_base()
        with device_annotation("rabia.devkv.decide_apply"):
            new_state, flags_dev = self._dev.decide_apply(
                self.alive, base, depth, ops, W=W,
                max_phases=self.max_phases, state=state_base,
            )
        with device_annotation("rabia.cycle.book"):
            # the first window of a kind or of a widths signature
            # compiles inside this dispatch, with its siblings at every
            # other rung — seconds of jit, not window latency
            self._lat_invalidate |= (
                self._dev.compiled_on_last_call and self._lat_timing
            )
            self.cycles += 1
            # version responses are DERIVED, not transferred: a clean
            # all-V1 full-width window advances every covered shard's
            # version by exactly one per wave, so the host mirror + wave
            # index reproduces the device counters bit-for-bit (pinned by
            # tests/test_device_kv.py against the host store). While a
            # DEL-bearing window is in flight the mirror base is unknown —
            # derivation then defers to settlement like the mixed lane's
            # (_dev_settle_set patches the provisional segment).
            deferred = self._dev_defer > 0
            with device_annotation("rabia.cycle.book.versions"):
                if deferred:
                    vers = None
                    sver_delta = None
                    seg_start = np.zeros_like(self._dev_sver)
                    seg_end = np.zeros_like(self._dev_sver)
                else:
                    vers = (
                        self._dev_sver[None, : self.S]
                        + np.arange(1, W + 1, dtype=np.int64)[:, None]
                    )
                    seg_start = self._dev_sver.copy()
                    seg_end = seg_start.copy()
                    seg_end[:n] += depth
                    sver_delta = np.zeros_like(self._dev_sver)
                    sver_delta[:n] = depth
                    self._dev_sver[:n] += depth
            with device_annotation("rabia.cycle.book.segment") as span:
                # retain this window's value bytes host-side: (shard,
                # version) uniquely identifies content, so the GET lane can
                # answer reads without downloading values (see _dev_resolve)
                seg = _RowSeg(seg_start, seg_end, ops.vlen, ops.vwin)
                if deferred:
                    seg.provisional = True
                    self._dev_defer += 1
                self._dev_push_segment(seg)
                if span is not None:
                    span.set_metadata(bytes=seg.nbytes)
            self._dev_commit_window(entries, depth)
            flags_fut, _, _ = self._dev_hand_off(flags_dev, (), None)
            rec = {
                "kind": "set",
                "flags_fut": flags_fut,
                "new_state": new_state,
                "entries": entries,
                "depth": depth,
                "n": n,
                "vers": vers,
                "seg": seg,
                "sver_delta": sver_delta,
                "deferred": deferred,
            }
        return self._dev_push_window(rec)

    def _dev_commit_window(self, entries, depth: int):
        """Shared commit bookkeeping for every device window kind: pop
        the consumed blocks, advance the slot counters, append to the
        bulk decision log (trimmed to the retention budget). Returns
        the per-shard start slots (for the log records)."""
        n = self.n_shards
        for _ in range(depth):
            self._full_blocks.popleft()
        self._dev_wdisp += depth  # read-lane write barrier advances
        start = self.next_slot.copy()
        self.next_slot[:n] += depth
        self.decided_v1 += depth * n
        for t, (block, bfut, inv) in enumerate(entries):
            self._bulk_log.append((start, t, block, inv))
        while len(self._bulk_log) > max(
            1, self.max_decision_history // max(1, self.window)
        ):
            self._bulk_log.popleft()
        return start

    def _dev_chain_base(self):
        """Table state a new device window dispatches against: the
        newest in-flight window's (unresolved) output, else the settled
        table — shared by all three window kinds."""
        return (
            self._dev_pipe[-1]["new_state"]
            if self._dev_pipe
            else self._dev.state
        )

    def _dev_push_window(self, rec) -> int:
        """Append an in-flight window record and enforce the pipe depth:
        beyond ``device_store_inflight`` in-flight windows, resolve the
        oldest (its flags have had that many windows' time to come
        back — depth 1 overlaps the readback with one pack, deeper
        pipes hide a round trip longer than a single pack). Owns the
        pipe policy so the three dispatch paths cannot diverge."""
        rec["t0"] = time.perf_counter()
        self._dev_pipe.append(rec)
        W = self.window
        # a marker of no length: the rung this window ran at, in the
        # name because a trace's readers see names and durations only
        with device_annotation(f"rabia.window.w{W}"):
            self._dev_windows[W] = self._dev_windows.get(W, 0) + 1
        if self._dev.compiled_on_last_call:
            # a jit compile (the first window of a kind or of a widths
            # signature, with its whole ladder) ran
            # inside this dispatch: seconds of one-off machinery sat
            # between every in-flight window's dispatch and its
            # resolve. Their settle samples would read as latency —
            # taint them (same policy as _lat_invalidate for the
            # governor's per-cycle samples)
            for r in self._dev_pipe:
                r["lat_taint"] = True
        applied = 0
        while len(self._dev_pipe) > self._dev_inflight:
            applied += self._dev_resolve_one()
            if not self._dev_active:
                break  # dirty window rolled the pipe back and demoted
        return applied

    def _dev_fetcher(self):
        """The executor that fetches window flags/meta/value planes
        off the main thread (see _run_cycle_fullwidth_device). Lazy and
        recreatable: demotion shuts it down (host mode needs no worker),
        re-promotion's first pipelined window brings it back."""
        import concurrent.futures

        if self._dev_fetcher_pool is None:
            # three workers per allowed in-flight window (GET/mixed
            # windows submit up to THREE blocking fetches — flags, meta
            # and, when prefetching, the value planes): with a deeper
            # pipe, window k's readbacks must not queue behind k-1's or
            # the fetches serialize one RTT apart and the extra depth
            # hides nothing
            self._dev_fetcher_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=3 * self._dev_inflight,
                thread_name_prefix="devkv-flags",
            )
        return self._dev_fetcher_pool

    def _dev_hand_off(self, flags, meta: tuple, planes):
        """The end of a window's ``book``: its readbacks handed to the
        workers, the small ones first (flags, then meta) so that neither
        queues behind the value planes' bulk copy. Returns the three
        futures, None for a readback the window does not have (a probe
        window's flags, a SET window's meta) or does not prefetch."""
        with device_annotation("rabia.cycle.book.handoff") as span:
            pool = self._dev_fetcher()
            flags_fut = meta_fut = None
            if flags is not None:
                flags_fut = pool.submit(
                    _fetch_readback, "rabia.fetch.flags", flags
                )
            if meta:
                meta_fut = pool.submit(
                    _fetch_readback, "rabia.fetch.meta", *meta
                )
            val_fut = self._dev_prefetch_values(pool, planes)
            if span is not None:
                futs = (flags_fut, meta_fut, val_fut)
                span.set_metadata(fetches=sum(f is not None for f in futs))
        return flags_fut, meta_fut, val_fut

    def close(self) -> None:
        """Release engine-held resources: settle in-flight device
        windows and stop the flags-fetch worker. Idempotent; the engine
        remains usable afterward (workers are lazily recreated)."""
        if self._dev is not None and self._dev_active:
            self._dev_drain_pipe()
        if getattr(self, "_dev_fetcher_pool", None) is not None:
            self._dev_fetcher_pool.shutdown(wait=False)
            self._dev_fetcher_pool = None

    def _dev_resolve_one(self) -> int:
        """Resolve the OLDEST in-flight device window: read its flags,
        then settle (clean) or roll back the whole pipe and demote
        (dirty). Handles all three window kinds ("set", "mixed", "get"
        — see their dispatch methods). Returns batches applied by the
        resolved window."""
        rec = self._dev_pipe[0]
        if rec["kind"] == "read":
            # consensus-free probe window: nothing was decided, nothing
            # can be dirty — FIFO resolution means every write it
            # chained on settled cleanly before it reached the head
            dirty = False
        else:
            with device_annotation("rabia.cycle.wait", what="flags"):
                flags = rec["flags_fut"].result()  # <=12 bytes: the readback
            if rec["kind"] == "get":
                dirty = not int(flags)  # lookup returns the all_v1 scalar
            else:
                dirty = not flags[0] or flags[1] or flags[2]
        if dirty:
            # roll back EVERY optimistic window, newest first — the
            # device state was never adopted, so restoring the host
            # bookkeeping re-creates the pre-window world exactly; the
            # host path then re-decides the same blocks. A record's
            # readback futures (a value prefetch among them) go with it
            # unread: the workers hold device handles and nothing of the
            # engine, so a late completion touches nothing
            while self._dev_pipe:
                r = self._dev_pipe.pop()
                d, rn = r["depth"], r["n"]
                if r["kind"] == "read":
                    # probe windows consumed no slots and no log
                    # entries: re-front the skimmed blocks so the
                    # demotion below flushes them to the host path
                    # (serialized after the rolled-back writes — all
                    # still-unsettled, so any order is linearizable).
                    # Un-count them: these ops end up host-served
                    self._read_stats["probe"] -= d * rn
                    self._read_stats["probe_windows"] -= 1
                    for e in reversed(r["entries"]):
                        self._read_pending.appendleft(e)
                    continue
                for _ in range(d):
                    if self._bulk_log:
                        self._bulk_log.pop()
                for e in reversed(r["entries"]):
                    self._full_blocks.appendleft(e)
                self._dev_wdisp -= d
                self.next_slot[:rn] -= d
                if r["sver_delta"] is not None:
                    self._dev_sver -= r["sver_delta"]
                if r.get("deferred"):
                    # deferred windows never advanced the mirror — the
                    # pending count is the only bookkeeping to unwind
                    self._dev_defer -= 1
                self.decided_v1 -= d * rn
                if (
                    r["seg"] is not None
                    and self._dev_vseg
                    and self._dev_vseg[-1] is r["seg"]
                ):
                    self._dev_vseg.pop()
                    self._dev_vseg_bytes -= r["seg"].nbytes
                # (an already-evicted segment only over-raised the
                # floor — safe: the GET path falls back to downloads)
            if self._queued_entries:
                # per-batch submissions arrived while the windows were in
                # flight (submit() found _full_blocks empty, so its
                # order-preserving demote had nothing to demote). The
                # rolled-back blocks predate everything in the queues —
                # push them to the FRONT now, or the later
                # _demote_full_blocks would append them BEHIND the newer
                # work and the host path would apply out of submission
                # order (divergence vs the host-only reference).
                # Every remaining _full_blocks entry was staged while
                # _queued_entries == 0, so it also predates the queues.
                self._lat_invalidate |= self._lat_timing
                self._spec = None
                while self._full_blocks:
                    block, bfut, _inv = self._full_blocks.pop()
                    for i in reversed(range(len(block))):
                        s = int(block.shards[i])
                        self.queues[s].appendleft(
                            _Pending(None, None, block=block, bidx=i, bfut=bfut)
                        )
                        self._queued_entries += 1
            self._demote_device_store()
            return 0
        # dispatch->settle latency: what a client actually waits at the
        # current pipe depth (depth multiplies it — the reason governed
        # mode defaults to depth 1); surfaced via governor_stats.
        # Compile-tainted windows are excluded (one-off jit machinery,
        # not steady-state latency)
        if not rec.get("lat_taint"):
            dt = time.perf_counter() - rec["t0"]
            self._lat_settle.append(dt * 1e3)
            self._h_window_settle.observe(dt)
        # the meta readback is waited for here, apart from the settle:
        # the settle's own .result() then returns at once
        meta_fut = rec.get("meta_fut")
        if meta_fut is not None:
            with device_annotation("rabia.cycle.wait", what="meta"):
                meta_fut.result()
        with device_annotation("rabia.cycle.settle"):
            self._dev_pipe.pop(0)
            # "get" windows are read-only: new_state is the (unchanged)
            # state they chained on, so adopting is a no-op by value and
            # keeps the pipe invariant uniform
            self._dev.adopt(rec["new_state"])
            if rec["kind"] == "set":
                self._dev_settle_set(rec)
            elif rec["kind"] == "mixed":
                self._dev_settle_mixed(rec)
            else:
                self._dev_settle_get(rec)
        return rec["depth"] * rec["n"]

    def _dev_settle_set(self, rec) -> None:
        """Settle a clean pure-SET window's futures from the derived
        version responses; counts==1 per covered shard (pack_window
        enforced it), so group bounds are the identity. A deferred
        window (dispatched behind a DEL-bearing one) derives here —
        the mirror is exact again — and patches its provisional
        segment."""
        from rabia_tpu.apps.vector_kv import FrameGroups, VectorShardedKV

        vers = rec["vers"]
        if rec.get("deferred"):
            depth, n = rec["depth"], rec["n"]
            vers = (
                self._dev_sver[None, : self.S]
                + np.arange(1, depth + 1, dtype=np.int64)[:, None]
            )
            seg = rec["seg"]
            seg.start = self._dev_sver.copy()
            seg.end = seg.start.copy()
            seg.end[:n] += depth
            seg.provisional = False
            self._dev_evict_segments()
            self._dev_sver[:n] += depth
            self._dev_defer -= 1
        entries = rec["entries"]
        with device_annotation("rabia.cycle.settle.blocks", blocks=len(entries)):
            for t, (block, bfut, _inv) in enumerate(entries):
                row = vers[t, np.asarray(block.shards, np.int64)]
                frames = VectorShardedKV._vers_frames(row)
                bounds = np.arange(len(block) + 1, dtype=np.int64)
                bfut._settle_bulk(FrameGroups(frames, bounds))

    def _dev_prefetch_values(self, pool, planes):
        """At dispatch: start the fetch of a GET-bearing window's value
        planes on a readback worker, iff the newest such window to
        settle had to download its own (``_dev_settle_values`` keeps
        that observation) and the planes are large enough to be worth a
        hand-over. Submitted after the window's flags and meta, which
        must not queue behind the bulk copy. Returns the future, or None
        when the planes stay on the device until a settle asks."""
        if not (planes and self._dev_prefetch):
            return None
        if sum(int(p.nbytes) for p in planes) < _PREFETCH_MIN_BYTES:
            return None
        return pool.submit(_prefetch_values, planes)

    def _dev_settle_values(self, rec, resolved: bool):
        """At settle: the value planes of a GET-bearing window on the
        host, or None when its reads ``resolved`` from the host
        segments. A plane comes from the window's worker when dispatch
        started one (the span is then the wait for it) and is downloaded
        here when not, exactly as before there were prefetches;
        ``rabia.cycle.settle.download`` is entered once either way. What
        this window needed decides whether the next window dispatched
        prefetches: a deployment whose reads resolve on the host pays
        for no transfer, one whose reads have left the segments pays for
        it off the window's thread."""
        fut = rec["val_fut"]
        self._dev_prefetch = not resolved
        if resolved:
            if fut is not None:
                fut.cancel()  # if still queued, the transfer is never made
                self._dev_value_fetch["unused"] += 1
            return None
        outcome = "inline" if fut is None else "prefetched"
        with device_annotation("rabia.cycle.settle.download", outcome=outcome):
            planes = (
                tuple(map(np.asarray, rec["val_dev"]))
                if fut is None
                else fut.result()
            )
        self._dev_value_fetch[outcome] += 1
        self._dev_value_download_bytes += sum(p.nbytes for p in planes)
        return planes

    def _dev_settle_get(self, rec) -> None:
        """Settle a clean GET window: meta (found/version) was fetched
        on the worker alongside the flags; value bytes resolve from the
        host-side segments unless an evicted version forces the
        value-plane download (:meth:`_dev_settle_values`: from the
        window's worker, or from the device handles retained in the
        record)."""
        from rabia_tpu.apps.device_kv import (
            GetFrameGroups,
            ResolvedGetFrameGroups,
        )

        depth = rec["depth"]
        found, ver = rec["meta_fut"].result()
        resolved = not self._dev_unresolvable(found[:depth], ver[:depth])
        planes = self._dev_settle_values(rec, resolved)
        if resolved:
            rsv = self._dev_make_resolver()
        else:
            # eviction edge: the window pays the value-plane download
            self._read_stats["fallback"] += depth * rec["n"]
            vlen, valw = planes
        entries = rec["entries"]
        with device_annotation("rabia.cycle.settle.blocks", blocks=len(entries)):
            for t, (block, bfut, _inv) in enumerate(entries):
                sh = np.asarray(block.shards, np.int64)
                if resolved:
                    bfut._settle_bulk(
                        ResolvedGetFrameGroups(sh, found[t], ver[t], rsv)
                    )
                else:
                    bfut._settle_bulk(
                        GetFrameGroups(sh, found[t], ver[t], vlen[t], valw[t])
                    )

    def _dev_settle_mixed(self, rec) -> None:
        """Settle a clean mixed window: SET versions derive from the
        recorded per-wave cumulative counters; GET meta was fetched on
        the worker; GET values resolve host-side with the downloaded
        value plane as the eviction fallback
        (:meth:`_dev_settle_values`).

        A DEFERRED window (DEL-bearing, or dispatched behind one)
        derives its versions HERE instead of at dispatch: FIFO
        settlement makes the mirror exact again, and the DEL found
        bits arrived with the meta plane — the authoritative per-shard
        bump vector (SET always, DEL on found — exactly the host
        store's semantics) patches the provisional segment and advances
        the mirror before any frame derives from it."""
        from rabia_tpu.apps.device_kv import (
            GetFrameGroups,
            MixedFrameGroups,
            ResolvedGetFrameGroups,
        )
        from rabia_tpu.apps.vector_kv import FrameGroups, VectorShardedKV

        kind = rec["kind_rows"]
        get_waves = rec["get_waves"]
        gpos = {int(t): j for j, t in enumerate(get_waves)}
        gfound_h = gver_h = gvlen_h = None
        if len(get_waves):
            meta_h = rec["meta_fut"].result()
            gver_h = meta_h[0]
            gvlen_h = meta_h[1] >> 1
            gfound_h = (meta_h[1] & 1).astype(bool)
        if rec.get("deferred"):
            bump = (kind == 1).astype(np.int64)
            for j, t in enumerate(get_waves):
                t = int(t)
                bump[t] += ((kind[t] == 3) & gfound_h[j]).astype(np.int64)
            cum = np.cumsum(bump, axis=0)
            svers = self._dev_sver[None, : self.S] + cum
            seg = rec["seg"]
            seg.start = self._dev_sver.copy()
            seg.end = seg.start + cum[-1]
            seg.svers = svers
            seg.provisional = False
            self._dev_evict_segments()
            self._dev_sver[: self.S] += cum[-1]
            self._dev_defer -= 1
        else:
            svers = rec["svers"]
        resolved = True
        if len(get_waves):
            # resolvability is about GET values only: EXISTS rows carry
            # found bits with version 0 and must not read as
            # unresolvable versions (meta planes are padded — compare
            # the real rows)
            g = len(get_waves)
            is_get_rows = kind[get_waves] == 2
            resolved = not self._dev_unresolvable(
                gfound_h[:g] & is_get_rows, gver_h[:g]
            )
            planes = self._dev_settle_values(rec, resolved)
            if resolved:
                rsv = self._dev_make_resolver()
            else:
                (gval_h,) = planes
        entries = rec["entries"]
        with device_annotation("rabia.cycle.settle.blocks", blocks=len(entries)):
            for t, (block, bfut, _inv) in enumerate(entries):
                sh = np.asarray(block.shards, np.int64)
                row_kind = kind[t]
                gf = None
                if t in gpos:
                    j = gpos[t]
                    if resolved:
                        gf = ResolvedGetFrameGroups(
                            sh, gfound_h[j], gver_h[j], rsv
                        )
                    else:
                        gf = GetFrameGroups(
                            sh, gfound_h[j], gver_h[j], gvlen_h[j], gval_h[j]
                        )
                if gf is None:
                    # pure-SET wave inside a mixed window: the lean framing
                    frames = VectorShardedKV._vers_frames(svers[t, sh])
                    bounds = np.arange(len(block) + 1, dtype=np.int64)
                    bfut._settle_bulk(FrameGroups(frames, bounds))
                elif not bool(((row_kind == 1) | (row_kind >= 3)).any()):
                    bfut._settle_bulk(gf)  # pure-GET wave (GET framing only)
                else:
                    # the reply owns its kind row: a view would hold the
                    # window's whole kind plane out of the pack's pool for
                    # as long as a client keeps the reply unread
                    bfut._settle_bulk(
                        MixedFrameGroups(sh, row_kind.copy(), svers[t], gf)
                    )

    def _dev_drain_pipe(self) -> int:
        """Resolve every in-flight device window (used before any
        operation that needs the settled table: GET/mixed windows,
        demotion, checkpointing, idle drain)."""
        applied = 0
        while self._dev_pipe and self._dev_active:
            applied += self._dev_resolve_one()
        return applied

    def _dev_serve_reads(self) -> int:
        """Serve every ELIGIBLE skimmed GET (write barrier dispatched)
        in one consensus-free ``lookup_only`` probe window: zero slots
        consumed, zero collectives in the program (pinned by
        benchmarks/ici_model.py), meta-only readback with host-segment
        value resolution — the device read-index lane.

        Linearizability: the window chains on the newest in-flight
        write window's output state, so a read observes every write
        dispatched before it (its barrier guarantees all EARLIER
        submissions are among them — read-your-writes) and possibly
        writes dispatched after it while it was parked — legal, those
        writes are still unsettled, i.e. concurrent invocations. The
        probe record joins the FIFO pipe, so its responses settle only
        after every write it observed settled cleanly; a dirty write
        rolls the probe back unserved (see _dev_resolve_one).

        Returns batches applied by windows the pipe resolved while
        enforcing its depth (the probe itself settles later)."""
        W = self.window
        batch = []
        while (
            self._read_pending
            and len(batch) < W
            and self._read_pending[0][2] <= self._dev_wdisp
        ):
            batch.append(self._read_pending.popleft())
        if not batch:
            return 0
        with device_annotation("rabia.cycle.pack"):
            packed = self._dev.pack_get_window([e[0] for e in batch])
        if packed is None:
            # outside the read envelope (long key, malformed op): put
            # the batch back and demote — the flush below hands every
            # parked read to the host path
            for e in reversed(batch):
                self._read_pending.appendleft(e)
            applied = self._dev_drain_pipe()
            self._demote_device_store()
            return applied
        state_base = self._dev_chain_base()
        with device_annotation("rabia.devkv.read_probe"):
            found_d, ver_d, vlen_d, valw_d = self._dev.lookup_only(
                packed, W=W, state=state_base
            )
        with device_annotation("rabia.cycle.book"):
            self._lat_invalidate |= (
                self._dev.compiled_on_last_call and self._lat_timing
            )
            self.cycles += 1
            depth = len(batch)
            n = self.n_shards
            self._read_stats["probe"] += depth * n
            self._read_stats["probe_windows"] += 1
            self._h_read_batch.observe(float(depth))
            # no flags: nothing decided, nothing to read
            _, meta_fut, val_fut = self._dev_hand_off(
                None, (found_d, ver_d), (vlen_d, valw_d)
            )
            rec = {
                "kind": "read",
                "flags_fut": None,
                "meta_fut": meta_fut,
                "val_dev": (vlen_d, valw_d),
                "val_fut": val_fut,
                # read-only: the chained state passes through untouched
                "new_state": state_base,
                "entries": batch,
                "depth": depth,
                "n": n,
                "seg": None,
                "sver_delta": None,
            }
        return self._dev_push_window(rec)

    def _run_cycle_fullwidth_device_get(self, depth: int) -> int:
        """GET-only full-width windows through the device table's
        read-only lookup program: consensus decides the slots and the
        match gathers (found, version, value) per op in one dispatch —
        no table mutation, no version advance, responses materialize
        lazily from the readback. Anything outside the read envelope
        (long keys, malformed ops) demotes exactly like the write lane.

        Readback is META-ONLY in the steady state: found bits + version
        words (~5 bytes/op). Value bytes resolve from the host-side
        segments/seed (every version a GET can see was packed by this
        host at SET time or seeded at re-promotion — (shard, version)
        is unique content identity). Only when the vectorized
        resolvability check finds an evicted version does the window
        download the value planes (~70 bytes/op, the round-4 cost); once
        a window had to, the next ones fetch theirs on a worker from
        dispatch on (:meth:`_dev_prefetch_values`).

        PIPELINED: the lookup chains on the newest in-flight window's
        output state (reads observe every earlier window's SETs —
        FIFO order), slot bookkeeping advances optimistically, and the
        all_v1 scalar + meta planes are fetched on the worker
        thread; settlement/rollback live in :meth:`_dev_resolve_one`."""
        W = self.window
        n = self.n_shards
        entries = [self._full_blocks[i] for i in range(depth)]
        with device_annotation("rabia.cycle.pack"):
            packed = self._dev.pack_get_window([e[0] for e in entries])
        if packed is None:
            # drain BEFORE demoting so in-flight windows' applied counts
            # reach the caller (demote's internal drain discards them)
            applied = self._dev_drain_pipe()
            self._demote_device_store()
            return applied + self._run_cycle_inner()
        base = np.zeros(self.S, np.int32)
        base[:n] = self.next_slot
        state_base = self._dev_chain_base()
        with device_annotation("rabia.devkv.lookup_window"):
            all_v1_d, found_d, ver_d, vlen_d, valw_d = (
                self._dev.lookup_window(
                    self.alive, base, depth, packed, W=W,
                    max_phases=self.max_phases, state=state_base,
                )
            )
        with device_annotation("rabia.cycle.book"):
            self._lat_invalidate |= (
                self._dev.compiled_on_last_call and self._lat_timing
            )
            self.cycles += 1
            self._read_stats["slot"] += depth * n  # GETs that consumed slots
            self._dev_commit_window(entries, depth)
            flags_fut, meta_fut, val_fut = self._dev_hand_off(
                all_v1_d, (found_d, ver_d), (vlen_d, valw_d)
            )
            rec = {
                "kind": "get",
                "flags_fut": flags_fut,
                "meta_fut": meta_fut,
                "val_dev": (vlen_d, valw_d),
                "val_fut": val_fut,
                # read-only window: the chained state passes through
                "new_state": state_base,
                "entries": entries,
                "depth": depth,
                "n": n,
                "seg": None,
                "sver_delta": None,
            }
        return self._dev_push_window(rec)

    def _run_cycle_fullwidth_device_mixed(self, count: int) -> int:
        """Full-width window MIXING SET and GET ops (per op, via the
        kind-masked fused program): SETs mutate the table, GETs read the
        wave-entry state, one dispatch for the whole window. SET
        response versions derive from the host mirror + the per-shard
        cumulative SET count (clean window ⇒ every SET applied exactly
        once); GET responses in the steady state carry META ONLY — value
        bytes resolve from the host-side segments (this window's SETs
        included, so reads of same-window writes resolve too), with the
        value-plane download kept as the eviction fallback (on a
        readback worker from dispatch on, once a window needed it).

        PIPELINED like the pure-SET lane: the dispatch chains on the
        newest in-flight window's output state, bookkeeping advances
        optimistically, and the flags + GET meta are fetched on
        the worker threads while the next window packs — settlement and
        the dirty-rollback both live in :meth:`_dev_resolve_one` /
        :meth:`_dev_settle_mixed`."""
        W = self.window
        n = self.n_shards
        entries = [self._full_blocks[i] for i in range(count)]
        with device_annotation("rabia.cycle.pack"):
            packed = self._dev.pack_mixed_window([e[0] for e in entries])
        if packed is None:
            # drain BEFORE demoting so in-flight windows' applied counts
            # reach the caller (demote's internal drain discards them)
            applied = self._dev_drain_pipe()
            self._demote_device_store()
            return applied + self._run_cycle_inner()
        kind, ops = packed
        # DEL bumps the shard version only when the key is FOUND — a
        # data-dependent bump the host mirror can't derive until the
        # meta readback (which DEL waves already ride: kind >= 2). Such
        # windows — and every window dispatched while one is in flight,
        # whose mirror base is equally unknown — DEFER version
        # derivation to settlement (_dev_settle_mixed), where FIFO
        # order guarantees the mirror is exact again. The dispatch
        # itself pipelines like any other window; the old design
        # drained the pipe and ran DEL windows synchronously, paying a
        # full readback round trip per window. EXISTS is read-only: its found bit rides
        # the meta plane, it bumps nothing and forces no deferral.
        deferred = bool((kind == 3).any()) or self._dev_defer > 0
        get_waves = np.nonzero((kind >= 2).any(axis=1))[0].astype(np.int32)
        base = np.zeros(self.S, np.int32)
        base[:n] = self.next_slot
        state_base = self._dev_chain_base()
        with device_annotation("rabia.devkv.mixed_apply"):
            new_state, flags_dev, meta_dev, gval_dev = self._dev.mixed_apply(
                self.alive, base, count, kind, get_waves, ops, W=W,
                max_phases=self.max_phases, state=state_base,
            )
        with device_annotation("rabia.cycle.book"):
            self._lat_invalidate |= (
                self._dev.compiled_on_last_call and self._lat_timing
            )
            self.cycles += 1
            # derived SET versions: host mirror + inclusive per-shard SET
            # count (GET waves advance nothing). Deferred windows push a
            # PROVISIONAL segment (empty placeholder range — matches no
            # resolver lookup, exempt from eviction) and leave the mirror
            # untouched; settlement patches range + svers from the exact
            # bump vector (SET always, DEL on found) and advances the
            # mirror then.
            with device_annotation("rabia.cycle.book.versions"):
                # GET ops that rode consensus slots inside the mixed window
                # (kind 2; DEL/EXISTS are not reads for the read-lane
                # counters)
                self._read_stats["slot"] += int((kind == 2).sum())
                is_set = kind == 1  # [count, S]
                set_cum = np.cumsum(is_set, axis=0, dtype=np.int64)
                if deferred:
                    svers = None
                    sver_delta = None
                    seg_start = np.zeros_like(self._dev_sver)
                    seg_end = np.zeros_like(self._dev_sver)
                else:
                    svers = self._dev_sver[None, : self.S] + set_cum
                    seg_start = self._dev_sver.copy()
                    seg_end = seg_start + set_cum[-1]
                    sver_delta = np.zeros_like(self._dev_sver)
                    sver_delta[: self.S] = set_cum[-1]
                    self._dev_sver += sver_delta
            with device_annotation("rabia.cycle.book.segment") as span:
                seg = _MixedSeg(
                    seg_start, seg_end, ops.vlen, ops.vwin,
                    set_cum if deferred else svers, kind,
                )
                if deferred:
                    seg.provisional = True
                    self._dev_defer += 1
                self._dev_push_segment(seg)
                if span is not None:
                    span.set_metadata(bytes=seg.nbytes)
            self._dev_commit_window(entries, count)
            val_dev = (gval_dev,) if len(get_waves) else None
            # meta fetched optimistically alongside the flags (a dirty
            # window wastes one small transfer — the rollback edge); the
            # value plane stays on the device unless the last settle had
            # to download one (then a worker fetches it from here on) or
            # this one's settle has to
            flags_fut, meta_fut, val_fut = self._dev_hand_off(
                flags_dev, (meta_dev,) if len(get_waves) else (), val_dev
            )
            rec = {
                "kind": "mixed",
                "flags_fut": flags_fut,
                "meta_fut": meta_fut,
                "val_dev": val_dev,
                "val_fut": val_fut,
                "new_state": new_state,
                "entries": entries,
                "depth": count,
                "n": n,
                "kind_rows": kind,
                "svers": svers,
                "get_waves": get_waves,
                "seg": seg,
                "sver_delta": sver_delta,
                "deferred": deferred,
            }
        return self._dev_push_window(rec)

    def _dev_push_segment(self, seg) -> None:
        """Retain one committed device window's value bytes (a
        :class:`_RowSeg` / :class:`_MixedSeg`).

        ``seg.start``/``seg.end`` bound the shard versions the window
        assigned (start[s] < v <= end[s]). Eviction (byte cap) raises
        ``_dev_floor`` — evicted versions become seed-only, and the GET
        path's resolvability check falls back to a value-plane download
        for them instead of mis-answering."""
        self._dev_vseg.append(seg)
        self._dev_vseg_bytes += seg.nbytes
        self._dev_evict_segments()

    def _dev_evict_segments(self) -> None:
        """Enforce the segment byte cap, oldest first. Provisional
        segments (in-flight deferred windows — contiguous at the newest
        end) are exempt: their exact version range is unknown until
        settlement patches them, and a wrong ``end`` would corrupt the
        floor; settlement re-runs this loop once they are exact."""
        while (
            self._dev_vseg_bytes > self._dev_vseg_cap
            and len(self._dev_vseg) > 1
            and not self._dev_vseg[0].provisional
        ):
            old = self._dev_vseg.popleft()
            self._dev_vseg_bytes -= old.nbytes
            np.maximum(self._dev_floor, old.end, out=self._dev_floor)

    def _dev_make_resolver(self) -> _SegResolver:
        """Snapshot resolver over the CURRENT segments + seed epoch —
        one per settled window, shared by its frame views. Only built
        after the vectorized resolvability check, so a miss inside a
        settled view is a logic error, not a runtime condition."""
        return _SegResolver(tuple(self._dev_vseg), self._dev_seed)

    def _dev_resolve(self, s: int, ver: int) -> bytes:
        """Value bytes for (shard, version) against the live engine
        state (test/debug convenience; settled views carry snapshots)."""
        return self._dev_make_resolver()(s, ver)

    def _dev_unresolvable(self, found: np.ndarray, ver: np.ndarray) -> bool:
        """True when ANY found (wave, shard) op's version cannot be
        resolved host-side — the caller then downloads the value planes
        for this window (graceful eviction fallback). Vectorized: only
        versions at or below the floor consult the seed index."""
        cand = found & (ver <= self._dev_floor[None, : ver.shape[1]])
        if not bool(cand.any()):
            return False
        if len(self._dev_seed_keys) == 0:
            return True
        t_idx, s_idx = np.nonzero(cand)
        keys = (s_idx.astype(np.int64) << 32) | ver[t_idx, s_idx].astype(
            np.int64
        )
        pos = np.searchsorted(self._dev_seed_keys, keys)
        pos = np.minimum(pos, len(self._dev_seed_keys) - 1)
        return not bool(np.all(self._dev_seed_keys[pos] == keys))

    def _dev_reindex_seed(self) -> None:
        self._dev_seed_keys = np.sort(
            np.fromiter(
                ((s << 32) | v for (s, v) in self._dev_seed),
                np.int64,
                len(self._dev_seed),
            )
        )

    @property
    def device_lane_active(self) -> bool:
        """True while the device-resident KV lane is serving windows
        (``device_store=True`` and the content is inside the lane's
        envelope). The public twin of the internal ``_dev_active`` flag
        for drivers/ops tooling."""
        return self._dev_active

    def sync_to_host(self) -> None:
        """Materialize the device KV table into every replica's host
        store for inspection (drains the in-flight window pipe first).

        Implemented as a lane demotion: the device table is downloaded
        once and fanned into the host stores, and the engine re-promotes
        automatically after ``device_store_repromote`` clean full-width
        cycles. Host-lane (or non-device) engines are already in sync —
        a no-op."""
        self._demote_device_store()

    def _demote_device_store(self) -> None:
        """Leave device-store mode: the device table becomes the host
        replica stores' content (rebuilt from scratch — in device mode
        the host replicas saw none of the applies)."""
        if not self._dev_active:
            return
        if self._dev_pipe:
            # the sync-down below must see the SETTLED table: resolve
            # every in-flight window first (a dirty one rolls the pipe
            # back and re-enters this method with an empty pipe)
            self._dev_drain_pipe()
            if not self._dev_active:
                return
        # a lane switch DURING a timed cycle voids that cycle's latency
        # sample; from outside a cycle (submit-path demotions) there is
        # no sample in flight to void
        self._lat_invalidate |= self._lat_timing
        self._dev_active = False
        # device-era settle samples must not read as live latency from
        # the host path (re-promotion starts a fresh window population)
        self._lat_settle.clear()
        self._dev_cooldown = self._dev_repromote  # earn the way back
        if self._dev_fetcher_pool is not None:
            # host mode needs no flags worker; re-promotion recreates it
            self._dev_fetcher_pool.shutdown(wait=False)
            self._dev_fetcher_pool = None
        # parked reads leave with the lane: re-enter them as ordinary
        # full-width blocks at the BACK of the staged stream (behind
        # any rolled-back writes — all still unsettled, so the order
        # is linearizable); the host GET path serves them
        while self._read_pending:
            block, bfut, _barrier = self._read_pending.popleft()
            shards = np.asarray(block.shards, np.int64)
            inv = self._block_inv(shards, self._is_identity(shards))
            self._full_blocks.append((block, bfut, inv))
        d = self._dev.dump()  # ONE table materialization for all replicas
        for sm in self.sms:
            self._dev.sync_into(sm, dump=d)
        logger.info(
            "device KV lane demoted to host stores (%d entries)",
            len(d["shards"]),
        )

    def _try_repromote_device_store(self) -> None:
        """Climb back onto the device lane after a demotion: rebuild the
        device table from replica 0's store (all replicas are equal — a
        divergence is already counted/handled by the apply path) and
        re-arm. Declines (outside the envelope: long keys, wide values,
        per-shard overflow) re-arm the cool-down and retry later —
        deletes/GC can bring the content back inside."""
        # pre-screen the WORKLOAD before paying the table upload: if the
        # very next window would demote again (e.g. a steady GET-bearing
        # stream), re-promoting would thrash a full upload+dump round
        # trip every cool-down period for zero device windows
        head = [self._full_blocks[0][0]] if self._full_blocks else []
        if head and self._dev.pack_mixed_window(head) is None:
            # mixed packer: SET, GET and interleaved heads all run
            # in-lane now; only genuinely out-of-envelope work declines
            self._dev_cooldown = self._dev_repromote
            return
        seed_epoch: dict = {}
        if self._dev.upload_from(self.sms[0], seed_cache=seed_epoch):
            self._dev_seed = seed_epoch
            self._dev_sver[:] = 0
            sv = self.sms[0].store.shard_version[: self.n_shards]
            self._dev_sver[: self.n_shards] = sv
            # versions at or below the promotion snapshot resolve via
            # the seed (just refilled with the uploaded content);
            # versions assigned by the host DURING the demotion that
            # were overwritten before re-promotion are unreachable
            np.maximum(
                self._dev_floor[: self.n_shards],
                sv.astype(np.int64),
                out=self._dev_floor[: self.n_shards],
            )
            self._dev_reindex_seed()
            self._dev_active = True
            # re-arm the read-lane write barrier: the staged (not yet
            # dispatched) blocks are the only writes a fresh read must
            # wait behind
            self._dev_wseq = len(self._full_blocks)
            self._dev_wdisp = 0
            self._lat_invalidate |= self._lat_timing  # upload, not latency
            logger.info("device KV lane re-promoted from host stores")
        else:
            self._dev_cooldown = self._dev_repromote

    def _run_cycle_fullwidth(self) -> int:
        """Vectorized happy path: the pending work is a FIFO of
        full-width blocks (every shard covered once per block) and no
        per-shard entries. One dispatch decides ``depth`` uniform waves;
        fault-free (all V1) the bookkeeping is pure numpy — no per-slot
        Python objects at all: slot counters advance by array add, the
        decision log records one RANGE entry per wave, and each block's
        future settles in one call. Any non-V1 outcome demotes the blocks
        to the per-shard queues and defers to the general path."""
        W = self.window
        n = self.n_shards
        depth = min(len(self._full_blocks), W)
        self._lat_saturated |= len(self._full_blocks) >= self._rung_above(W)
        base = np.zeros(self.S, np.int32)
        base[:n] = self.next_slot
        if self._multi:
            # multi-controller SPMD: inputs must assemble through
            # make_array_from_callback + allgather (no speculation — the
            # blocking collective IS the step)
            decided = self._decide_window(self._fullwidth_votes(depth), W)
            return self._finish_cycle_fullwidth(decided, depth)
        key = (depth, base.tobytes(), self.alive.tobytes())
        if self._spec is not None and self._spec[0] == key:
            dev = self._spec[1]  # the previous cycle already dispatched us
        else:
            dev = self._dispatch_window(self._fullwidth_votes(depth), base, W)
        self._spec = None
        self.cycles += 1  # one CONSUMED window (discarded specs don't count)
        # dispatch the NEXT window before this one's readback: its inputs
        # assume this window decides all-V1 (exactly the full-width happy
        # path), so device compute overlaps the readback + host apply
        # below; a fault outcome just discards it (deterministic kernel —
        # re-deciding later with the true base slots is harmless)
        if len(self._full_blocks) > depth:
            sdepth = min(len(self._full_blocks) - depth, W)
            sbase = base.copy()
            sbase[:n] += depth
            skey = (sdepth, sbase.tobytes(), self.alive.tobytes())
            sdev = self._dispatch_window(
                self._fullwidth_votes(sdepth), sbase, W
            )
            try:
                # queue the device->host transfer behind the compute so the
                # decided plane is already on host when the next cycle
                # reads it (the transfer latency hides under this cycle's
                # apply)
                sdev.copy_to_host_async()
            except AttributeError:
                pass
            self._spec = (skey, sdev)
        return self._finish_cycle_fullwidth(np.asarray(dev), depth)

    def _finish_cycle_fullwidth(self, decided: np.ndarray, depth: int) -> int:
        """Bookkeeping + apply for a decided full-width window."""
        n = self.n_shards
        if not bool((decided[:depth, :n] == V1).all()):
            # faults interrupted the uniform wave: re-run through the
            # general path with the SAME (deterministically re-decided)
            # votes — demotion preserves per-shard FIFO order
            self._demote_full_blocks()
            return self._run_cycle_inner()  # second dispatch; cycles counts both
        entries = [self._full_blocks.popleft() for _ in range(depth)]
        start = self.next_slot.copy()
        self.next_slot[:n] += depth
        self.decided_v1 += depth * n
        for t, (block, bfut, inv) in enumerate(entries):
            self._bulk_log.append((start, t, block, inv))
        while len(self._bulk_log) > max(
            1, self.max_decision_history // max(1, self.window)
        ):
            self._bulk_log.popleft()
        if len(entries) == 1 or not self._apply_entries_multi(entries):
            for block, bfut, inv in entries:
                idxs = np.arange(len(block))
                self._apply_block_group(block, idxs, None, bulk_future=bfut)
        return depth * n

    def _fullwidth_votes(self, depth: int) -> np.ndarray:
        """Initial votes for ``depth`` uniform full-width waves."""
        votes = np.zeros((self.window, self.S, self.R), np.int8)
        votes[:depth, : self.n_shards, :] = V1
        return votes

    def _demote_full_blocks(self) -> None:
        """Move staged full-width blocks onto the per-shard queues (the
        general path's representation), preserving submission order."""
        self._lat_invalidate |= self._lat_timing  # void only mid-cycle
        self._spec = None  # speculated on the full-width lane's slots
        while self._full_blocks:
            block, bfut, _inv = self._full_blocks.popleft()
            for i, s in enumerate(block.shards.tolist()):
                self.queues[s].append(
                    _Pending(None, None, block=block, bidx=i, bfut=bfut)
                )
                self._queued_entries += 1

    def _decide_window(self, votes: np.ndarray, W: int) -> np.ndarray:
        """One consumed consensus window; returns decided i8[W, S]."""
        base = np.zeros(self.S, np.int32)
        base[: self.n_shards] = self.next_slot
        if self._multi:
            decided = self._run_window_multihost(votes, base, W)
        else:
            decided = np.asarray(self._dispatch_window(votes, base, W))
        self.cycles += 1
        return decided

    def _dispatch_window(self, votes: np.ndarray, base: np.ndarray, W: int):
        """Enqueue one slot_window dispatch; returns the UNmaterialized
        device plane (JAX dispatch is async — the caller blocks only at
        ``np.asarray``, which is what the full-width lane exploits to
        overlap the next window's compute with this one's apply). The
        caller accounts ``cycles`` when a window is CONSUMED — a
        discarded speculative dispatch is not a cycle."""
        import jax.numpy as jnp

        return self.kernel.slot_window(
            jnp.asarray(votes),
            self.kernel.place(jnp.asarray(self.alive)),
            jnp.asarray(base),
            n_slots=W,
            max_phases=self.max_phases,
        )

    def _run_window_multihost(
        self, votes: np.ndarray, base: np.ndarray, W: int
    ) -> np.ndarray:
        """One consensus window as a multi-controller SPMD step: inputs
        assembled from each process's addressable shards, the decided
        plane re-replicated to every host."""
        import jax
        from jax.experimental import multihost_utils
        from jax.sharding import NamedSharding, PartitionSpec as P

        def put(arr, spec):
            sharding = NamedSharding(self.mesh, spec)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx]
            )

        decided = self.kernel.slot_window(
            put(votes.astype(np.int8), P(None, "shard", "replica")),
            put(self.alive, P("shard", "replica")),
            put(base.astype(np.int32), P("shard")),
            n_slots=W,
            max_phases=self.max_phases,
        )
        return np.asarray(
            multihost_utils.process_allgather(decided, tiled=True)
        )

    def _record(
        self, s: int, slot: int, value: int, batch: Optional[CommandBatch]
    ) -> None:
        d = self.decisions[s]
        d[slot] = (value, batch)
        if value == V1:
            self.decided_v1 += 1
        else:
            self.decided_v0 += 1
        while len(d) > self.max_decision_history:
            del d[next(iter(d))]  # insertion order is slot order: O(1) trim

    def _apply_waves_scalar(
        self, waves: list[list[tuple[int, int, _Pending]]]
    ) -> None:
        for wave in waves:
            for s, slot, pend in wave:
                batch = pend.materialize()
                responses = None
                err: Optional[Exception] = None
                for i, sm in enumerate(self.sms):
                    try:
                        r = sm.apply_batch(batch)
                    except Exception as e:  # deterministic app failure
                        if i == 0:
                            err = RabiaError(f"apply failed: {e}")
                        r = None
                    if i == 0:
                        responses = r
                    elif r != responses:
                        # a committed batch MUST apply identically on
                        # every replica — a differing outcome means the
                        # state machines have diverged (non-determinism
                        # or an earlier partial failure)
                        self.divergences += 1
                        logger.error(
                            "replica %d diverged applying batch %s on "
                            "shard %d slot %d: %r != %r",
                            i, batch.id.short(), s, slot, r, responses,
                        )
                pend.settle(err if err is not None else responses)

    def _apply_waves_bulk(
        self, waves: list[list[tuple[int, int, _Pending]]]
    ) -> None:
        """One apply_block call per (source block, window position) per
        replica — submitted blocks apply with zero repacking; scalar
        batches are packed into a synthesized block per wave."""
        from rabia_tpu.core.blocks import build_block

        for wave in waves:
            if not wave:
                continue
            # group block-sourced entries by their source block (the
            # common case is ONE submitted block covering the whole wave)
            by_block: dict[int, list[_Pending]] = {}
            loose: list[tuple[int, int, _Pending]] = []
            for e in wave:
                p = e[2]
                if p.block is not None:
                    by_block.setdefault(id(p.block), []).append(p)
                else:
                    loose.append(e)
            for group in by_block.values():
                block = group[0].block
                idxs = np.fromiter(
                    (p.bidx for p in group), np.int64, len(group)
                )
                self._apply_block_group(
                    block, idxs, [p.settle for p in group]
                )

            if not loose:
                continue
            # blocks carry >=1 command per covered shard; empty batches
            # (legal no-op commits) go through the scalar path
            bulk = [e for e in loose if len(e[2].batch.commands)]
            if len(bulk) != len(loose):
                self._apply_waves_scalar(
                    [[e for e in loose if not len(e[2].batch.commands)]]
                )
            if not bulk:
                continue
            shards = [s for s, _slot, _p in bulk]
            cmds = [
                [c.data for c in p.batch.commands] for _s, _slot, p in bulk
            ]
            try:
                block = build_block(shards, cmds)
            except Exception:
                # a batch the block codec rejects must not poison the
                # whole wave: apply it (and the rest) per batch instead
                logger.exception("bulk wave fell back to scalar apply")
                self._apply_waves_scalar([bulk])
                continue
            self._apply_block_group(
                block,
                np.arange(len(bulk)),
                [p.settle for _s, _slot, p in bulk],
            )

    def _apply_entries_multi(self, entries: list) -> bool:
        """Apply a whole full-width cycle's decided blocks with ONE
        state-machine call per replica (``apply_block_multi`` — the
        vector store concatenates the waves into a single vectorized
        pass). Returns False when the SMs lack the interface; the caller
        then falls back to per-block applies."""
        if not all(
            callable(getattr(sm, "apply_block_multi", None))
            for sm in self.sms
        ):
            return False
        blocks = [e[0] for e in entries]
        idxs_list = [np.arange(len(b)) for b in blocks]
        results: list = []  # per replica: result list, or the raised error
        for i, sm in enumerate(self.sms):
            try:
                results.append(
                    sm.apply_block_multi(
                        blocks, idxs_list, want_responses=(i == 0)
                    )
                )
            except Exception as e:  # deterministic app failure
                results.append(e)
        lead = results[0]
        # divergence accounting: a follower disagreeing with replica 0 on
        # group failure, or (where per-wave outcomes exist) on any wave's
        # failure-ness, has diverged
        for i, r in enumerate(results[1:], 1):
            if isinstance(r, Exception) != isinstance(lead, Exception):
                self.divergences += 1
                logger.error(
                    "replica %d %s a wave group replica 0 %s",
                    i,
                    "rejected" if isinstance(r, Exception) else "applied",
                    "applied" if isinstance(r, Exception) else "rejected",
                )
            elif isinstance(r, list) and isinstance(lead, list):
                for j in range(len(entries)):
                    if isinstance(r[j], Exception) != isinstance(
                        lead[j], Exception
                    ):
                        self.divergences += 1
                        logger.error(
                            "replica %d diverged on wave %d of a group", i, j
                        )
        # settlement follows replica 0's outcomes (per wave when they
        # exist — waves that committed keep their real responses even if a
        # later wave in the group failed)
        for j, (block, bfut, _inv) in enumerate(entries):
            if isinstance(lead, Exception) or lead is None:
                out = RabiaError(f"apply failed: {lead}")
                bfut._settle_bulk([out] * len(block))
            elif isinstance(lead[j], Exception):
                out = RabiaError(f"apply failed: {lead[j]}")
                bfut._settle_bulk([out] * len(block))
            else:
                bfut._settle_bulk(lead[j])
        return True

    def _apply_block_group(
        self, block, idxs, settles, bulk_future: Optional[MeshBlockFuture] = None
    ) -> None:
        responses = None
        err: Optional[Exception] = None
        for i, sm in enumerate(self.sms):
            failed = False
            try:
                r = sm.apply_block(block, idxs, want_responses=(i == 0))
            except Exception as e:  # deterministic app failure
                failed = True
                if i == 0:
                    err = RabiaError(f"apply failed: {e}")
                elif err is None:
                    # replica 0 succeeded but a follower failed: that IS
                    # divergence. (All replicas failing identically is a
                    # deterministic app error, not divergence — matching
                    # the scalar path's accounting.)
                    self.divergences += 1
                    logger.error(
                        "replica %d failed bulk apply of block %s: %s",
                        i, block.id, e,
                    )
                r = None
            if i == 0:
                responses = r
            elif not failed and err is not None:
                # the mirror-image divergence: a follower applied a wave
                # replica 0 rejected — its state mutated alone
                self.divergences += 1
                logger.error(
                    "replica %d applied block %s that replica 0 rejected",
                    i, block.id,
                )
        if err is not None or responses is None:
            fail = err if err is not None else RabiaError("apply failed")
            if bulk_future is not None:
                bulk_future._settle_bulk([fail] * len(idxs))
            else:
                for settle in settles:
                    settle(fail)
        elif bulk_future is not None:
            bulk_future._settle_bulk(responses)
        else:
            for j, settle in enumerate(settles):
                settle(responses[j])

    def flush(self, max_cycles: int = 1000) -> int:
        """Run cycles until every queue drains (or quorum stalls progress).

        Returns total batches applied. Raises if ``max_cycles`` elapse with
        work still queued (quorum loss — heal a replica and call again).
        """
        total = 0
        for _ in range(max_cycles):
            if not self._has_pending():
                return total
            got = self.run_cycle()
            total += got
            if got == 0 and not self.has_quorum:
                raise RabiaError("quorum lost: flush stalled")
        if self._has_pending():
            raise RabiaError(f"flush incomplete after {max_cycles} cycles")
        return total

    def _has_pending(self) -> bool:
        return bool(
            self._queued_entries
            or self._full_blocks
            or self._read_pending
            or (self._dev is not None and self._dev_pipe)
        )

    def read_lane_stats(self) -> dict:
        """Read-index lane counters (the ``rabia_devkv_read_*`` family
        as a plain dict): ops served off-consensus (``probe``), GETs
        that consumed consensus slots (``slot``), value-plane download
        events (``fallback``), probe windows dispatched
        (``probe_windows``)."""
        return dict(self._read_stats)

    # -- checkpoint / restore ------------------------------------------------

    def checkpoint(self):
        """Durable snapshot of the committed log position + state
        (the transport engine's PersistedEngineState, same shape)."""
        from rabia_tpu.core.persistence import PersistedEngineState

        if self._dev_active:
            self._dev_drain_pipe()  # snapshot the SETTLED table
        if self._dev_active:
            # the device table is authoritative in device mode: reflect
            # it into the host replicas so the snapshot below sees it
            # (device mode stays active; the host copies are snapshots)
            d = self._dev.dump()
            for sm in self.sms:
                self._dev.sync_into(sm, dump=d)

        return PersistedEngineState(
            current_phase=int(self.next_slot.max(initial=0)),
            last_committed_phase=int(self.next_slot.sum()),
            state_version=self.decided_v1,
            snapshot=self.sms[0].create_snapshot(),
            per_shard_phase=self.next_slot.tolist(),
            per_shard_committed=self.next_slot.tolist(),
            per_shard_version=[],
        )

    def restore(self, state) -> None:
        """Adopt a checkpoint into a FRESH engine (empty queues): every
        replica state machine restores the snapshot; slot counters resume
        where the checkpoint left off."""
        if self._has_pending():
            raise RabiaError("restore requires an idle engine")
        self._spec = None  # speculated on pre-restore slot counters
        # a restored snapshot supersedes any device-lane state: continue
        # on the host path (no sync — the checkpoint IS the state); the
        # re-promotion path may climb back after the usual cool-down.
        # Pre-restore settle samples die with the lane (stats are also
        # gated on _dev_active, but a re-promotion must not mix them
        # into its fresh window population)
        self._dev_active = False
        self._lat_settle.clear()
        self._dev_cooldown = self._dev_repromote
        committed = np.asarray(
            state.per_shard_committed[: self.n_shards], np.int64
        )
        self.next_slot[: len(committed)] = committed
        if state.snapshot is not None:
            for sm in self.sms:
                sm.restore_snapshot(state.snapshot)
        self.decided_v1 = int(state.state_version)
        # drop any pre-restore decision history: rewound slot numbers will
        # be re-decided, and stale entries would contradict the new log
        self._bulk_log.clear()
        for d in self.decisions:
            d.clear()

    async def save_to(self, persistence) -> None:
        await persistence.save_engine_state(self.checkpoint())

    async def load_from(self, persistence) -> bool:
        state = await persistence.load_engine_state()
        if state is None:
            return False
        self.restore(state)
        return True

    # -- introspection -------------------------------------------------------

    def decisions_for(self, shard: int) -> dict[int, tuple[int, Optional[CommandBatch]]]:
        """Committed decision log: slot -> (value, batch). ``batch`` is
        None only for V0 null slots; block-lane commits materialize their
        batch from the (log-retained) source block on access. Full-width
        waves live range-compressed in ``_bulk_log`` and expand here."""
        out: dict[int, tuple[int, Optional[CommandBatch]]] = {}
        for start, t, block, inv in self._bulk_log:
            out[int(start[shard]) + t] = (
                V1,
                block.materialize_batch(int(inv[shard])),
            )
        for slot, (v, b) in self.decisions[shard].items():
            if isinstance(b, tuple):
                b = b[0].materialize_batch(b[1])
            out[slot] = (v, b)
        return dict(sorted(out.items()))  # iteration order = slot order

    def throughput(
        self, batches_per_shard: int = 4, commands_per_batch: int = 1
    ) -> dict:
        """Measure end-to-end decisions/s (consensus + apply + futures)."""
        payload = [b"x" * 16] * commands_per_batch
        for _ in range(batches_per_shard):
            for s in range(self.n_shards):
                self.submit(payload, s)
        t0 = time.perf_counter()
        applied = self.flush()
        dt = time.perf_counter() - t0
        return {
            "applied": applied,
            "elapsed_s": dt,
            "decisions_per_sec": applied / dt if dt > 0 else float("inf"),
        }

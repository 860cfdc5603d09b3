"""Device-mesh execution: shard-axis pjit + replica-axis collectives.

The distributed communication backend of SURVEY.md §5.8's *device plane*:
within a slice, consensus replicas map onto a mesh axis and a round's vote
exchange is ONE ``all_gather`` over that axis — replacing the reference's
N×(N−1) TCP unicasts per round (tcp.rs:771-789) with a single ICI
collective. The shard axis is data-parallel: S independent consensus
instances partitioned across devices.

Two executors:

:class:`ShardedClusterKernel`
    A :class:`~rabia_tpu.kernel.phase_driver.ClusterKernel` whose state
    lives sharded over the mesh's shard axis (NamedSharding); every jitted
    step then runs SPMD across devices with **zero** cross-device traffic
    (shards are independent) — pure scale-out.

:class:`MeshPhaseKernel`
    Lockstep replica-parallel weak MVC via ``shard_map``: each device owns a
    block of (shard, replica) state; one ``phase_step`` = R1 all_gather →
    R2 vote → R2 all_gather → decide/advance, i.e. one full MVC phase in two
    collectives. Fault-free it is decision-identical to
    ``ClusterKernel.slot_pipeline`` with ``rounds_per_slot=2`` (conformance
    gate, SURVEY.md §7.4.6).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rabia_tpu.core.types import ABSENT, V0, V1, VQUESTION, f_plus_1, quorum_size
from rabia_tpu.kernel.phase_driver import ClusterKernel, ClusterState, _coin_bits

I8 = jnp.int8
I32 = jnp.int32

SHARD_AXIS = "shard"
REPLICA_AXIS = "replica"


def make_mesh(
    devices: Optional[Sequence] = None,
    shard_axis_size: Optional[int] = None,
    replica_axis_size: int = 1,
) -> Mesh:
    """Build a 2D (shard × replica) device mesh.

    Defaults: all available devices on the shard axis (replica axis 1 —
    replicas vmapped within each device, the simulation mode). Axis sizes
    must multiply to the device count.

    Multi-host: after ``jax.distributed.initialize()``, ``jax.devices()``
    spans every host's chips and the same call builds a cross-host mesh —
    replica-axis all_gathers then ride ICI within a slice and DCN across
    slices, with no code changes here (standard JAX multi-host SPMD; lay
    the replica axis within a slice so vote exchange stays on ICI).
    """
    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs)
    if shard_axis_size is None:
        shard_axis_size = n // replica_axis_size
    if shard_axis_size * replica_axis_size != n:
        raise ValueError(
            f"mesh {shard_axis_size}x{replica_axis_size} != {n} devices"
        )
    arr = np.array(devs).reshape(shard_axis_size, replica_axis_size)
    return Mesh(arr, (SHARD_AXIS, REPLICA_AXIS))


# ---------------------------------------------------------------------------
# Shard-axis data parallelism over ClusterKernel
# ---------------------------------------------------------------------------

# ClusterState field -> which dim is the shard axis (all leading)
_CLUSTER_SPECS = {
    "slot": P(SHARD_AXIS),
    "phase": P(SHARD_AXIS, None),
    "stage": P(SHARD_AXIS, None),
    "my_r1": P(SHARD_AXIS, None),
    "my_r2": P(SHARD_AXIS, None),
    "prev_r1": P(SHARD_AXIS, None),
    "prev_r2": P(SHARD_AXIS, None),
    "led1": P(SHARD_AXIS, None, None),
    "led2": P(SHARD_AXIS, None, None),
    "decided": P(SHARD_AXIS),
    "decided_phase": P(SHARD_AXIS),
    "done": P(SHARD_AXIS, None),
    "active": P(SHARD_AXIS),
}


class ShardedClusterKernel(ClusterKernel):
    """ClusterKernel with state partitioned over the mesh's shard axis.

    Placement is by data: state arrays carry NamedShardings, and every
    inherited jitted step follows them (XLA partitions the elementwise
    program with no communication — shards never interact).
    """

    def __init__(
        self,
        n_shards: int,
        n_replicas: int,
        mesh: Mesh,
        *,
        coin_p1: float = 0.5,
        seed: int = 0,
    ):
        if n_shards % mesh.shape[SHARD_AXIS] != 0:
            raise ValueError(
                f"n_shards {n_shards} not divisible by shard axis "
                f"{mesh.shape[SHARD_AXIS]}"
            )
        super().__init__(n_shards, n_replicas, coin_p1=coin_p1, seed=seed)
        self.mesh = mesh

    def _shard_state(self, state: ClusterState) -> ClusterState:
        placed = {
            f: jax.device_put(
                getattr(state, f), NamedSharding(self.mesh, spec)
            )
            for f, spec in _CLUSTER_SPECS.items()
        }
        return ClusterState(**placed)

    def init_state(self) -> ClusterState:
        return self._shard_state(super().init_state())

    def place_votes(self, votes: jnp.ndarray) -> jnp.ndarray:
        """Shard an [T, S, R] (or [S, R]) initial-vote array over S."""
        spec = (
            P(None, SHARD_AXIS, None) if votes.ndim == 3 else P(SHARD_AXIS, None)
        )
        return jax.device_put(votes, NamedSharding(self.mesh, spec))


# ---------------------------------------------------------------------------
# Replica-axis collectives (shard_map)
# ---------------------------------------------------------------------------


class MeshPhaseState(NamedTuple):
    """Lockstep replica-parallel state: (shard, replica)-partitioned."""

    slot: jnp.ndarray  # i32[S, R] (same value across R; lives with replicas)
    phase: jnp.ndarray  # i32[S, R]
    my_r1: jnp.ndarray  # i8[S, R]
    decided: jnp.ndarray  # i8[S, R]  (each replica's view; ABSENT until known)


class MeshPhaseKernel:
    """One full weak-MVC phase per step, replicas exchanged by all_gather.

    Lockstep model: every live replica participates in each phase and
    delivery is reliable within the collective (a crashed replica is an
    ``alive`` mask row — its contributions are masked out of the tally).
    This is the ICI/DCN production mode of SURVEY.md §5.8: one all_gather
    per round instead of per-peer unicasts.
    """

    def __init__(
        self,
        n_shards: int,
        n_replicas: int,
        mesh: Mesh,
        *,
        coin_p1: float = 0.5,
        seed: int = 0,
    ):
        self.S = int(n_shards)
        self.R = int(n_replicas)
        self.mesh = mesh
        self.quorum = quorum_size(self.R)
        self.f1 = f_plus_1(self.R)
        self.coin_p1 = float(coin_p1)
        self.seed = int(seed)
        if self.S % mesh.shape[SHARD_AXIS] != 0:
            raise ValueError("n_shards not divisible by shard axis")
        if self.R % mesh.shape[REPLICA_AXIS] != 0:
            raise ValueError("n_replicas not divisible by replica axis")
        self._sr = P(SHARD_AXIS, REPLICA_AXIS)
        self._spec_state = MeshPhaseState(self._sr, self._sr, self._sr, self._sr)

    def init_state(self, initial_votes: jnp.ndarray) -> MeshPhaseState:
        """Start slot 0 on every shard with the given i8[S, R] R1 votes."""
        sr = NamedSharding(self.mesh, self._sr)
        place = lambda a: jax.device_put(a, sr)
        S, R = self.S, self.R
        return MeshPhaseState(
            slot=place(jnp.zeros((S, R), I32)),
            phase=place(jnp.zeros((S, R), I32)),
            my_r1=place(jnp.asarray(initial_votes, I8)),
            decided=place(jnp.full((S, R), ABSENT, I8)),
        )

    @functools.partial(jax.jit, static_argnums=0)
    def phase_step(
        self, state: MeshPhaseState, alive: jnp.ndarray, shard_index: jnp.ndarray
    ) -> MeshPhaseState:
        """One MVC phase for every (shard, replica): two all_gathers.

        ``alive``: bool[S, R] (sharded like the state); ``shard_index``:
        i32[S, R] global shard ids (for the common coin).
        """
        mesh = self.mesh
        Q, F1 = self.quorum, self.f1
        seed, p1 = self.seed, self.coin_p1

        def step_block(slot, phase, my_r1, decided, alive_b, shard_idx):
            # blocks: [S_blk, R_blk]
            undecided = decided == ABSENT
            # ---- round 1: exchange votes over the replica axis ----------
            # all_gather over REPLICA_AXIS concatenates the R_blk columns of
            # every device in the replica row -> full [S_blk, R] sender set
            r1_all = lax.all_gather(
                jnp.where(alive_b & undecided, my_r1, I8(ABSENT)),
                REPLICA_AXIS,
                axis=1,
                tiled=True,
            )  # [S_blk, R]
            c0 = jnp.sum(r1_all == V0, axis=-1, dtype=I32)[:, None]
            c1 = jnp.sum(r1_all == V1, axis=-1, dtype=I32)[:, None]
            r2 = jnp.where(
                c1 >= Q, I8(V1), jnp.where(c0 >= Q, I8(V0), I8(VQUESTION))
            ) * jnp.ones_like(my_r1)
            # ---- round 2: exchange R2 votes ------------------------------
            r2_all = lax.all_gather(
                jnp.where(alive_b & undecided, r2, I8(ABSENT)),
                REPLICA_AXIS,
                axis=1,
                tiled=True,
            )
            d0 = jnp.sum(r2_all == V0, axis=-1, dtype=I32)[:, None]
            d1 = jnp.sum(r2_all == V1, axis=-1, dtype=I32)[:, None]
            decide1 = d1 >= F1
            decide0 = d0 >= F1
            coin = _coin_bits(seed, shard_idx, slot, phase, p1)
            next_v = jnp.where(
                decide1,
                I8(V1),
                jnp.where(
                    decide0,
                    I8(V0),
                    jnp.where(d1 > 0, I8(V1), jnp.where(d0 > 0, I8(V0), coin)),
                ),
            )
            newly = (decide1 | decide0) & undecided & alive_b
            dec_val = jnp.where(decide1, I8(V1), I8(V0))
            decided = jnp.where(newly, dec_val, decided)
            phase = jnp.where(undecided & alive_b, phase + 1, phase)
            my_r1 = jnp.where(undecided & alive_b, next_v, my_r1)
            return slot, phase, my_r1, decided

        stepped = shard_map(
            step_block,
            mesh=mesh,
            in_specs=(self._sr,) * 6,
            out_specs=(self._sr,) * 4,
        )(state.slot, state.phase, state.my_r1, state.decided, alive, shard_index)
        return MeshPhaseState(*stepped)

    def _shard_index_grid(self) -> jnp.ndarray:
        """i32[S, R] global shard ids (the coin's shard coordinate)."""
        return jnp.broadcast_to(
            jnp.arange(self.S, dtype=I32)[:, None], (self.S, self.R)
        )

    def shard_index_array(self) -> jnp.ndarray:
        """i32[S, R] global shard ids, placed like the state."""
        return jax.device_put(
            self._shard_index_grid(), NamedSharding(self.mesh, self._sr)
        )

    def place(self, arr: jnp.ndarray) -> jnp.ndarray:
        return jax.device_put(arr, NamedSharding(self.mesh, self._sr))

    @functools.partial(
        jax.jit,
        static_argnums=(0, 3, 4, 5),
        static_argnames=("n_slots", "max_phases", "start_slot_index"),
    )
    def slot_pipeline(
        self,
        initial_votes: jnp.ndarray,  # i8[T, S, R] per-slot initial R1 votes
        alive: jnp.ndarray,  # bool[S, R]
        n_slots: int,
        max_phases: int = 4,
        start_slot_index: int = 0,
    ) -> jnp.ndarray:
        """Decide ``n_slots`` consecutive slots for all shards ON THE MESH:
        scan over slots, ``max_phases`` collective phases each (one phase
        suffices fault-free; extra phases absorb split initial votes via
        the common coin). The device-plane twin of
        ``ClusterKernel.slot_pipeline`` — every phase's vote exchange is
        two ``all_gather``s over the replica axis instead of N×(N−1)
        transport messages (SURVEY.md §5.8).

        Returns ``decided i8[T, S]`` (the agreed value per slot per shard;
        ABSENT only if a shard failed to decide within ``max_phases`` —
        callers re-run such shards with a deeper window).

        ``start_slot_index`` offsets the slot numbering (and therefore the
        common-coin stream) exactly like ``ClusterKernel.slot_pipeline`` —
        successive windows MUST pass their log position or cross-window
        coins would repeat.
        """
        shard_idx = self._shard_index_grid()

        def per_slot(slot_no, slot_votes):
            st = MeshPhaseState(
                slot=jnp.full((self.S, self.R), slot_no, I32),
                phase=jnp.zeros((self.S, self.R), I32),
                my_r1=slot_votes.astype(I8),
                decided=jnp.full((self.S, self.R), ABSENT, I8),
            )

            def ph(st, _):
                return self.phase_step(st, alive, shard_idx), ()

            st, _ = lax.scan(ph, st, None, length=max_phases)
            # a decided replica's view; max over the replica axis collapses
            # ABSENT (=3) only when nobody decided — mask it out explicitly
            dec = st.decided
            concrete = jnp.where(dec == ABSENT, I8(-1), dec)
            best = jnp.max(concrete, axis=1)
            return jnp.where(best < 0, I8(ABSENT), best.astype(I8))

        slots = jnp.arange(
            start_slot_index, start_slot_index + n_slots, dtype=I32
        )
        decided = lax.map(
            lambda args: per_slot(args[0], args[1]),
            (slots, initial_votes),
        )
        return decided

    @functools.partial(
        jax.jit,
        static_argnums=(0,),
        static_argnames=("n_slots", "max_phases"),
    )
    def slot_window(
        self,
        initial_votes: jnp.ndarray,  # i8[T, S, R] per-slot initial R1 votes
        alive: jnp.ndarray,  # bool[S, R]
        base_slots: jnp.ndarray,  # i32[S] PER-SHARD first slot number
        *,
        n_slots: int,
        max_phases: int = 4,
    ) -> jnp.ndarray:
        """:meth:`slot_pipeline` with PER-SHARD slot numbering: window
        entry ``t`` of shard ``s`` runs as slot ``base_slots[s] + t``.

        The engine plane needs this because shards advance independently —
        a uniform ``start_slot_index`` would make the common-coin stream of
        a shard depend on every OTHER shard's progress, breaking replay
        and conformance with the per-shard transport engine. Returns
        ``decided i8[T, S]`` like :meth:`slot_pipeline`.
        """
        shard_idx = self._shard_index_grid()

        def per_slot(t, slot_votes):
            slot = jnp.broadcast_to(
                (base_slots.astype(I32) + t)[:, None], (self.S, self.R)
            )
            st = MeshPhaseState(
                slot=slot,
                phase=jnp.zeros((self.S, self.R), I32),
                my_r1=slot_votes.astype(I8),
                decided=jnp.full((self.S, self.R), ABSENT, I8),
            )

            def ph(st, _):
                return self.phase_step(st, alive, shard_idx), ()

            st, _ = lax.scan(ph, st, None, length=max_phases)
            dec = st.decided
            concrete = jnp.where(dec == ABSENT, I8(-1), dec)
            best = jnp.max(concrete, axis=1)
            return jnp.where(best < 0, I8(ABSENT), best.astype(I8))

        offsets = jnp.arange(n_slots, dtype=I32)
        return lax.map(
            lambda args: per_slot(args[0], args[1]),
            (offsets, initial_votes),
        )

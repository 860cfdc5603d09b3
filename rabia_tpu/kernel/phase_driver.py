"""The batched weak-MVC phase driver: consensus as an array program.

This module vectorizes the weak-MVC transition relation (the reference's
formal spec, docs/weak_mvc.ivy:82-186; scalar executable form in
:mod:`rabia_tpu.core.oracle`) over ``S`` independent consensus instances
("shards") × ``R`` replicas:

- vote ledgers are ``int8[S, R, R]`` arrays (receiver-major) instead of the
  reference's per-phase HashMaps (rabia-core/src/messages.rs:138-223);
- the majority tally is a one-hot sum over the sender axis instead of
  ``PhaseData::count_votes`` loops (messages.rs:185-211);
- the round-2 tie-break is a **common coin** — ``fold_in(key, (shard, slot,
  phase))`` — identical on every replica by construction, implementing the
  spec's shared ``coin(P,V)`` relation (weak_mvc.ivy:169-182) rather than the
  reference implementation's per-node RNG (engine.rs:454-481, a documented
  deviation, SURVEY.md §3.1);
- crashes and partitions are boolean masks (``alive[S,R]``,
  ``deliver[S,R,R]``), not control flow.

Two kernels share the transition spec:

:class:`ClusterKernel`
    Whole-cluster simulation: all R replicas' state lives in one set of
    arrays. One ``round_step`` = one synchronous communication round with
    lossy delivery + implicit retransmission — bit-identical in semantics to
    ``WeakMVCOracle.step``. Used by the fault-injection harness and the
    benchmark ``slot_pipeline`` (which runs whole decision slots under
    ``lax.scan`` without host round-trips).

:class:`NodeKernel`
    One node's view (state ``[S]``, inboxes ``[S, R]`` ABSENT-coded): the
    device half of the host engine, which feeds it votes arriving from real
    transports and turns its outboxes into messages. Host-paced rounds
    resolve the async-protocol-on-synchronous-device tension (SURVEY.md
    §7.4.1).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from rabia_tpu.core.types import ABSENT, V0, V1, VQUESTION, f_plus_1, quorum_size

I8 = jnp.int8
I32 = jnp.int32

R1_WAIT = 0
R2_WAIT = 1


# ---------------------------------------------------------------------------
# Common coin
# ---------------------------------------------------------------------------
#
# The coin is a *portable* integer hash — the same uint32 avalanche
# sequence evaluates bit-identically under numpy (the engine's host
# kernel, rabia_tpu/kernel/host_driver.py) and under XLA on any backend.
# This replaces the round-1 design's threefry fold_in chain, which (a) was
# the dominant cost of a node_step dispatch on CPU and (b) could not be
# replayed outside JAX. The spec only requires a *shared* coin(P, V)
# relation (docs/weak_mvc.ivy:169-182): any deterministic function of
# (seed, shard, slot, phase) that every replica evaluates identically
# qualifies; the reference instead flips per-node RNGs
# (engine.rs:454-481), a documented deviation we fix.

_GOLD = 0x9E3779B9  # 2^32 / golden ratio, the hash_combine offset


def _mix32(h):
    """lowbias32 avalanche (a well-mixed uint32 permutation)."""
    h = h ^ (h >> 16)
    h = h * 0x21F0AAAD
    h = h ^ (h >> 15)
    h = h * 0x735A2D97
    h = h ^ (h >> 15)
    return h


def coin_threshold(p1: float) -> int:
    """uint32 acceptance threshold for coin probability ``p1``.

    Bit-identity-critical: every coin implementation (this XLA/numpy
    kernel, the numpy host kernel, and native/hostkernel.cpp) must derive
    the threshold from ``p1`` with EXACTLY this rounding/clamping, or
    replicas on different backends flip different coins."""
    return min(int(p1 * 4294967296.0), 4294967295)


def _coin_bits(seed, shard, slot, phase, p1: float, xp=jnp):
    """Common-coin values for (shard, slot, phase) triples (same shape).

    Depends only on the seed and the triple — never on the replica flipping
    it — so every replica (and every host/device replay) sees the same coin.
    ``xp`` is the array namespace (``jax.numpy`` or ``numpy``); both produce
    identical bits. Returns int8 V0/V1 of the broadcast shape.
    """
    u32 = xp.uint32
    shard, slot, phase = xp.broadcast_arrays(
        xp.asarray(shard), xp.asarray(slot), xp.asarray(phase)
    )
    h = _mix32(xp.full(shard.shape, u32(seed)) ^ u32(_GOLD))
    h = _mix32(h ^ (shard.astype(u32) + u32(_GOLD)))
    h = _mix32(h ^ (slot.astype(u32) + u32(_GOLD)))
    h = _mix32(h ^ (phase.astype(u32) + u32(_GOLD)))
    threshold = u32(coin_threshold(p1))
    return xp.where(h < threshold, xp.int8(V1), xp.int8(V0))


def device_coin(seed: int, shard: int, slot: int, phase: int, p1: float = 0.5) -> int:
    """Scalar host-side view of the common coin (for the oracle/tests)."""
    import numpy as np

    return int(
        _coin_bits(
            seed, np.array([shard]), np.array([slot]), np.array([phase]), p1, xp=np
        )[0]
    )


def _tally(ledger: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Count V0/V1/V? and total present votes over the last (sender) axis.

    The batched form of PhaseData::count_votes (messages.rs:185-211).
    """
    c0 = jnp.sum(ledger == V0, axis=-1, dtype=I32)
    c1 = jnp.sum(ledger == V1, axis=-1, dtype=I32)
    cq = jnp.sum(ledger == VQUESTION, axis=-1, dtype=I32)
    total = c0 + c1 + cq
    return c0, c1, cq, total


# ---------------------------------------------------------------------------
# Cluster-simulation kernel
# ---------------------------------------------------------------------------


class ClusterState(NamedTuple):
    """All-replica consensus state for S shards × R replicas (device)."""

    slot: jnp.ndarray  # i32[S]   decision-slot counter (host-advanced)
    phase: jnp.ndarray  # i32[S,R] weak-MVC phase within the slot
    stage: jnp.ndarray  # i8[S,R]  R1_WAIT | R2_WAIT
    my_r1: jnp.ndarray  # i8[S,R]  this replica's round-1 vote (current phase)
    my_r2: jnp.ndarray  # i8[S,R]  round-2 vote (ABSENT until cast)
    # previous phase's votes, re-offered to stragglers one phase behind:
    # weak MVC assumes reliable broadcast, so under lossy delivery a sender
    # keeps retransmitting the votes of the phase it just left — otherwise a
    # quorum can splinter across adjacent phases and deadlock.
    prev_r1: jnp.ndarray  # i8[S,R]
    prev_r2: jnp.ndarray  # i8[S,R]
    led1: jnp.ndarray  # i8[S,R,R] round-1 ledger [shard, receiver, sender]
    led2: jnp.ndarray  # i8[S,R,R]
    decided: jnp.ndarray  # i8[S]  slot decision (ABSENT until first decider)
    decided_phase: jnp.ndarray  # i32[S] min MVC phase of any decision (or -1)
    done: jnp.ndarray  # bool[S,R] replica knows the decision
    active: jnp.ndarray  # bool[S] shard has a live instance this slot


class ClusterKernel:
    """Factory of jitted cluster-simulation step functions.

    ``n_replicas``, quorum and f+1 are static (baked into the compiled
    program); shard count is dynamic up to the padded shape.
    """

    def __init__(self, n_shards: int, n_replicas: int, *, coin_p1: float = 0.5, seed: int = 0):
        self.S = int(n_shards)
        self.R = int(n_replicas)
        self.quorum = quorum_size(self.R)
        self.f1 = f_plus_1(self.R)
        self.coin_p1 = float(coin_p1)
        self.seed = int(seed)
        self.key = jax.random.key(self.seed)
        self._shard_idx = jnp.arange(self.S, dtype=I32)

    # -- state constructors -------------------------------------------------

    def init_state(self) -> ClusterState:
        S, R = self.S, self.R
        return ClusterState(
            slot=jnp.zeros((S,), I32),
            phase=jnp.zeros((S, R), I32),
            stage=jnp.full((S, R), R1_WAIT, I8),
            my_r1=jnp.full((S, R), ABSENT, I8),
            my_r2=jnp.full((S, R), ABSENT, I8),
            prev_r1=jnp.full((S, R), ABSENT, I8),
            prev_r2=jnp.full((S, R), ABSENT, I8),
            led1=jnp.full((S, R, R), ABSENT, I8),
            led2=jnp.full((S, R, R), ABSENT, I8),
            decided=jnp.full((S,), ABSENT, I8),
            decided_phase=jnp.full((S,), -1, I32),
            done=jnp.zeros((S, R), bool),
            active=jnp.zeros((S,), bool),
        )

    @functools.partial(jax.jit, static_argnums=0)
    def start_slot(
        self, state: ClusterState, shard_mask: jnp.ndarray, initial_votes: jnp.ndarray
    ) -> ClusterState:
        """Begin a new decision slot on masked shards with the given initial
        round-1 votes (V1 where the replica holds the proposal, V0 where it
        gave up waiting — weak_mvc.ivy:113-131)."""
        S, R = self.S, self.R
        m = shard_mask  # bool[S]
        mr = m[:, None]
        eye = jnp.eye(R, dtype=bool)[None, :, :]
        led1_fresh = jnp.where(
            eye, initial_votes[:, :, None].astype(I8), I8(ABSENT)
        )
        return ClusterState(
            slot=jnp.where(m, state.slot + jnp.where(state.active, 1, 0), state.slot),
            phase=jnp.where(mr, 0, state.phase),
            stage=jnp.where(mr, I8(R1_WAIT), state.stage),
            my_r1=jnp.where(mr, initial_votes.astype(I8), state.my_r1),
            my_r2=jnp.where(mr, I8(ABSENT), state.my_r2),
            prev_r1=jnp.where(mr, I8(ABSENT), state.prev_r1),
            prev_r2=jnp.where(mr, I8(ABSENT), state.prev_r2),
            led1=jnp.where(mr[:, :, None], led1_fresh, state.led1),
            led2=jnp.where(mr[:, :, None], I8(ABSENT), state.led2),
            decided=jnp.where(m, I8(ABSENT), state.decided),
            decided_phase=jnp.where(m, -1, state.decided_phase),
            done=jnp.where(mr, False, state.done),
            active=jnp.logical_or(state.active, m),
        )

    # -- the synchronous round step ----------------------------------------

    @functools.partial(jax.jit, static_argnums=0)
    def round_step(
        self,
        state: ClusterState,
        alive: jnp.ndarray,  # bool[S,R] (or broadcastable [R])
        deliver: jnp.ndarray,  # bool[S,R,R]  [shard, sender, receiver]
    ) -> ClusterState:
        """One synchronous communication round for every shard at once.

        No buffer donation here: the simulation kernel's callers (fault
        harness, tests) legitimately hold old states for inspection; the
        hot multi-round drivers (`run_rounds`, `slot_pipeline`) scan on
        device, where XLA reuses the carry buffers anyway. The engine's
        NodeKernel path IS donated — its state is threaded linearly.

        Semantics are element-for-element those of ``WeakMVCOracle.step``:
        (1) deliver outstanding votes under the mask (with retransmission —
        a sender's *current* votes are re-offered every round), (2) run every
        enabled R1→R2 and R2→advance transition, (3) propagate decisions.
        """
        S, R, Q, F1 = self.S, self.R, self.quorum, self.f1
        alive = jnp.broadcast_to(alive, (S, R))
        act = state.active[:, None]

        # ---- 1. delivery ------------------------------------------------
        # link[s,i,j]: sender i's traffic reaches receiver j this round
        link = (
            deliver
            & alive[:, :, None]
            & alive[:, None, :]
            & ~jnp.eye(R, dtype=bool)[None]
        )
        same_phase = state.phase[:, :, None] == state.phase[:, None, :]  # [s,i,j]
        ahead_one = state.phase[:, :, None] == state.phase[:, None, :] + 1
        rcv_open = ~state.done[:, None, :]  # decided receivers stop listening
        offer1 = link & rcv_open & (
            (same_phase & (state.my_r1 != ABSENT)[:, :, None])
            | (ahead_one & (state.prev_r1 != ABSENT)[:, :, None])
        )
        offer2 = link & rcv_open & (
            (
                same_phase
                & (state.stage == R2_WAIT)[:, :, None]
                & (state.my_r2 != ABSENT)[:, :, None]
            )
            | (ahead_one & (state.prev_r2 != ABSENT)[:, :, None])
        )
        val1 = jnp.where(same_phase, state.my_r1[:, :, None], state.prev_r1[:, :, None])
        val2 = jnp.where(same_phase, state.my_r2[:, :, None], state.prev_r2[:, :, None])
        # ledgers are [s, receiver, sender] — transpose the offer/value grids
        o1 = jnp.swapaxes(offer1, 1, 2)
        o2 = jnp.swapaxes(offer2, 1, 2)
        v1 = jnp.swapaxes(jnp.broadcast_to(val1, (S, R, R)), 1, 2)
        v2 = jnp.swapaxes(jnp.broadcast_to(val2, (S, R, R)), 1, 2)
        led1 = jnp.where((state.led1 == ABSENT) & o1, v1, state.led1)
        led2 = jnp.where((state.led2 == ABSENT) & o2, v2, state.led2)

        # ---- 2. transitions (on pre-step stages, like the oracle) --------
        enabled = act & alive & ~state.done
        eye = jnp.eye(R, dtype=bool)[None]

        # R1 -> R2: with a quorum of round-1 votes, vote v on an all-v
        # majority, else V?  (weak_mvc.ivy:133-147)
        c0, c1, _, tot1 = _tally(led1)
        cast_r2 = enabled & (state.stage == R1_WAIT) & (tot1 >= Q)
        r2_val = jnp.where(c1 >= Q, I8(V1), jnp.where(c0 >= Q, I8(V0), I8(VQUESTION)))
        my_r2 = jnp.where(cast_r2, r2_val, state.my_r2)
        stage = jnp.where(cast_r2, I8(R2_WAIT), state.stage)
        led2 = jnp.where(cast_r2[:, :, None] & eye, my_r2[:, :, None], led2)

        # R2 -> advance: decide on f+1 agreeing non-? votes; else adopt any
        # non-? vote; else flip the common coin  (weak_mvc.ivy:149-186)
        d0, d1, _, tot2 = _tally(led2)
        advance = enabled & (state.stage == R2_WAIT) & (tot2 >= Q)
        decide1 = d1 >= F1
        decide0 = d0 >= F1
        coin = _coin_bits(
            self.seed,
            jnp.broadcast_to(self._shard_idx[:, None], (S, R)),
            jnp.broadcast_to(state.slot[:, None], (S, R)),
            state.phase,
            self.coin_p1,
        )
        next_v = jnp.where(
            decide1,
            I8(V1),
            jnp.where(
                decide0,
                I8(V0),
                jnp.where(d1 > 0, I8(V1), jnp.where(d0 > 0, I8(V0), coin)),
            ),
        )
        newly_decided = advance & (decide1 | decide0)
        dec_vals = jnp.where(
            newly_decided, jnp.where(decide1, I8(V1), I8(V0)), I8(-1)
        )
        shard_dec = jnp.max(dec_vals, axis=1)  # -1 if no decider this round
        decided = jnp.where(
            (state.decided == ABSENT) & (shard_dec >= 0),
            shard_dec.astype(I8),
            state.decided,
        )
        # decided_phase = minimum MVC phase at which any replica decided
        intmax = jnp.iinfo(I32).max
        round_min = jnp.min(
            jnp.where(newly_decided, state.phase, intmax), axis=1
        )
        existing = jnp.where(state.decided_phase < 0, intmax, state.decided_phase)
        merged = jnp.minimum(existing, round_min)
        decided_phase = jnp.where(merged == intmax, -1, merged)
        done = state.done | newly_decided

        phase = jnp.where(advance, state.phase + 1, state.phase)
        prev_r1 = jnp.where(advance, state.my_r1, state.prev_r1)
        prev_r2 = jnp.where(advance, my_r2, state.prev_r2)
        my_r1 = jnp.where(advance, next_v, state.my_r1)
        stage = jnp.where(advance, I8(R1_WAIT), stage)
        my_r2 = jnp.where(advance, I8(ABSENT), my_r2)
        adv3 = advance[:, :, None]
        led1 = jnp.where(
            adv3, jnp.where(eye, next_v[:, :, None], I8(ABSENT)), led1
        )
        led2 = jnp.where(adv3, I8(ABSENT), led2)

        # ---- 3. decision propagation ------------------------------------
        # any done replica whose link reaches an undecided one informs it
        informed = jnp.einsum("si,sij->sj", (done & alive).astype(I32), deliver.astype(I32)) > 0
        adopt = state.active[:, None] & alive & ~done & informed & (decided != ABSENT)[:, None]
        done = done | adopt

        return ClusterState(
            slot=state.slot,
            phase=phase,
            stage=stage,
            my_r1=my_r1,
            my_r2=my_r2,
            prev_r1=prev_r1,
            prev_r2=prev_r2,
            led1=led1,
            led2=led2,
            decided=decided,
            decided_phase=decided_phase,
            done=done,
            active=state.active,
        )

    # -- multi-round / multi-slot drivers ----------------------------------

    @functools.partial(
        jax.jit,
        static_argnums=(0, 3, 5),
        static_argnames=("n_rounds", "p_deliver"),
    )
    def run_rounds(
        self,
        state: ClusterState,
        alive: jnp.ndarray,
        n_rounds: int,
        step_key: jnp.ndarray,
        p_deliver: float = 1.0,
        link_mask: Optional[jnp.ndarray] = None,
    ) -> ClusterState:
        """Run ``n_rounds`` round_steps in one dispatch (lax.scan), drawing a
        fresh Bernoulli delivery mask per round ∧ an optional static link
        mask (partitions). ``step_key`` seeds delivery randomness only —
        protocol coins come from the kernel's own key."""
        S, R = self.S, self.R
        base_link = (
            jnp.ones((S, R, R), bool) if link_mask is None else jnp.broadcast_to(link_mask, (S, R, R))
        )

        def body(st, k):
            if p_deliver >= 1.0:
                d = base_link
            else:
                d = base_link & jax.random.bernoulli(k, p_deliver, (S, R, R))
            return self.round_step(st, alive, d), ()

        keys = jax.random.split(step_key, n_rounds)
        state, _ = lax.scan(body, state, keys)
        return state

    @functools.partial(
        jax.jit,
        static_argnums=(0, 3, 4, 5),
        static_argnames=("n_slots", "rounds_per_slot", "start_slot_index"),
    )
    def slot_pipeline(
        self,
        initial_votes: jnp.ndarray,  # i8[T, S, R] per-slot initial R1 votes
        alive: jnp.ndarray,  # bool[S,R]
        n_slots: int,
        rounds_per_slot: int = 2,
        start_slot_index: int = 0,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Decide ``n_slots`` consecutive slots for all S shards entirely on
        device: scan over slots, ``rounds_per_slot`` full-delivery rounds
        each (2 suffices fault-free: R1 exchange+cast, R2 exchange+decide).

        Returns ``(decided[T, S], decided_phase[T, S])``. This is the
        benchmark hot path — no host round-trips between decisions, which is
        what amortizes dispatch overhead across thousands of shards
        (SURVEY.md §7.4.4).
        """
        S, R = self.S, self.R
        full = jnp.ones((S, R, R), bool)
        every = jnp.ones((S,), bool)

        def per_slot(state, inp):
            slot_votes, slot_idx = inp
            st = self.start_slot(state, every, slot_votes)
            st = st._replace(slot=jnp.full((S,), slot_idx, I32))

            def rd(s, _):
                return self.round_step(s, alive, full), ()

            st, _ = lax.scan(rd, st, None, length=rounds_per_slot)
            return st, (st.decided, st.decided_phase)

        state0 = self.init_state()
        slots = jnp.arange(start_slot_index, start_slot_index + n_slots, dtype=I32)
        _, (decided, dphase) = lax.scan(
            per_slot, state0, (initial_votes, slots)
        )
        return decided, dphase

    @functools.partial(
        jax.jit,
        static_argnums=(0, 3, 4, 5, 6),
        static_argnames=(
            "n_slots", "rounds_per_slot", "start_slot_index", "block"
        ),
    )
    def slot_pipeline_wide(
        self,
        initial_votes: jnp.ndarray,  # i8[T, S, R] per-slot initial R1 votes
        alive: jnp.ndarray,  # bool[S,R]
        n_slots: int,
        rounds_per_slot: int = 2,
        start_slot_index: int = 0,
        block: int = 256,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """:meth:`slot_pipeline` with ``block`` slots evaluated in
        parallel per scan step (vmap over the slot axis).

        Consecutive slots of one shard are independent consensus
        instances (each ``per_slot`` iteration rebuilds its state from
        ``start_slot``), so batching them is semantics-preserving —
        decisions are bit-identical to :meth:`slot_pipeline`
        (conformance-tested). Whether it is FASTER is geometry- and
        backend-dependent and not measured on the attached chip — use
        this variant for batch evaluation of many small windows, not as
        a default.

        ``n_slots`` must be a multiple of ``block`` (callers pad votes
        with unanimous-V0 filler slots, which decide in phase 0).
        """
        if n_slots % block:
            raise ValueError(
                f"n_slots {n_slots} not a multiple of block {block}"
            )
        S, R = self.S, self.R
        full = jnp.ones((S, R, R), bool)
        every = jnp.ones((S,), bool)
        state0 = self.init_state()

        def one_slot(slot_votes, slot_idx):
            st = self.start_slot(state0, every, slot_votes)
            st = st._replace(slot=jnp.full((S,), slot_idx, I32))

            def rd(s, _):
                return self.round_step(s, alive, full), ()

            st, _ = lax.scan(rd, st, None, length=rounds_per_slot)
            return st.decided, st.decided_phase

        votes_b = initial_votes.reshape(n_slots // block, block, S, R)
        slots_b = jnp.arange(
            start_slot_index, start_slot_index + n_slots, dtype=I32
        ).reshape(n_slots // block, block)

        def per_chunk(_, inp):
            vb, sb = inp
            return None, jax.vmap(one_slot)(vb, sb)

        _, (decided, dphase) = lax.scan(per_chunk, None, (votes_b, slots_b))
        return (
            decided.reshape(n_slots, S),
            dphase.reshape(n_slots, S),
        )

    def slot_pipeline_fused(
        self,
        initial_votes: jnp.ndarray,  # i8[T, S, R]
        alive: jnp.ndarray,  # bool[S,R] (or broadcastable [R])
        n_slots: int,
        use_pallas: Optional[bool] = None,
        interpret: bool = False,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Fault-free fast path: bit-identical to
        ``slot_pipeline(votes, alive, T)`` at the default
        ``rounds_per_slot=2`` (full delivery provably collapses to a
        closed-form quorum tally — derivation in
        :mod:`rabia_tpu.kernel.fused_window`), evaluated as ONE fused
        Pallas kernel on TPU, or the same closed form as a plain XLA
        program elsewhere. The scanned :meth:`slot_pipeline` remains the
        semantics owner (and the path for lossy/crash simulation via
        :meth:`run_rounds`)."""
        from rabia_tpu.kernel import fused_window

        if initial_votes.shape[0] != n_slots:
            # slot_pipeline fails loudly on this mismatch (scan length);
            # silent truncation would break the drop-in equivalence
            raise ValueError(
                f"votes carry {initial_votes.shape[0]} slots, "
                f"n_slots={n_slots}"
            )
        alive = jnp.broadcast_to(alive, (self.S, self.R))
        votes = initial_votes
        if use_pallas is None:
            use_pallas = (
                jax.default_backend() == "tpu" and self.S % 128 == 0
            )
        if use_pallas or interpret:
            return fused_window.pallas_window(
                votes, alive, self.quorum, interpret=interpret
            )
        return fused_window.closed_form_window(votes, alive, self.quorum)

    def slot_pipeline_fused_rmajor(
        self,
        votes_rm: jnp.ndarray,  # i8[R, T, S] — replica-major planes
        alive_rm: jnp.ndarray,  # bool[R, S] (or broadcastable [R, 1])
        n_slots: int,
        use_pallas: Optional[bool] = None,
        interpret: bool = False,
        want_phase: bool = True,
    ):
        """:meth:`slot_pipeline_fused` on replica-major votes — the
        bandwidth-shaped entry for producers that build the vote tensor
        themselves (the mesh engine does). Skipping the ``[T,S,R]`` API
        layout avoids an i8 minor-axis relayout; ``want_phase=False``
        additionally skips the redundant i32 phase plane (derivable:
        0 iff decided). Bit-identical to
        ``slot_pipeline(transpose(votes_rm, (1,2,0)), ...)`` — pinned in
        tests/test_kernel.py and scripts/fuzz_conformance.py."""
        from rabia_tpu.kernel import fused_window

        if votes_rm.shape[1] != n_slots:
            raise ValueError(
                f"votes carry {votes_rm.shape[1]} slots, n_slots={n_slots}"
            )
        if votes_rm.shape[0] != self.R or votes_rm.shape[2] != self.S:
            # loud failure on an accidental [T,S,R]-layout pass-through:
            # R binding to T would statically unroll a T-iteration loop
            raise ValueError(
                f"votes_rm is {votes_rm.shape}, expected replica-major "
                f"[R={self.R}, T={n_slots}, S={self.S}]"
            )
        alive_rm = jnp.broadcast_to(alive_rm, (self.R, self.S))
        if use_pallas is None:
            use_pallas = (
                jax.default_backend() == "tpu" and self.S % 128 == 0
            )
        if use_pallas or interpret:
            return fused_window.pallas_window_rmajor(
                votes_rm,
                alive_rm,
                self.quorum,
                interpret=interpret,
                want_phase=want_phase,
            )
        return fused_window.closed_form_window_rmajor(
            votes_rm, alive_rm, self.quorum, want_phase=want_phase
        )

    def slot_pipeline_fused_packed(
        self,
        packed_rm: jnp.ndarray,  # u32[R, T, SW] — 16 votes/word, 2-bit codes
        alive_packed: jnp.ndarray,  # u32[R, SW] — lane-LSB alive bits
        n_slots: int,
    ) -> jnp.ndarray:
        """:meth:`slot_pipeline_fused_rmajor` on word-packed votes — the
        minimum-bytes entry: (2R+2)/8 bytes per decision instead of R+1,
        tallied with word-wise bit arithmetic (kernel/packed_window.py).
        Producers pack with ``packed_window.pack_codes`` /
        ``pack_alive``; returns PACKED decisions u32[T, SW] (decode with
        ``packed_window.unpack_codes``; phase derivable: 0 iff decided).
        Bit-identical to the rmajor entry — pinned in
        tests/test_packed_window.py."""
        from rabia_tpu.kernel import packed_window

        SW = packed_window.packed_width(self.S)
        if packed_rm.shape[1] != n_slots:
            raise ValueError(
                f"votes carry {packed_rm.shape[1]} slots, n_slots={n_slots}"
            )
        if packed_rm.shape[0] != self.R or packed_rm.shape[2] != SW:
            raise ValueError(
                f"packed_rm is {packed_rm.shape}, expected packed "
                f"replica-major [R={self.R}, T={n_slots}, SW={SW}]"
            )
        return packed_window.packed_window_rmajor(
            packed_rm, alive_packed, self.quorum
        )


# ---------------------------------------------------------------------------
# Per-node kernel (the host engine's device half)
# ---------------------------------------------------------------------------


class NodeState(NamedTuple):
    """One node's consensus state over its S shards."""

    slot: jnp.ndarray  # i32[S]
    phase: jnp.ndarray  # i32[S]
    stage: jnp.ndarray  # i8[S]
    my_r1: jnp.ndarray  # i8[S]
    my_r2: jnp.ndarray  # i8[S]
    led1: jnp.ndarray  # i8[S,R]  votes seen for current (slot, phase)
    led2: jnp.ndarray  # i8[S,R]
    decided: jnp.ndarray  # i8[S]
    done: jnp.ndarray  # bool[S]
    active: jnp.ndarray  # bool[S]


class NodeOutbox(NamedTuple):
    """What the host must transmit after a node_step."""

    cast_r2: jnp.ndarray  # bool[S] — broadcast VoteRound2(phase, my_r2)
    r2_vals: jnp.ndarray  # i8[S]
    advanced: jnp.ndarray  # bool[S] — broadcast VoteRound1(phase+1, my_r1)
    new_r1: jnp.ndarray  # i8[S]
    new_phase: jnp.ndarray  # i32[S]
    newly_decided: jnp.ndarray  # bool[S] — broadcast Decision(slot, value)
    decided_vals: jnp.ndarray  # i8[S]


class NodeKernel:
    """Jitted per-node step: ledgers in, transitions out (SURVEY.md §7.1).

    The host engine owns message routing and slot lifecycle; this kernel owns
    every piece of per-phase math the reference computes in
    engine.rs:424-706, for all shards at once.
    """

    def __init__(self, n_shards: int, n_replicas: int, me: int, *, coin_p1: float = 0.5, seed: int = 0):
        self.S = int(n_shards)
        self.R = int(n_replicas)
        self.me = int(me)
        self.quorum = quorum_size(self.R)
        self.f1 = f_plus_1(self.R)
        self.coin_p1 = float(coin_p1)
        self.seed = int(seed)
        self._shard_idx = jnp.arange(self.S, dtype=I32)

    def init_state(self) -> NodeState:
        S, R = self.S, self.R
        return NodeState(
            slot=jnp.zeros((S,), I32),
            phase=jnp.zeros((S,), I32),
            stage=jnp.full((S,), R1_WAIT, I8),
            my_r1=jnp.full((S,), ABSENT, I8),
            my_r2=jnp.full((S,), ABSENT, I8),
            led1=jnp.full((S, R), ABSENT, I8),
            led2=jnp.full((S, R), ABSENT, I8),
            decided=jnp.full((S,), ABSENT, I8),
            done=jnp.zeros((S,), bool),
            active=jnp.zeros((S,), bool),
        )

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def start_slots(
        self,
        state: NodeState,
        shard_mask: jnp.ndarray,  # bool[S]
        slot_index: jnp.ndarray,  # i32[S]
        initial_votes: jnp.ndarray,  # i8[S]
    ) -> NodeState:
        return self._start_slots_math(state, shard_mask, slot_index, initial_votes)

    def _start_slots_math(
        self, state, shard_mask, slot_index, initial_votes
    ) -> NodeState:
        R = self.R
        m = shard_mask
        led1 = jnp.where(
            m[:, None],
            jnp.where(
                jnp.arange(R)[None, :] == self.me,
                initial_votes[:, None].astype(I8),
                I8(ABSENT),
            ),
            state.led1,
        )
        return NodeState(
            slot=jnp.where(m, slot_index, state.slot),
            phase=jnp.where(m, 0, state.phase),
            stage=jnp.where(m, I8(R1_WAIT), state.stage),
            my_r1=jnp.where(m, initial_votes.astype(I8), state.my_r1),
            my_r2=jnp.where(m, I8(ABSENT), state.my_r2),
            led1=led1,
            led2=jnp.where(m[:, None], I8(ABSENT), state.led2),
            decided=jnp.where(m, I8(ABSENT), state.decided),
            done=jnp.where(m, False, state.done),
            active=state.active | m,
        )

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def node_step(
        self,
        state: NodeState,
        inbox_r1: jnp.ndarray,  # i8[S,R] votes for current (slot, phase); ABSENT elsewhere
        inbox_r2: jnp.ndarray,  # i8[S,R]
        decision_in: jnp.ndarray,  # i8[S] ABSENT or adopted decision value
    ) -> tuple[NodeState, NodeOutbox]:
        """Consume routed inboxes, run enabled transitions on every shard.

        ``state`` is DONATED (device buffers reused in place); do not reuse
        the passed-in state afterwards."""
        return self._node_step_math(state, inbox_r1, inbox_r2, decision_in)

    @functools.partial(
        jax.jit, static_argnums=(0, 8), donate_argnums=1
    )
    def node_cycle(
        self,
        state: NodeState,
        shard_mask: jnp.ndarray,  # bool[S] slots to (re)start this tick
        slot_index: jnp.ndarray,  # i32[S]
        initial_votes: jnp.ndarray,  # i8[S]
        inbox_r1: jnp.ndarray,  # i8[S,R]
        inbox_r2: jnp.ndarray,  # i8[S,R]
        decision_in: jnp.ndarray,  # i8[S]
        n_steps: int,
    ) -> tuple[NodeState, NodeOutbox]:
        """One device dispatch for a whole engine tick: start newly opened
        slots, then chain ``n_steps`` node_steps (inboxes consumed by the
        first; later substeps cascade stage transitions — cast R2, then
        decide — on ledger-resident votes). Returns the final state and a
        NodeOutbox of [n_steps, ...]-stacked transition flags.

        This is the SURVEY.md §7.4.4 dispatch-amortization lever for the
        transport engine: per-round host<->device stepping pays the
        dispatch latency once per STAGE; chaining substeps pays it once
        per tick.
        """
        state = self._start_slots_math(
            state, shard_mask, slot_index, initial_votes
        )
        K = int(n_steps)
        pad1 = jnp.full((K - 1,) + inbox_r1.shape, ABSENT, I8)
        ib1 = jnp.concatenate([inbox_r1[None].astype(I8), pad1])
        ib2 = jnp.concatenate([inbox_r2[None].astype(I8), pad1])
        dec = jnp.concatenate(
            [
                decision_in[None].astype(I8),
                jnp.full((K - 1,) + decision_in.shape, ABSENT, I8),
            ]
        )

        def body(st, xs):
            st, outbox = self._node_step_math(st, xs[0], xs[1], xs[2])
            return st, outbox

        state, outboxes = lax.scan(body, state, (ib1, ib2, dec))
        return state, outboxes

    def _node_step_math(
        self,
        state: NodeState,
        inbox_r1: jnp.ndarray,
        inbox_r2: jnp.ndarray,
        decision_in: jnp.ndarray,
    ) -> tuple[NodeState, NodeOutbox]:
        S, R, Q, F1 = self.S, self.R, self.quorum, self.f1

        led1 = jnp.where((state.led1 == ABSENT) & (inbox_r1 != ABSENT), inbox_r1, state.led1)
        led2 = jnp.where((state.led2 == ABSENT) & (inbox_r2 != ABSENT), inbox_r2, state.led2)

        enabled = state.active & ~state.done

        c0, c1, _, tot1 = _tally(led1)
        cast_r2 = enabled & (state.stage == R1_WAIT) & (tot1 >= Q)
        r2_val = jnp.where(c1 >= Q, I8(V1), jnp.where(c0 >= Q, I8(V0), I8(VQUESTION)))
        my_r2 = jnp.where(cast_r2, r2_val, state.my_r2)
        stage = jnp.where(cast_r2, I8(R2_WAIT), state.stage)
        own = jnp.arange(R)[None, :] == self.me
        led2 = jnp.where(cast_r2[:, None] & own, my_r2[:, None], led2)

        d0, d1, _, tot2 = _tally(led2)
        advance = enabled & (state.stage == R2_WAIT) & (tot2 >= Q)
        decide1 = d1 >= F1
        decide0 = d0 >= F1
        coin = _coin_bits(self.seed, self._shard_idx, state.slot, state.phase, self.coin_p1)
        next_v = jnp.where(
            decide1,
            I8(V1),
            jnp.where(
                decide0,
                I8(V0),
                jnp.where(d1 > 0, I8(V1), jnp.where(d0 > 0, I8(V0), coin)),
            ),
        )
        newly_decided = advance & (decide1 | decide0)
        dec_val = jnp.where(decide1, I8(V1), I8(V0))

        # external decision adoption (Decision broadcast / sync)
        adopt = enabled & ~newly_decided & (decision_in != ABSENT)
        decided = jnp.where(
            newly_decided, dec_val, jnp.where(adopt, decision_in, state.decided)
        )
        done = state.done | newly_decided | adopt

        phase = jnp.where(advance, state.phase + 1, state.phase)
        my_r1 = jnp.where(advance, next_v, state.my_r1)
        stage = jnp.where(advance, I8(R1_WAIT), stage)
        my_r2_out = my_r2
        my_r2 = jnp.where(advance, I8(ABSENT), my_r2)
        led1 = jnp.where(
            advance[:, None],
            jnp.where(own, next_v[:, None], I8(ABSENT)),
            led1,
        )
        led2 = jnp.where(advance[:, None], I8(ABSENT), led2)

        new_state = NodeState(
            slot=state.slot,
            phase=phase,
            stage=stage,
            my_r1=my_r1,
            my_r2=my_r2,
            led1=led1,
            led2=led2,
            decided=decided,
            done=done,
            active=state.active,
        )
        outbox = NodeOutbox(
            cast_r2=cast_r2,
            r2_vals=my_r2_out,
            advanced=advance,
            new_r1=my_r1,
            new_phase=phase,
            newly_decided=newly_decided,
            decided_vals=decided,
        )
        return new_state, outbox


# ---------------------------------------------------------------------------
# Wire phase packing: (slot, mvc_phase) <-> u64 sequence number
# ---------------------------------------------------------------------------

_MVC_BITS = 16


def pack_phase(slot: int, mvc_phase: int) -> int:
    """Encode (decision slot, weak-MVC phase) into a wire sequence number.

    The reference's monotone PhaseId (one per decision) maps to our slot;
    the in-slot MVC phase is new (its engine folds retries into fresh
    PhaseIds instead — SURVEY.md §3.1)."""
    if mvc_phase >= (1 << _MVC_BITS):
        raise ValueError("mvc phase overflow")
    return (slot << _MVC_BITS) | mvc_phase


def unpack_phase(seq: int) -> tuple[int, int]:
    return seq >> _MVC_BITS, seq & ((1 << _MVC_BITS) - 1)

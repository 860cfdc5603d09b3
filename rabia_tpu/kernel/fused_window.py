"""Pallas TPU kernel for the fault-free slot window: one fused pass.

The general :meth:`ClusterKernel.slot_pipeline` runs every slot through the
full weak-MVC machinery — two scanned ``round_step`` dispatches with
``[S, R, R]`` delivery grids — because it must also model loss, partitions
and per-replica divergence. Under the conditions ``slot_pipeline``
actually runs with (FULL delivery, fresh per-slot state, the default
``rounds_per_slot=2``), that machinery provably collapses to a closed
form, which this module evaluates as a single Pallas kernel over the
vote tensor. Its roofline share on the attached chip is not yet
measured (benchmarks/roofline.py has the methodology); chip_smoke.py
compiles it there and holds it bit for bit to the scanned owner.

Derivation (each step mirrors ``round_step``, phase_driver.py:224-367):

1. With full delivery, every alive receiver's round-1 ledger contains
   exactly the *present* sender set ``{i : alive[i] and vote[i] != ABSENT}``
   (a sender's own diagonal entry from ``start_slot`` coincides with its
   delivered vote), so every alive replica computes the SAME tally
   ``(c0, c1, tot)``.
2. Round 1's transition: if ``tot >= Q`` every alive replica casts the
   same round-2 vote ``r2 = V1 if c1>=Q else V0 if c0>=Q else V?``;
   if ``tot < Q`` nothing ever happens (the ledger cannot grow).
3. Round 2's delivery gives every alive receiver ``n_alive`` copies of
   that same ``r2``; the advance condition ``tot2 >= Q`` holds because
   ``n_alive >= tot >= Q``, and the decide condition ``count >= f+1``
   holds because ``quorum >= f+1`` for every R. So the slot decides
   ``r2`` iff ``r2 != V?`` — at MVC phase 0 — and stays undecided
   otherwise (the coin is never reached within two rounds, so the
   decision is independent of the slot index).

Therefore::

    decided[t, s] = V1      if c1 >= Q
                    V0      elif c0 >= Q
                    ABSENT  else (incl. tot < Q: c0,c1 <= tot)

``tests/test_kernel.py`` pins this bit-identical to ``slot_pipeline``
over random votes (all four codes), random crash masks and odd sizes —
the general kernel remains the semantics owner; this is its proven
fast path. No reference analog: the reference decides one instance at a
time (rabia-core/src/messages.rs:185-211 tallies per phase).

Round 5: the preferred fast path is the PACKED formulation
(`kernel/packed_window.py` — 16 votes per u32 word, bitwise tally),
which moves 4x fewer bytes and streams at the HBM marginal rate; the
i8 entries here remain as the unpacked fallback and the roofline
comparison rows (docs/PERFORMANCE.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from rabia_tpu.core.types import ABSENT, V0, V1

I8 = jnp.int8
I32 = jnp.int32


@functools.partial(jax.jit, static_argnames=("quorum",))
def closed_form_window(
    votes: jnp.ndarray,  # i8[T, S, R]
    alive: jnp.ndarray,  # bool[S, R]
    quorum: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The closed form as one jitted XLA program (any backend)."""
    present = (votes != ABSENT) & alive[None, :, :]
    c1 = jnp.sum(present & (votes == V1), axis=-1, dtype=I32)
    c0 = jnp.sum(present & (votes == V0), axis=-1, dtype=I32)
    dec = jnp.where(
        c1 >= quorum, I8(V1), jnp.where(c0 >= quorum, I8(V0), I8(ABSENT))
    )
    ph = jnp.where(dec != ABSENT, I32(0), I32(-1))
    return dec, ph


@functools.partial(jax.jit, static_argnames=("quorum", "want_phase"))
def closed_form_window_rmajor(
    votes_rm: jnp.ndarray,  # i8[R, T, S] — replica-major planes
    alive_rm: jnp.ndarray,  # bool[R, S]
    quorum: int,
    want_phase: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray] | jnp.ndarray:
    """The closed form on replica-major votes: every operand is a
    well-tiled [T, S] plane, so no i8 minor-axis relayout is needed.
    Bit-identical to ``closed_form_window(transpose(votes_rm,(1,2,0)))``.
    ``want_phase=False`` returns only the decision plane and the i32
    phase plane is never materialized.
    """
    R = votes_rm.shape[0]
    T, S = votes_rm.shape[1], votes_rm.shape[2]
    c1 = jnp.zeros((T, S), I32)
    c0 = jnp.zeros((T, S), I32)
    for r in range(R):  # static unroll: R is tiny
        v = votes_rm[r]
        a = alive_rm[r][None, :]
        c1 = c1 + ((v == V1) & a).astype(I32)
        c0 = c0 + ((v == V0) & a).astype(I32)
    dec = jnp.where(
        c1 >= quorum, I8(V1), jnp.where(c0 >= quorum, I8(V0), I8(ABSENT))
    )
    if not want_phase:
        return dec
    ph = jnp.where(dec != ABSENT, I32(0), I32(-1))
    return dec, ph


def _make_kernel(R: int, quorum: int, want_phase: bool = True):
    """Kernel body closure (R and the quorum are compile-time static)."""

    def kernel(votes_ref, alive_ref, dec_ref, ph_ref=None):
        # votes_ref: i8[R, Tb, S] — replica-major so each plane is a
        # contiguous (Tb, S) tile; alive_ref: i8[R, 1, S]. Integer
        # arithmetic with explicit broadcasts throughout — Mosaic rejects
        # mixed-rank i1 broadcasts ("non-singleton dimension replicated").
        shape = dec_ref.shape
        c1 = jnp.zeros(shape, I32)
        c0 = jnp.zeros(shape, I32)
        for r in range(R):  # static unroll over the replica axis
            v = votes_ref[r].astype(I32)
            a = jnp.broadcast_to(alive_ref[r], shape).astype(I32)
            c1 = c1 + (v == V1).astype(I32) * a
            c0 = c0 + (v == V0).astype(I32) * a
        # stay in i32 until the final store: an i1 mask from an i32
        # compare cannot drive an i8-tiled select (another relayout trap)
        dec = jnp.where(
            c1 >= quorum, I32(V1), jnp.where(c0 >= quorum, I32(V0), I32(ABSENT))
        )
        dec_ref[:] = dec.astype(I8)
        if want_phase:
            ph_ref[:] = jnp.where(dec != ABSENT, I32(0), I32(-1))

    return kernel  # ph_ref defaults to None on the no-phase arity


def _pick_block(T: int, S: int, R: int) -> tuple[int, int]:
    """(slot rows, shard lanes) of one VMEM block.

    Mosaic tiles i8 as (32, 128): a block dim is legal when it is a
    multiple of its tile or spans the whole array axis. So the slot
    tile is 64 or 32 (never the 16..1 a divisor search lands on for a
    ragged ``T``), the grid is ``cdiv`` and the last block of a ragged
    axis is partial: the kernel is elementwise per (slot, shard), so
    whatever the padding rows hold never reaches a valid row. The
    budget point is 64 slots x 4096 shards x 5 replicas of i8 votes
    plus their i32 intermediates, double-buffered; wider ``S`` tiles
    the lane axis, more replicas halve the slot tile."""
    bs = min(S, 4096)
    bt = 64 if 64 * bs * R <= 64 * 4096 * 5 else 32
    return min(T, bt), bs


@functools.partial(
    jax.jit, static_argnames=("quorum", "interpret", "want_phase")
)
def pallas_window_rmajor(
    votes_rm: jnp.ndarray,  # i8[R, T, S] — replica-major planes
    alive_rm: jnp.ndarray,  # bool[R, S]
    quorum: int,
    interpret: bool = False,
    want_phase: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray] | jnp.ndarray:
    """The closed form as one Pallas TPU kernel on replica-major votes.

    This is the bandwidth-shaped entry: each replica's votes are a
    contiguous, well-tiled ``[T, S]`` i8 plane, so the kernel streams
    them with no minor-axis relayout (the ``[T, S, R]`` layout puts
    R=5 on the lane axis and needs an i8 relayout to fix that).

    ``want_phase=False`` skips the i32 phase plane (4 redundant
    bytes/decision: in the fault-free closed form the phase is
    derivable — 0 iff decided) and returns only the decision plane.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, T, S = votes_rm.shape
    bt, bs = _pick_block(T, S, R)
    alive_t = alive_rm.astype(I8)[:, None, :]  # [R, 1, S]
    plane = pl.BlockSpec(
        (bt, bs), lambda i, j: (i, j), memory_space=pltpu.VMEM
    )
    out_specs = [plane]
    out_shape = [jax.ShapeDtypeStruct((T, S), I8)]
    if want_phase:
        out_specs.append(plane)
        out_shape.append(jax.ShapeDtypeStruct((T, S), I32))
    out = pl.pallas_call(
        _make_kernel(R, quorum, want_phase=want_phase),
        grid=(pl.cdiv(T, bt), pl.cdiv(S, bs)),
        in_specs=[
            pl.BlockSpec(
                (R, bt, bs), lambda i, j: (0, i, j), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (R, 1, bs), lambda i, j: (0, 0, j), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="rabia_fused_window",
    )(votes_rm, alive_t)
    if want_phase:
        return out[0], out[1]
    return out[0]


@functools.partial(
    jax.jit, static_argnames=("quorum", "interpret")
)
def pallas_window(
    votes: jnp.ndarray,  # i8[T, S, R]
    alive: jnp.ndarray,  # bool[S, R]
    quorum: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The closed form on the API ``[T, S, R]`` layout: relayouts to
    replica-major, then runs :func:`pallas_window_rmajor`. Producers
    that can build votes replica-major should call the rmajor entry
    directly and skip the relayout."""
    votes_t = jnp.transpose(votes, (2, 0, 1))  # [R, T, S]
    alive_t = jnp.transpose(alive, (1, 0))  # [R, S]
    return pallas_window_rmajor(
        votes_t, alive_t, quorum, interpret=interpret
    )

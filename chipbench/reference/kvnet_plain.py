"""The plain reference of the lossy-network KV deployment: ``kv_plain``'s
answers, plus the slot at which weak-MVC decides each op of a shard under
the stated network.

It imports nothing of the program. The model, for slot ``k`` of shard
``s`` with ``R`` replicas, ``f = (R - 1) // 2``, views of ``q = R - f``
senders, the quorum ``Q = R // 2 + 1`` and the decision threshold
``F1 = f + 1`` (every draw a 32-bit hash of (seed, s, k, ...)):

1. The slot's request reached its origin replica, drawn uniformly; every
   other replica is stale with probability ``loss``. A fresh replica
   proposes the request, a stale one proposes none.
2. In exchange ``e`` (0: proposals; ``2p - 1`` and ``2p``: phase ``p``'s
   two rounds) receiver ``r`` hears itself and the ``q - 1`` alive senders
   that come first in a seeded arrival order; a lost message (probability
   ``loss``) is ordered after every delivered one. A replica that has
   decided keeps sending its decided value.
3. Phase 0: a replica's state is 1 if at least ``Q`` of the proposals in
   its view are the request, else 0.
4. Phase ``p``: its vote is ``v`` if at least ``Q`` of the states in its
   view are ``v``, else ``?``; then it decides ``v`` if at least ``F1`` of
   the votes in its view are ``v``, else takes ``v`` as its state if any
   vote in its view is ``v``, else the common coin of (seed, s, k, p).
5. The slot is decided when every alive replica has decided: 1 applies
   the shard's next op at the slot, 0 is a null slot and the op is retried
   at the next one.

A slot whose alive replicas all propose the request decides it in phase 1
whatever the views (every view holds the request only), so only the other
slots run the exchanges. Everything is plain numpy over ``[slots, R]`` and
``[slots, R, R]``.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.reference.kv_plain import PlainKV

U32 = np.uint32
GOLD = 0x9E3779B9  # the hash_combine offset, as in the common coin
NET = 0x85EBCA6B  # the network draws start apart from the coin's chain
ORIGIN = 0xFFFFFFFF  # tags of the origin and stale draws: never an exchange
STALE = 0xFFFFFFFE
ORDER = 0x5BD1E995  # the arrival order's salt
CHUNK = 1 << 17  # slots a pass holds at once (passes run on threads)


def mix32(h: np.ndarray) -> np.ndarray:
    """lowbias32 on a uint32 array."""
    h = h ^ (h >> U32(16))
    h = h * U32(0x21F0AAAD)
    h = h ^ (h >> U32(15))
    h = h * U32(0x735A2D97)
    return h ^ (h >> U32(15))


def chain(h: np.ndarray, x) -> np.ndarray:
    return mix32(h ^ (np.asarray(x).astype(U32) + U32(GOLD)))


def threshold(p: float) -> int:
    return min(int(p * 4294967296.0), 4294967295)


def coin(seed: int, shard, slot, phase, p1: float = 0.5) -> np.ndarray:
    """The common coin of (seed, shard, slot, phase): 1 or 0."""
    shard, slot, phase = np.broadcast_arrays(shard, slot, phase)
    h = mix32(np.full(shard.shape, seed, U32) ^ U32(GOLD))
    h = chain(chain(chain(h, shard), slot), phase)
    return (h < U32(threshold(p1))).astype(np.int8)


class Network:
    """Weak-MVC under the lossy network, for any slots of any shards."""

    def __init__(self, n_replicas: int, seed: int, loss: float, p1: float = 0.5,
                 alive=None, max_phases: int = 64) -> None:
        self.R = int(n_replicas)
        self.seed = int(seed) & 0xFFFFFFFF
        self.lost = U32(threshold(loss))
        self.p1 = float(p1)
        self.alive = np.ones(self.R, bool) if alive is None else np.asarray(alive, bool)
        f = (self.R - 1) // 2
        self.q = self.R - f
        self.Q = self.R // 2 + 1
        self.F1 = f + 1
        self.max_phases = int(max_phases)

    def _proposals(self, base: np.ndarray) -> np.ndarray:
        """i8[n, R]: 1 where the replica proposes the request."""
        r = np.arange(self.R, dtype=U32)
        origin = chain(base, ORIGIN) % U32(self.R)
        stale = chain(chain(base, STALE)[:, None], r[None, :]) < self.lost
        stale[np.arange(len(base)), origin] = False
        return (~stale).astype(np.int8)

    def _views(self, base: np.ndarray, e: int) -> np.ndarray:
        """bool[n, R (receiver), R (sender)]: who each receiver hears. A
        sender's place in the arrival order is its key, a lost message's
        with the top bit set; equal keys go by sender index."""
        R = self.R
        r = np.arange(R, dtype=U32)
        h = chain(chain(chain(base, e)[:, None], r)[:, :, None], r[None, None, :])
        key = (mix32(h ^ U32(ORDER)) >> U32(1)) | np.where(
            h < self.lost, U32(0x80000000), U32(0))
        key = (key.astype(np.int64) << 8) | np.arange(R)  # distinct keys
        others = ~np.eye(R, dtype=bool) & self.alive[None, :]
        rank = np.zeros(key.shape, np.int8)  # [n, receiver, sender]
        for j in range(R):  # senders ahead of each sender in the order
            rank += (key[:, :, j : j + 1] < key) & others[None, :, j : j + 1]
        return (others[None] & (rank < self.q - 1)) | np.eye(R, dtype=bool)[None]

    def _count(self, view: np.ndarray, sent: np.ndarray, v: int) -> np.ndarray:
        """i32[n, R]: senders in each receiver's view that sent ``v``."""
        out = np.zeros(sent.shape, np.int8)
        for j in range(self.R):
            out += view[:, :, j] & (sent[:, None, j] == v)
        return out

    def _run(self, shards: np.ndarray, slots: np.ndarray) -> tuple:
        base = mix32(np.full(len(shards), self.seed, U32) ^ U32(NET))
        base = chain(chain(base, shards), slots)
        prop = self._proposals(base)
        value = np.ones(len(base), np.int8)
        phases = np.ones(len(base), np.int32)
        split = ~(prop[:, self.alive] == 1).all(1) | (self.alive.sum() < self.Q)
        if not split.any():
            return value, phases
        idx = np.nonzero(split)[0]
        value[idx], phases[idx] = self._exchanges(base[idx], shards[idx], slots[idx],
                                                  prop[idx])
        return value, phases

    def _exchanges(self, base, shards, slots, prop) -> tuple:
        n, R = prop.shape
        state = (self._count(self._views(base, 0), prop, 1) >= self.Q).astype(np.int8)
        decided = np.full((n, R), -1, np.int8)
        at = np.zeros((n, R), np.int32)
        todo = np.arange(n)  # slots with an alive replica still undecided
        for p in range(1, self.max_phases + 1):
            if not len(todo):
                break
            b, st, dec = base[todo], state[todo], decided[todo]
            active = self.alive[None, :] & (dec < 0)
            sent = np.where(dec >= 0, dec, st)
            view = self._views(b, 2 * p - 1)
            vote = np.full(st.shape, 2, np.int8)  # 2: "?"
            vote[self._count(view, sent, 0) >= self.Q] = 0
            vote[self._count(view, sent, 1) >= self.Q] = 1
            sent = np.where(dec >= 0, dec, vote)
            view = self._views(b, 2 * p)
            d0, d1 = self._count(view, sent, 0), self._count(view, sent, 1)
            flip = coin(self.seed, shards[todo], slots[todo], p, self.p1)[:, None]
            nxt = np.where(d1 > 0, 1, np.where(d0 > 0, 0, flip)).astype(np.int8)
            now = active & ((d1 >= self.F1) | (d0 >= self.F1))
            decided[todo] = np.where(now, np.where(d1 >= self.F1, 1, 0), dec)
            at[todo] = np.where(now, p, at[todo])
            state[todo] = np.where(active, nxt, st)
            todo = todo[(self.alive[None, :] & (decided[todo] < 0)).any(1)]
        live = decided[:, self.alive]
        if ((live != live.max(1, keepdims=True)) & (live >= 0)).any():
            raise AssertionError("two replicas decided different values in one slot")
        value = np.where((live >= 0).all(1), live.max(1), -1).astype(np.int8)
        return value, at[:, self.alive].max(1)

    def decide(self, shards, slots) -> tuple:
        """``(value i8[n], phases i32[n])`` of the slots ``(shards[i],
        slots[i])``: 1, 0 (null), or -1 where the alive replicas had not all
        decided within ``max_phases``; the phases the slot took."""
        shards = np.asarray(shards, np.int64).astype(U32)
        slots = np.asarray(slots, np.int64).astype(U32)
        cuts = range(0, len(slots), CHUNK)
        with ThreadPoolExecutor(min(len(cuts), os.cpu_count() or 1) or 1) as pool:
            parts = list(pool.map(
                lambda a: self._run(shards[a : a + CHUNK], slots[a : a + CHUNK]), cuts))
        if not parts:
            return np.zeros(0, np.int8), np.zeros(0, np.int32)
        return tuple(np.concatenate(x) for x in zip(*parts))

    def slots_for(self, shards, ops) -> list:
        """For each shard, the values of its slots from 0 through the slot
        that decides its ``ops``-th op: ``[i8[slots]]``, each ending in 1
        (empty where ``ops`` is 0)."""
        shards = [int(s) for s in shards]
        out = {s: [] for s in shards}
        found = dict.fromkeys(shards, 0)
        start = 0
        while True:
            need = [s for s in shards if found[s] < ops]
            if not need:
                break
            span = max(64, min(1 << 18, int((ops - min(found[s] for s in need)) * 1.25)))
            sh = np.repeat(need, span)
            sl = np.tile(np.arange(start, start + span), len(need))
            value, _ = self.decide(sh, sl)
            if (value < 0).any():
                raise AssertionError("a slot did not decide within max_phases")
            for i, s in enumerate(need):
                v = value[i * span : (i + 1) * span]
                ones = np.cumsum(v == 1) + found[s]
                if ones[-1] >= ops:
                    v = v[: int(np.searchsorted(ones, ops)) + 1]
                out[s].append(v)
                found[s] += int((v == 1).sum())
            start += span
        return [np.concatenate(out[s]) if out[s] else np.zeros(0, np.int8)
                for s in shards]


class PlainKVNet(PlainKV):
    """``kv_plain``'s map and answers (weak-MVC never reorders a shard's
    ops, so the answers do not depend on the network), the count of waves
    applied (each one op a shard) and the network's model of the slots."""

    def __init__(self, n_shards: int, n_keys: int, value_bytes: int, *,
                 n_replicas: int, seed: int, loss: float, p1: float = 0.5) -> None:
        super().__init__(n_shards, n_keys, value_bytes)
        self.net = Network(n_replicas, seed, loss, p1)
        self.waves = 0

    def apply_wave(self, kind, kid, vlen, val, want=None) -> dict:
        self.waves += 1
        return super().apply_wave(kind, kid, vlen, val, want)


Reference = PlainKVNet

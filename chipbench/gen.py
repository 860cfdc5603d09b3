"""Traffic generation: the key universe, values and waves of one cell, from
``--seed`` alone. One generator reads every traffic file.

A *wave* is one op per shard (a full-width block: the only shape the device
lane admits, ``parallel/mesh_engine.py`` ``submit_block``; anything else
demotes the lane for good). Which shard an op goes to is therefore not
drawn. Which of the shard's records it asks for is drawn as YCSB draws it:
a Zipfian law over the records' popularity ranks (``P(rank i) ~ 1/i^theta``,
YCSB's ``ZipfianGenerator``), the ranks scattered over the shard's keys by a
seed-drawn permutation (YCSB's ``ScrambledZipfianGenerator`` hashes them).
Every op reads or updates a record that the load phase inserted, as YCSB's
transaction phase does.

The generator draws a **pool** of waves before the window and encodes each
once; the runner cycles through the pool, so that drawing traffic costs the
measured window nothing and every run of one seed sends the same ops. Each
submit still gets a block of its own (``block``): a new id, new arrays and
a new copy of the bytes, as a block that arrives from a client has.

Traffic file keys (all data, no code): ``loop`` ("closed" | "open"),
``in_flight_windows`` (closed), ``rate_ops`` and ``batch_blocks`` (open),
``readproportion`` and ``updateproportion`` (YCSB's names; drawn per op),
``requestdistribution`` ("zipfian") with ``zipfian_constant``,
``pool_windows``, ``warmup_windows`` and ``check_block_share`` (the share
of blocks whose replies, every shard's, are compared).
"""

from __future__ import annotations

import random
import uuid

import numpy as np

from chipbench import wire

_ALNUM = np.frombuffer(
    b"0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ", np.uint8
)
_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)
_STEM = 8  # "ssssjjj." : 4 hex digits of the shard, 3 of the key index
_FULL_WIDTH_SHARE = 0.125


class Wave:
    """One op per shard: ``kind`` u8[S], ``kid`` (index into the shard's key
    universe) i64[S], ``vlen`` i64[S], ``val`` u8[S, VW]."""

    __slots__ = ("kind", "kid", "vlen", "val")

    def __init__(self, kind, kid, vlen, val) -> None:
        self.kind, self.kid, self.vlen, self.val = kind, kid, vlen, val


class Generator:
    def __init__(self, seed: int, config: dict, traffic: dict) -> None:
        self.rng = np.random.default_rng(int(seed))
        self.S = int(config["n_shards"])
        self.P = int(config["per_shard_capacity"])
        self.K = int(config["key_bytes"])
        self.VW = int(config["value_bytes"])
        self.W = int(config["window"])
        self.traffic = traffic
        if self.S > 0x10000 or self.P > 0x1000 or self.K < _STEM:
            raise ValueError("key stem holds 4 hex digits of shard, 3 of key")
        self.n_keys = self.P
        self._make_keys()
        self.shards = np.arange(self.S, dtype=np.int64)
        self._ids = random.Random(int(self.rng.integers(0, 2**63)))

    # -- keys and values -----------------------------------------------------

    def _text(self, shape) -> np.ndarray:
        return _ALNUM[self.rng.integers(0, len(_ALNUM), shape)]

    def _lengths(self, shape, width: int) -> np.ndarray:
        n = self.rng.integers(1, width + 1, shape)
        n[self.rng.random(shape) < _FULL_WIDTH_SHARE] = width
        return n

    def _make_keys(self) -> None:
        """``S x P`` distinct text keys of 8..K bytes (a share at the full
        width): a fixed stem names shard and index, the rest is drawn."""
        S, n = self.S, self.n_keys
        key = self._text((S, n, self.K))
        s = np.arange(S)[:, None]
        j = np.arange(n)[None, :]
        for d in range(4):
            key[:, :, d] = _HEX[(s >> (4 * (3 - d))) & 0xF]
        for d in range(3):
            key[:, :, 4 + d] = _HEX[(j >> (4 * (2 - d))) & 0xF]
        key[:, :, 7] = ord(".")
        klen = np.maximum(self._lengths((S, n), self.K), _STEM)
        key[np.arange(self.K)[None, None, :] >= klen[:, :, None]] = 0
        self.key, self.klen = key, klen

    def key_bytes(self, s: int, j: int) -> bytes:
        return self.key[s, j, : self.klen[s, j]].tobytes()

    # -- waves -----------------------------------------------------------------

    def load_waves(self) -> list[Wave]:
        """The load phase: ``P`` waves, wave t inserting key t of every
        shard, so the table ends at capacity with distinct records."""
        return [
            Wave(
                np.full(self.S, wire.SET, np.uint8),
                np.full(self.S, t, np.int64),
                self._lengths(self.S, self.VW),
                self._text((self.S, self.VW)),
            )
            for t in range(self.P)
        ]

    def pool_waves(self) -> list[Wave]:
        """The transaction phase: ``pool_windows x window`` waves, each op a
        read or an update by the traffic file's proportions, of a record
        drawn by its request distribution."""
        t = self.traffic
        read, update = float(t["readproportion"]), float(t["updateproportion"])
        if min(read, update) < 0 or abs(read + update - 1.0) > 1e-9:
            raise ValueError("readproportion + updateproportion must be 1")
        if t["requestdistribution"] != "zipfian":
            raise ValueError("requestdistribution: only zipfian is generated")
        p = 1.0 / np.arange(1, self.P + 1) ** float(t["zipfian_constant"])
        cdf = np.cumsum(p / p.sum())
        by_rank = np.argsort(self.rng.random((self.S, self.P)), axis=1)
        waves = []
        for _ in range(int(t["pool_windows"]) * self.W):
            kind = np.where(self.rng.random(self.S) < update, wire.SET, wire.GET)
            rank = np.minimum(np.searchsorted(cdf, self.rng.random(self.S)), self.P - 1)
            waves.append(
                Wave(kind.astype(np.uint8), by_rank[self.shards, rank],
                     self._lengths(self.S, self.VW), self._text((self.S, self.VW)))
            )
        return waves

    def encode(self, wave: Wave) -> tuple[np.ndarray, np.ndarray]:
        """The wave's op bytes (u8 array) and per-op sizes, on the wire."""
        rows = self.shards
        data, sizes = wire.encode_wave(
            wave.kind, self.klen[rows, wave.kid], self.key[rows, wave.kid],
            wave.vlen, wave.val,
        )
        return data, sizes

    def block(self, data: np.ndarray, sizes: np.ndarray):
        """One arriving block: the program's full-width ``PayloadBlock`` with
        an id, arrays and bytes of its own (nothing of an earlier block's
        object, cached offsets or buffer is handed in again)."""
        from rabia_tpu.core.blocks import PayloadBlock

        return PayloadBlock(
            uuid.UUID(int=self._ids.getrandbits(128)), self.shards.copy(),
            np.full(self.S, -1, np.int64), np.ones(self.S, np.int64),
            sizes.copy(), data.tobytes(),
        )

    def sampler(self):
        """Which blocks have their replies compared: ``picked(i) -> bool``
        for the block at stream index ``i``, a ``check_block_share`` of all
        blocks, drawn once from the seed over a prime period."""
        share = float(self.traffic["check_block_share"])
        rng = np.random.default_rng(self.rng.integers(0, 2**63))
        flags = (rng.random(65521) < share).tolist()
        return lambda i: flags[i % 65521]

"""The plain reference of the KV deployments: a versioned key-value map per
shard, applied one wave (one op per shard) at a time.

It imports nothing of the program. A key is named by its index in the
shard's key universe (the generator guarantees the universe's keys are
distinct), values are bytes. Semantics, per shard, in submission order:

- SET: the shard's version counter goes up by one; the key holds the value
  at that version; the reply is the new version.
- GET: the reply is (found, the key's version, its value).

Shards are independent, so one wave is applied to all shards at once with
plain numpy indexing; there is no cache, batching or pipelining.
"""

from __future__ import annotations

import numpy as np

from chipbench import wire


class PlainKV:
    def __init__(self, n_shards: int, n_keys: int, value_bytes: int) -> None:
        self.n = n_shards
        self.present = np.zeros((n_shards, n_keys), bool)
        self.ver = np.zeros((n_shards, n_keys), np.int64)
        self.vlen = np.zeros((n_shards, n_keys), np.int64)
        self.val = np.zeros((n_shards, n_keys, value_bytes), np.uint8)
        self.shard_version = np.zeros(n_shards, np.int64)
        self._rows = np.arange(n_shards)

    def apply_wave(self, kind, kid, vlen, val, want=None) -> dict:
        """Apply one op per shard; returns ``{shard: reply frame}`` for the
        shards in ``want`` (replies are as of this wave, before later ones).
        """
        rows = self._rows
        found = self.present[rows, kid]
        frames = {}
        if want is not None:
            new_ver = self.shard_version + 1
            for s in want:
                s = int(s)
                k, j = int(kind[s]), int(kid[s])
                if k == wire.SET:
                    f = wire.reply_frame(wire.OK, new_ver[s], None)
                elif k != wire.GET:
                    raise ValueError(f"op kind {k} is not in this reference")
                elif found[s]:
                    v = self.val[s, j, : self.vlen[s, j]].tobytes()
                    f = wire.reply_frame(wire.OK, self.ver[s, j], v)
                else:
                    f = wire.reply_frame(wire.NOT_FOUND, 0, None)
                frames[s] = f
        is_set = kind == wire.SET
        self.shard_version += is_set
        s_set = rows[is_set]
        k_set = kid[is_set]
        self.present[s_set, k_set] = True
        self.ver[s_set, k_set] = self.shard_version[s_set]
        self.vlen[s_set, k_set] = vlen[is_set]
        self.val[s_set, k_set] = val[is_set]
        return frames


Reference = PlainKV

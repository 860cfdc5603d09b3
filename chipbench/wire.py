"""The KV lane's wire format, as the client side of the benchmark speaks it.

Ops (client -> system): ``u8 opcode | u16 klen LE | key | value`` with
opcode 1 SET, 2 GET (the lane also knows 3 DEL and 4 EXISTS; no traffic
mix sends them yet); only SET carries a value. Replies
(system -> client): ``u8 status | u32 version LE | u8 has_value | value``
with status 0 ok, 1 not found. The benchmark builds its blocks and its
expected reply frames from this description, not from the program's codec,
so a change to the program's encoder or decoder cannot move the yardstick.
"""

from __future__ import annotations

import numpy as np

SET, GET = 1, 2
OP_HEADER = 3  # opcode + klen
OK, NOT_FOUND = 0, 1


def encode_wave(kind, klen, key, vlen, val) -> tuple[np.ndarray, np.ndarray]:
    """One op per row -> (concatenated op bytes as u8[n], per-op sizes).

    ``kind`` u8[n], ``klen`` i[n], ``key`` u8[n, K] (zero padded), ``vlen``
    i[n], ``val`` u8[n, VW]; a row's value is sent only where it is a SET.
    """
    n, K = key.shape
    VW = val.shape[1]
    vlen = np.where(kind == SET, vlen, 0)
    rows = np.empty((n, OP_HEADER + K + VW), np.uint8)
    rows[:, 0] = kind
    rows[:, 1] = klen & 0xFF
    rows[:, 2] = klen >> 8
    rows[:, OP_HEADER : OP_HEADER + K] = key
    rows[:, OP_HEADER + K :] = val
    keep = np.ones(rows.shape, bool)
    keep[:, OP_HEADER : OP_HEADER + K] = np.arange(K)[None, :] < klen[:, None]
    keep[:, OP_HEADER + K :] = np.arange(VW)[None, :] < vlen[:, None]
    sizes = (OP_HEADER + klen + vlen).astype(np.int64)
    # row-major boolean indexing keeps header, key, value in order per op
    return rows[keep], sizes


def reply_frame(status: int, version: int, value: bytes | None) -> bytes:
    head = bytes((status,)) + (int(version) & 0xFFFFFFFF).to_bytes(4, "little")
    if value is None:
        return head + b"\x00"
    return head + b"\x01" + value

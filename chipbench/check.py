"""The comparison that decides ``correct``.

Once the window has closed the plain reference replays every block the run
submitted, in submission order, and the run is held to it in three ways:

- **replies**: every sampled reply of a block that settled in the window
  is byte for byte the reference's frame (status, version, value);
- **replicas**: after ``sync_to_host`` every one of the R replica stores
  holds exactly the reference's keys, values, versions and per-shard
  version counters;
- **lane**: the device lane served the whole run (never demoted, no
  divergence, host replicas empty until the sync), and every block
  submitted settled.

Each number is exact, so each limit is 0 (or a floor of 1 for the count of
replies compared).
"""

from __future__ import annotations

import numpy as np


def replay(ref, stream, wanted: dict) -> dict:
    """Feed ``stream`` (waves in submission order) to the reference and
    return ``{stream index: {shard: frame}}`` for the indices in ``wanted``."""
    expected = {}
    for i, wave in enumerate(stream):
        want = wanted.get(i)
        frames = ref.apply_wave(wave.kind, wave.kid, wave.vlen, wave.val, want)
        if want is not None:
            expected[i] = frames
    return expected


def reply_mismatches(served: dict, expected: dict) -> tuple[int, int, str]:
    """(replies compared, replies that differ, the first difference)."""
    compared = wrong = 0
    first = ""
    for i, frames in served.items():
        for s, got in frames.items():
            compared += 1
            want = expected[i][s]
            if got != want:
                wrong += 1
                first = first or (
                    f"block {i} shard {s}: served {got!r}, reference {want!r}"
                )
    return compared, wrong, first


def lane_faults(eng) -> int:
    """How many of the lane's three signs say the run left the device."""
    return (
        int(not eng.device_lane_active)
        + int(eng.divergences != 0)
        + int(any(len(sm.store) for sm in eng.sms))
    )


def _value_mismatches(vals: list, want_val, want_len) -> int:
    """Rows whose stored value is missing or differs. A run holds a million
    rows a replica, so all of them are compared as one buffer first and row
    by row only to count what differs."""
    if all(v is not None for v in vals):
        lens = np.fromiter(map(len, vals), np.int64, len(vals))
        if (lens == want_len).all():
            want = want_val[np.arange(want_val.shape[1])[None, :] < want_len[:, None]]
            if b"".join(vals) == want.tobytes():
                return 0
    return sum(
        v is None or bytes(v) != want_val[i, : want_len[i]].tobytes()
        for i, v in enumerate(vals)
    )


def replica_mismatches(eng, ref, gen) -> tuple[int, str]:
    """Rows, counters and values on which any replica store differs from the
    reference, over all replicas (call after ``sync_to_host``)."""
    s_idx, k_idx = np.nonzero(ref.present)
    n = len(s_idx)
    lanes = np.ascontiguousarray(gen.key[s_idx, k_idx])
    klens = gen.klen[s_idx, k_idx].astype(np.int64)
    want_ver = ref.ver[s_idx, k_idx]
    want_len = ref.vlen[s_idx, k_idx]
    want_val = ref.val[s_idx, k_idx]
    wrong = 0
    first = ""
    for r, sm in enumerate(eng.sms):
        store = sm.store
        bad = abs(len(store) - n)
        bad += int(
            (np.asarray(store.shard_version[: ref.n]) != ref.shard_version).sum()
        )
        if lanes.shape[1] != store.K:
            raise ValueError("replica store key width differs from the config")
        vers, vals = store.bulk_get(
            s_idx, lanes.view(np.uint64).reshape(n, store.L), klens
        )
        bad += int((vers != want_ver).sum())
        bad += _value_mismatches(vals, want_val, want_len)
        if bad and not first:
            first = f"replica {r}: {bad} rows, counters or values differ"
        wrong += bad
    return wrong, first

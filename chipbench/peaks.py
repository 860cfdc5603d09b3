"""The table of peaks and the bytes a window must move.

Peaks are per chip, keyed by ``device_kind`` as JAX reports it; a device
that is not in the table is an error, never a default. Source: Google
Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s.
"""

from __future__ import annotations

HBM_BYTES_PER_S = {
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
}


def hbm_peak(device_kind: str) -> float:
    try:
        return HBM_BYTES_PER_S[device_kind]
    except KeyError:
        raise KeyError(
            f"no HBM peak for device_kind {device_kind!r}; add it to "
            "chipbench/peaks.py with its source"
        ) from None


def table_bytes(config: dict) -> int:
    """The device table of a configuration: per slot a used flag (1 B), the
    key, its length (4 B), the version (4 B), the value and its length
    (4 B); per shard a version counter (4 B)."""
    S = int(config["n_shards"])
    P = int(config["per_shard_capacity"])
    slot = 1 + int(config["key_bytes"]) + 4 + 4 + int(config["value_bytes"]) + 4
    return S * P * slot + S * 4


def window_bytes(config: dict) -> int:
    """The least a window of ``window`` full-width waves must move through
    HBM, from the configuration's shapes alone and whatever program does it:
    the table read once and written once, the op planes in (per op: key and
    value lengths, 2 B each, key and value bytes), the reply meta out (per
    op: a version word and a found/length word, 8 B) and 12 B of flags. A
    program that passes over the table once per wave moves more and so reads
    a smaller share; it cannot read above 100 %."""
    S = int(config["n_shards"])
    W = int(config["window"])
    op = 2 + 2 + int(config["key_bytes"]) + int(config["value_bytes"])
    return 2 * table_bytes(config) + W * S * op + W * S * 8 + 12

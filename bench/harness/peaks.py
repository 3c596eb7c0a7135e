"""The chip's published peaks, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import pathlib

TABLE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    """The table's entry for ``device_kind``; a kind not in the table
    is an error, never a default."""
    table = json.loads(TABLE.read_text())
    try:
        return table[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {TABLE.name}; "
            f"known: {sorted(table)}") from None


def least_time_s(device_kind: str, *, flops: float = 0.0,
                 bytes_moved: float = 0.0, dtype: str = "bf16") -> float:
    """The least time the chip could take: the larger of flops over the
    peak rate and bytes over the HBM bandwidth."""
    p = peaks(device_kind)
    return max(flops / p["flops_per_s"][dtype],
               bytes_moved / p["hbm_bytes_per_s"])

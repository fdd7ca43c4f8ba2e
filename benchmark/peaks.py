"""The peak table (peaks.json), keyed by `device_kind` as JAX reports it.
A device missing from the table is an error, never a default."""

from __future__ import annotations

import json
import os

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "peaks.json")) as _f:
    TABLE = json.load(_f)


def peak(device_kind: str, what: str) -> float:
    if device_kind not in TABLE:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json")
    return float(TABLE[device_kind][what])

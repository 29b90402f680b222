"""The one table of chip peaks, keyed by `device_kind` (peaks.json)."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def for_kind(device_kind: str) -> dict:
    """-> {"flops_per_s", "bytes_per_s", "hbm_bytes", "source"}. A kind
    that is not in the table is an error, never a default."""
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in {_PATH}: "
            f"add a row with its source")
    return table[device_kind]

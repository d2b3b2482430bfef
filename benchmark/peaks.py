"""The table of published device peaks, keyed by ``device_kind``."""
from __future__ import annotations

import json
import os
from typing import Dict

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str, table_path: str = _TABLE) -> Dict[str, float]:
    """The peaks of one chip.  A device that is not in the table is an
    error, never a default: a share of an assumed peak is not a measurement."""
    with open(table_path) as f:
        devices = json.load(f)["devices"]
    if device_kind not in devices:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {table_path}; known: {sorted(devices)}")
    return devices[device_kind]

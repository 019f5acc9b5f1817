"""The chip's published peaks, keyed by ``device_kind`` (``peaks.json``).
A device that is not in the table is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).with_name("peaks.json")


def peak(device_kind: str, table: Path = _TABLE) -> dict:
    """``{"flops_per_s", "hbm_bytes_per_s", "hbm_bytes"}`` of one chip."""
    devices = json.loads(table.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{table.name}; known: {sorted(devices)}")
    return devices[device_kind]

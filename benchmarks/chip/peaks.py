"""The chips' published peaks, keyed by ``device_kind`` (``peaks.json``)."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


def lookup(device_kind: str) -> dict:
    """Peaks of one chip of this kind; a kind not in the table is an error."""
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {TABLE.name}; "
                       f"known: {sorted(devices)}")
    return devices[device_kind]

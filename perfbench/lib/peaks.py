"""The table of published peaks, keyed by ``device_kind``. A device that
is not in the table is an error, never another chip's figure."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

_TABLE = Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = json.loads(_TABLE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks on record for device kind {device_kind!r}; "
            f"add a row with its source to {_TABLE.name} "
            f"(known: {sorted(table)})")
    return dict(table[device_kind])

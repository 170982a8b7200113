"""Shrinking a cell for the CPU rehearsal: the tiny preset's widths, toy
sequence lengths and engine geometry, every traffic length divided by a
fixed number. Parameters in ``perfbench/rehearsal.json``; the control
flow stays the cell's own."""
from __future__ import annotations

import copy
import json
from pathlib import Path
from typing import Dict, Tuple


def shrink(path: Path, config: Dict, traffic: Dict) -> Tuple[Dict, Dict]:
    sizes = json.loads(Path(path).read_text())
    config = copy.deepcopy(config)
    traffic = copy.deepcopy(traffic)
    config.update(sizes["model"])
    for group in ("training", "serving"):
        if group in config:
            config[group].update(sizes[group])
    div = int(sizes["length_divisor"])
    for key in ("prompt", "output", "length"):
        if key in traffic:
            for field in ("median", "min", "max"):
                traffic[key][field] = max(1, int(traffic[key][field]) // div)
    for key, value in sizes["traffic"].items():
        if key in traffic:
            traffic[key] = value
    return config, traffic

"""The chip's published peaks, keyed by `device_kind`. A device that is
not in `peaks.json` is an error, never a default."""

from __future__ import annotations

import json
import os
from typing import Dict

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> Dict[str, float]:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table)}); add its published peaks with "
            "their source")
    return table[device_kind]

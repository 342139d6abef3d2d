"""The table of peaks, keyed by ``device_kind``. A device that is not in
``peaks.json`` is an error, never a default: a guessed peak makes every
utilization wrong."""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_TABLE, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        known = sorted(k for k in table if not k.startswith("_"))
        raise ValueError(f"no peak is recorded for device_kind {device_kind!r}; "
                         f"perfbench/peaks.json has {known}")
    return table[device_kind]

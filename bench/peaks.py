"""The chip's published peaks, keyed by the ``device_kind`` JAX reports."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind missing from the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS_FILE.name}; add them with their source")
    return table[device_kind]

"""Peaks of the chips the benchmark knows, and the least bytes a window
step has to move.

The bytes come from the cell's semantics, not from the program's array
shapes, so the count does not change when the implementation does: any
implementation has to read each admitted event once and write each
emitted window result once.
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"

#: bytes of one admitted event: int32 event time, int32 key, float32
#: value, one validity byte
EVENT_BYTES = 4 + 4 + 4 + 1
#: bytes of one window result: a float32 per key
RESULT_BYTES = 4


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def step_bytes(events: int, window_ends: int, n_keys: int) -> int:
    """Least bytes that steps admitting ``events`` events and emitting
    ``window_ends`` window ends over ``n_keys`` keys move."""
    return events * EVENT_BYTES + window_ends * n_keys * RESULT_BYTES

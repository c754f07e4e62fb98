"""The harness's sink: window results into numpy columns.

Each result is kept as ``(window_end, key, value, arrival time)`` in
preallocated chunks, so the sink holds no Python object per result.  It
also records the last watermark each instance was given: the engine
forwards a watermark only after every result it closes, so a sink
watermark at ``W`` means every result of a window ending at or before
``W`` has arrived.
"""

from __future__ import annotations

import bisect
import time
from typing import Callable, Dict, List, Tuple

import numpy as np

CHUNK_ROWS = 1 << 20


class ResultColumns:
    """Append-only columns in fixed-size chunks."""

    def __init__(self):
        self._chunks: List[Dict[str, np.ndarray]] = []
        self._fill = CHUNK_ROWS
        self.rows = 0
        #: sink instance -> ([wall times], [watermarks]) of each watermark
        #: given, in arrival order
        self.watermarks: Dict[int, Tuple[List[float], List[int]]] = {}
        #: how many sink instances the job runs (known once one reports)
        self.instances = 1

    def _new_chunk(self) -> None:
        self._chunks.append({
            "end": np.empty(CHUNK_ROWS, np.int64),
            "key": np.empty(CHUNK_ROWS, np.int64),
            "value": np.empty(CHUNK_ROWS, np.float64),
            "t": np.empty(CHUNK_ROWS, np.float64)})
        self._fill = 0

    def append(self, end, key, value, t: float) -> None:
        n, i = len(end), 0
        while i < n:
            if self._fill == CHUNK_ROWS:
                self._new_chunk()
            c, f = self._chunks[-1], self._fill
            take = min(n - i, CHUNK_ROWS - f)
            c["end"][f:f + take] = end[i:i + take]
            c["key"][f:f + take] = key[i:i + take]
            c["value"][f:f + take] = value[i:i + take]
            c["t"][f:f + take] = t
            self._fill += take
            self.rows += take
            i += take

    def columns(self) -> Dict[str, np.ndarray]:
        """Every row so far, one array per column."""
        if not self._chunks:
            return {k: np.empty(0, d) for k, d in
                    (("end", np.int64), ("key", np.int64),
                     ("value", np.float64), ("t", np.float64))}
        last = self._fill
        return {k: np.concatenate([c[k] for c in self._chunks[:-1]]
                                  + [self._chunks[-1][k][:last]])
                for k in ("end", "key", "value", "t")}

    def frontier(self, at: float = float("inf")) -> int:
        """Lowest watermark over the sink instances as of wall time ``at``
        (-1 while some instance has had none)."""
        if len(self.watermarks) < self.instances:
            return -1
        low = None
        for times, marks in self.watermarks.values():
            i = bisect.bisect_right(times, at)
            if not i:
                return -1
            low = marks[i - 1] if low is None else min(low, marks[i - 1])
        return low


def make_sink(columns: ResultColumns, fields: Callable):
    """Supplier of sink processors that write into ``columns``;
    ``fields(item)`` gives an item's ``(window end, key, value)``."""
    from repro.core.processor import Processor

    class ColumnSink(Processor):
        def process(self, ordinal, inbox) -> None:
            items = list(inbox)
            inbox.clear()
            n = len(items)
            if not n:
                return
            now = time.monotonic()
            rows = np.array([fields(ev.value) for ev in items], np.float64)
            columns.append(rows[:, 0].astype(np.int64),
                           rows[:, 1].astype(np.int64), rows[:, 2], now)

        def try_process_watermark(self, wm) -> bool:
            columns.instances = self.ctx.total_parallelism
            times, marks = columns.watermarks.setdefault(
                self.ctx.global_index, ([], []))
            times.append(time.monotonic())
            marks.append(wm.ts)
            return True

    return ColumnSink

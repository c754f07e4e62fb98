"""NEXMark Q5, hot items: bids per auction over a sliding window, then the
auction with the most bids in each window.  How the benchmark builds it in
the program, what the sink reads from each item, the comparison, and the
plain reference.

``build`` goes through the program's ordinary entry point, the
``Pipeline`` with the window placed on the device; with the
configuration's ``hot_items`` it runs the second stage too.
``reference`` imports nothing of the program: it regenerates the bids from
the benchmark's own stream and counts them per (window end, auction) in
exact integer arithmetic.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import numpy as np

from .. import check
from ..nexmark import KIND_BID, NexmarkStream

#: events generated per block while the reference regenerates the stream
REF_BLOCK = 1 << 21


def build(cfg: Dict, source: Callable, sink: Callable):
    """The cell's job: paced source -> bid filter -> key by auction ->
    sliding window on the device, counting -> (with ``hot_items``) the
    auction with the most bids per window end -> sink."""
    from repro.nexmark import queries
    if cfg["aggregate"] != "count":
        raise ValueError(f"unknown aggregate {cfg['aggregate']!r}")
    return queries.q5(source, sink, window_ms=cfg["window_ms"],
                      slide_ms=cfg["slide_ms"],
                      with_global_max=cfg["hot_items"], placement="device",
                      device=dict(cfg["device"]))


def fields(cfg: Dict) -> Callable:
    """``(window end, auction, count)`` of one item the sink receives."""
    if cfg["hot_items"]:
        return lambda answer: answer        # already (end, auction, count)
    return lambda r: (r.window_end, r.key, r.value)


def compare(cfg: Dict, cols: Dict[str, np.ndarray], totals: np.ndarray,
            due_end: int) -> Dict[str, int]:
    """The numbers compared: ``cols`` received against the reference."""
    fn = check.compare_top if cfg["hot_items"] else check.compare
    return fn(cols, totals, cfg["slide_ms"], due_end)


def answers(cfg: Dict, totals: np.ndarray, end: np.ndarray,
            key: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``(key, value)`` that ``totals``, in the program's place, would have
    sent at the window ends (and keys) the program sent."""
    n_ends, n_keys = totals.shape
    row = np.clip(end // cfg["slide_ms"] - 1, 0, n_ends - 1)
    if cfg["hot_items"]:
        return totals[row].argmax(axis=1), totals[row].max(axis=1)
    return key, totals[row, np.clip(key, 0, n_keys - 1)]


def reference(cfg: Dict, stream: NexmarkStream, n_ends: int) -> np.ndarray:
    """Exact bid counts, ``(n_ends, n_keys)`` int64: row ``i`` is the
    window ending at ``(i + 1) * slide`` ms, which holds the bids with
    ``end - window_ms <= ts < end``."""
    slide, size = cfg["slide_ms"], cfg["window_ms"]
    if size % slide:
        raise ValueError("window_ms must be a multiple of slide_ms")
    frames_per_window = size // slide
    n_keys = stream.n_keys
    frames = np.zeros((n_ends, n_keys), np.int64)
    end_seq = stream.first_seq_at(n_ends * slide)
    for lo in range(0, end_seq, REF_BLOCK):
        c = stream.columns(np.arange(lo, min(lo + REF_BLOCK, end_seq)))
        bid = c["kind"] == KIND_BID
        cell = (c["ts"][bid] // slide) * n_keys + c["key"][bid]
        frames += np.bincount(cell, minlength=frames.size
                              ).reshape(frames.shape)
    run = np.zeros((n_ends + 1, n_keys), np.int64)
    np.cumsum(frames, axis=0, out=run[1:])
    last = np.arange(1, n_ends + 1)
    first = np.maximum(last - frames_per_window, 0)
    return run[last] - run[first]

"""The benchmark's traffic in the program's input format.

``Feed`` is the ``gen_fn`` handed to the program's ``PacedGeneratorSource``:
``feed(seq)`` gives one ``(ts, key, model object)`` triple and
``feed.gen_block(seqs)`` an ``EventBlock`` with the ``kind``/``seq``/
``bidder`` columns the NEXMark queries read -- the same shapes the
program's own generator produces, computed by :mod:`bench.nexmark`.

Every call is logged as (wall time, first sequence number asked for), on
the cluster's clock (``time.monotonic``).  From that log the harness takes
the schedule's anchor -- the source asks for sequence 0 the moment it
anchors its schedule -- and how late the generator ran behind it.
"""

from __future__ import annotations

import time
from typing import Any, List, Tuple

import numpy as np

from .nexmark import (AUCTION_PROPORTION, KIND_AUCTION, KIND_BID,
                      PERSON_PROPORTION, TOTAL_PROPORTION, NexmarkStream)

_MASK64 = 0xFFFFFFFFFFFFFFFF


class _RowPayload:
    """``payload_fn`` of a block: row ``i``'s model object, rebuilt from its
    ``seq`` column (only on the program's per-event fallback path)."""

    __slots__ = ("feed",)

    def __init__(self, feed: "Feed"):
        self.feed = feed

    def __call__(self, blk, i: int) -> Any:
        return self.feed.row(int(blk.cols["seq"][i]))[2]


class Feed:
    def __init__(self, stream: NexmarkStream):
        from repro.core.events import EventBlock
        from repro.nexmark import model
        self.stream = stream
        self.rate = stream.rate
        self.n_keys = stream.n_keys
        self._block = EventBlock
        self._model = model
        self._payload = _RowPayload(self)
        self.calls_t: List[float] = []
        self.calls_seq: List[int] = []

    def _log(self, seq: int) -> None:
        self.calls_t.append(time.monotonic())
        self.calls_seq.append(seq)

    def row(self, seq: int) -> Tuple[int, int, Any]:
        """Scalar form of :meth:`NexmarkStream.columns` in plain Python
        ints: the source takes this path for bursts too small for a block,
        so it has to cost about what the program's own generator does."""
        ts = int(seq * 1000 / self.rate)
        x = (seq + self.stream.offset + 0x9E3779B97F4A7C15) & _MASK64
        x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
        x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
        r = x ^ (x >> 31)
        n = self.n_keys
        key = r % n
        slot = seq % TOTAL_PROPORTION
        m = self._model
        if slot >= PERSON_PROPORTION + AUCTION_PROPORTION:
            return ts, key, m.Bid(key, (r >> 16) % n,
                                  100 + ((r >> 32) % 9900), ts)
        if slot < PERSON_PROPORTION:
            return ts, key, m.Person(
                key, f"person-{key}", f"p{key}@example.com",
                m.CITIES[r % len(m.CITIES)],
                m.US_STATES[(r >> 8) % len(m.US_STATES)], ts)
        return ts, key, m.Auction(key, (r >> 16) % n, (r >> 24) % 10,
                                  100 + r % 900, ts + 60_000, ts)

    def __call__(self, seq: int) -> Tuple[int, int, Any]:
        self._log(seq)
        return self.row(seq)

    def gen_block(self, seqs):
        seqs = np.asarray(seqs, dtype=np.int64)
        if len(seqs):
            self._log(int(seqs[0]))
        c = self.stream.columns(seqs)
        kind = c["kind"]
        value = np.where(kind == KIND_BID, c["price"],
                         np.where(kind == KIND_AUCTION, c["reserve"], 0)
                         ).astype(np.float64)
        return self._block(c["ts"], c["key"], value,
                           payload_fn=self._payload,
                           cols={"kind": kind, "seq": seqs,
                                 "bidder": c["bidder"]})

    # -- what the log says ----------------------------------------------------
    def anchor(self) -> float:
        """Wall time at which the schedule started: when sequence 0 was
        asked for (the source asks for it as it anchors)."""
        seqs = np.asarray(self.calls_seq, np.int64)
        hits = np.nonzero(seqs == 0)[0]
        if not len(hits):
            raise RuntimeError("the source never asked for sequence 0")
        return self.calls_t[int(hits[0])]

    def lag_ms(self, t_from: float, t_to: float) -> np.ndarray:
        """(wall time, lag in ms) of every call made in ``[t_from, t_to)``:
        how long after its scheduled time each first sequence was asked
        for."""
        t = np.asarray(self.calls_t, np.float64)
        s = np.asarray(self.calls_seq, np.float64)
        sel = (t >= t_from) & (t < t_to)
        due = self.anchor() + s[sel] / self.rate
        return np.stack([t[sel], (t[sel] - due) * 1000.0])

"""The NEXMark event stream as numpy columns, kept with the benchmark.

A copy of the arithmetic of the program's ``NexmarkGenerator``
(``src/repro/nexmark/generator.py``): splitmix64 over ``seq + offset(seed)``,
the 1 person : 3 auctions : 46 bids mix per 50 events, uniform keys over
``n_keys`` and the ideal event time ``ts = int(seq * 1000 / rate)`` ms.  The
traffic the benchmark offers and the reference it checks against both come
from here, so neither changes when the program's generator does.  Imports
nothing of the program.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

PERSON_PROPORTION = 1
AUCTION_PROPORTION = 3
BID_PROPORTION = 46
TOTAL_PROPORTION = PERSON_PROPORTION + AUCTION_PROPORTION + BID_PROPORTION
KIND_PERSON, KIND_AUCTION, KIND_BID = 0, 1, 2

_U64 = np.uint64
_MASK64 = 0xFFFFFFFFFFFFFFFF


def mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 over a uint64 vector (wrapping arithmetic is native)."""
    x = x + _U64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> _U64(27))) * _U64(0x94D049BB133111EB)
    return x ^ (x >> _U64(31))


class NexmarkStream:
    """Seeded NEXMark events as a pure function of their sequence number."""

    def __init__(self, rate: float, n_keys: int, seed: int):
        if rate <= 0 or n_keys <= 0:
            raise ValueError(f"rate and n_keys must be positive "
                             f"(got {rate}, {n_keys})")
        self.rate = rate
        self.n_keys = n_keys
        self.seed = seed
        #: ``seed`` offsets the splitmix64 input; any Python int works,
        #: including seeds past 32 bits
        self.offset = (seed * 0xBF58476D1CE4E5B9) & _MASK64

    def ts(self, seqs: np.ndarray) -> np.ndarray:
        """Ideal event time (ms) of each sequence number."""
        return (np.asarray(seqs, np.int64).astype(np.float64) * 1000.0
                / self.rate).astype(np.int64)

    def columns(self, seqs) -> Dict[str, np.ndarray]:
        """Every field the queries read, one array per field."""
        seqs = np.asarray(seqs, dtype=np.int64)
        r = mix64(seqs.astype(_U64) + _U64(self.offset))
        slot = seqs % TOTAL_PROPORTION
        kind = np.where(
            slot >= PERSON_PROPORTION + AUCTION_PROPORTION, KIND_BID,
            np.where(slot < PERSON_PROPORTION, KIND_PERSON,
                     KIND_AUCTION)).astype(np.int8)
        n = _U64(self.n_keys)
        return {
            "seq": seqs,
            "ts": self.ts(seqs),
            "kind": kind,
            "key": (r % n).astype(np.int64),
            "bidder": ((r >> _U64(16)) % n).astype(np.int64),
            "price": (_U64(100) + ((r >> _U64(32)) % _U64(9900))
                      ).astype(np.int64),
            "reserve": (_U64(100) + (r % _U64(900))).astype(np.int64),
        }

    def first_seq_at(self, ts_ms: int) -> int:
        """Smallest sequence number whose event time is ``ts_ms`` or later."""
        s = max(0, int(ts_ms * self.rate / 1000.0) - 2)
        while int(self.ts(np.asarray([s]))[0]) < ts_ms:
            s += 1
        return s


def bids_between(lo: int, hi: int) -> int:
    """Number of bids among the sequence numbers ``lo <= seq < hi``."""
    def below(n: int) -> int:
        full, rest = divmod(max(n, 0), TOTAL_PROPORTION)
        first_bid = PERSON_PROPORTION + AUCTION_PROPORTION
        return full * BID_PROPORTION + max(0, rest - first_bid)
    return below(hi) - below(lo)

"""Log-bucketed latency histogram (HdrHistogram's scheme).

A copy of ``benchmarks/bench_latency.LatencyHistogram`` with ``record``
vectorised: values in microseconds are bucketed by magnitude with
``2**sub_bucket_bits`` linear sub-buckets per power of two, so a
percentile is off by at most ``1/2**sub_bucket_bits`` of its value and is
biased to the bucket's upper edge (never optimistic).
"""

from __future__ import annotations

import numpy as np


class LatencyHistogram:
    def __init__(self, max_value_us: int = 600_000_000,
                 sub_bucket_bits: int = 7):
        self.sub_bucket_bits = sub_bucket_bits
        self.sub_bucket_count = 1 << sub_bucket_bits
        buckets = 1
        top = self.sub_bucket_count
        while top < max_value_us:
            top <<= 1
            buckets += 1
        self.max_value_us = max_value_us
        # bucket 0 holds [0, sub_bucket_count) at resolution 1; bucket
        # b >= 1 holds [sub_bucket_count * 2**(b-1), ... * 2**b) in
        # sub_bucket_count/2 live sub-buckets of width 2**b
        self.counts = np.zeros((buckets + 1) * self.sub_bucket_count,
                               dtype=np.int64)
        self.total = 0

    def _index(self, v: np.ndarray) -> np.ndarray:
        """Bucket index of each non-negative integer value."""
        # frexp's exponent is the bit length, exactly, for v < 2**53
        bits = np.frexp(v.astype(np.float64))[1].astype(np.int64)
        bucket = np.where(v >= self.sub_bucket_count,
                          bits - self.sub_bucket_bits, 0)
        sub = v >> bucket
        return (bucket << self.sub_bucket_bits) + sub

    def record(self, values_us) -> None:
        """Add every value (microseconds) of an array."""
        v = np.asarray(values_us, np.float64)
        if not v.size:
            return
        v = np.clip(v.astype(np.int64), 0, self.max_value_us)
        self.counts += np.bincount(self._index(v),
                                   minlength=len(self.counts))
        self.total += int(v.size)

    def percentile(self, pct: float) -> float:
        """Value (us) at the given percentile, upper-bucket-edge biased."""
        if self.total == 0:
            raise ValueError("no samples recorded")
        target = int(np.ceil(pct / 100.0 * self.total))
        idx = int(np.searchsorted(np.cumsum(self.counts), max(target, 1)))
        bucket = idx >> self.sub_bucket_bits
        sub = idx & (self.sub_bucket_count - 1)
        width = 1 if bucket == 0 else 1 << bucket
        base = sub if bucket == 0 else sub << bucket
        return float(base + width - 1)

"""Benchmarks: one per paper table/figure + device tier."""

"""Source layer: highest lag (ms) of the generator behind its schedule in the window (moves latency_p50_ms)."""
from bench.readers import gen_lag_ms as read  # noqa: F401

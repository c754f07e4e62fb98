"""Device step layer, paced cell: % of the traced window with no operation on the chip (moves latency_p50_ms)."""
from bench.readers import device_idle_share as read  # noqa: F401

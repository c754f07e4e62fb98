"""Bridge layer, paced cell: % of device batch rows holding an admitted bid (moves latency_p50_ms)."""
from bench.readers import batch_fill as read  # noqa: F401

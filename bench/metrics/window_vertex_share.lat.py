"""Engine layer, paced cell: % of sampled worker time in the device window vertex (moves latency_p50_ms)."""
from bench.readers import window_vertex_share as read  # noqa: F401

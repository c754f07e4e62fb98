"""Median latency (ms) from the ideal time of a window end to the answer's arrival at the sink, over every answer due in the window."""
from bench.readers import latency_p50_ms as read  # noqa: F401

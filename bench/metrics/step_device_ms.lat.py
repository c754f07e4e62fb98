"""Device step layer, paced cell: mean device ms of one execution of the step program (moves latency_p50_ms)."""
from bench.readers import step_device_ms as read  # noqa: F401

"""Device step layer, saturated cell: mean device ms of one execution of the step program (moves events_per_s)."""
from bench.readers import step_device_ms as read  # noqa: F401

"""Kernel layer, saturated cell: % of the HBM roofline the step programs reach (moves events_per_s)."""
from bench.readers import step_roofline as read  # noqa: F401

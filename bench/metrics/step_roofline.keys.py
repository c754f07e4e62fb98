"""Kernel layer, per-key cell: % of the HBM roofline the step programs reach; 4 B per key per window end is the per-key output's least write (moves events_per_s)."""
from bench.readers import step_roofline as read  # noqa: F401

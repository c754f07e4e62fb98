"""Bridge layer, saturated cell: % of device batch rows holding an admitted bid (moves events_per_s)."""
from bench.readers import batch_fill as read  # noqa: F401

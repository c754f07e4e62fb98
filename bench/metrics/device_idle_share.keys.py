"""Device step layer, per-key cell: % of the traced window with no operation on the chip (moves events_per_s)."""
from bench.readers import device_idle_share as read  # noqa: F401

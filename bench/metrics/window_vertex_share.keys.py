"""Engine layer, per-key cell: % of sampled worker time in the device window vertex (moves events_per_s)."""
from bench.readers import window_vertex_share as read  # noqa: F401

"""NEXMark events per second that the whole pipeline completed: events whose event time the sink's watermark passed in the window, over its seconds."""
from bench.readers import events_per_s as read  # noqa: F401

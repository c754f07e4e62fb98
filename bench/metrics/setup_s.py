"""Seconds from process start to the window's open: imports, TPU init, compile or cache read, submit and warm-up."""
from bench.readers import setup_s as read  # noqa: F401

"""Scheduler: rows holding a request over max_active, mean over the window's steps (%)."""
from serving.readers import batch_occupancy as read  # noqa: F401

"""Scheduler: admission minus scheduled arrival, p95 over the window's requests (ms)."""
from serving.readers import queue_wait_p95_ms as read  # noqa: F401

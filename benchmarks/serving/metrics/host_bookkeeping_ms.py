"""Paged state: host bookkeeping the state counts per step (gather_s), mean over the window's steps (ms)."""
from serving.readers import host_bookkeeping_ms as read  # noqa: F401

"""Device: 1 - busy / traced window from the profiler trace (%); closed-loop batch cells."""
from serving.readers import device_idle_share as read  # noqa: F401

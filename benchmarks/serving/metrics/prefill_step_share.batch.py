"""Session step loop: share of the window's wall time in steps that carry a prompt chunk (%); closed-loop batch cells."""
from serving.readers import prefill_step_share as read  # noqa: F401

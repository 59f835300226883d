"""Kernels: least time of the window's paged_attention calls at bf16 over the kernel's device time in the trace (%)."""
from serving.readers import paged_attention_roofline as read  # noqa: F401

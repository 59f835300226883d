"""Fused step: model FLOPs of the tokens fed in the traced window over chips x peak bf16 FLOP/s (%); closed-loop batch cells."""
from serving.readers import mfu as read  # noqa: F401

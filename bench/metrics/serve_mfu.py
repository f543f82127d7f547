"""Whole routed step, throughput cell: model FLOPs of the requests routed
in the traced window over the window and the chip's bf16 peak, in %."""
from harness.mfu import serve_mfu as read  # noqa: F401
